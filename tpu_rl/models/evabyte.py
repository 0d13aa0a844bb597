"""EvaByte (``evabyte``) policy core: every layer EVA attention — a query
reads the exact keys of its own ``window_size``-step block and one learned
summary for every ``chunk_size``-step chunk of the blocks before it, under one
softmax — and a dense SwiGLU MLP, each behind a ``1 + w`` RMSNorm.

Widths come from ``Config.arch``, the model's own ``config.json`` under its
published key names (``config.EVABYTE_ARCH_KEYS``). The trunk (the embedding,
the unroll and act loops, the acting carry, the heads) is
``models/backbone.py``'s; the norm and the rotation are ``models/layers.py``'s;
the mixer is this file's.

    x = Dense(obs)
    per layer i:  x = x + EVA(N(x))
                  x = x + W_2(silu(W_1 N(x)) * W_3 N(x))
    logits = log_softmax(Dense(N(x)));  value = Dense(N(x))

``N(x) = x rsqrt(mean x^2 + eps) (1 + w)``, ``w`` starting at 0.

EVA attention (``EvaAttention``, scope ``eva``), H heads of D, no bias, block
W = ``window_size``, chunk C = ``chunk_size``, ``s = D^-1/2``:

- **The grid is the episode's.** ``p(t)``: steps since the episode's first
  step (since the window's first step for the fragment a window opens with).
  Block ``b(t) = p(t) // W``, chunk ``c(t) = p(t) // C``; a chunk is complete
  once its C-th step exists and never crosses a seam. Block and chunk
  boundaries are therefore data (``is_fir``), not indices of the window.
- ``q, k, v = W_q u, W_k u, W_v u`` (``eva_qkv``), q and k rotated at ``p(t)``
  over the whole head (``attn_rope``).
- **Chunk summaries** (``eva_pool``), per head with learned ``mu, phi`` in
  R^D, over the C members of a complete chunk:
  ``k~ = sum_m softmax_m(s mu.k_m) k_m``, ``v~ = sum_m softmax_m(s phi.k_m) v_m``.
- **One softmax over both sets**: query ``t`` reads the exact pairs
  ``E(t) = {m <= t of its episode and block}`` and the summaries
  ``S(t) = {chunks of its episode in blocks before b(t)}``;
  ``o_t = [sum_E e^{s q.k_m} v_m + sum_S e^{s q.k~_c} v~_c] / [sum_E e^{s q.k_m} + sum_S e^{s q.k~_c}]``.
- ``EVA(u) = W_o [o_1 .. o_H]`` (``eva_o``).

Training form: two calls merged by their logsumexps. The exact half is the
square causal kernel as it stands on a finer segment id — a block boundary is
one more seam: ``parallel/sequence.flash_attention_lse`` over the ids
``(episode, block)`` (monotone, so the seam skip steps over every tile a
boundary empties), which also hands back each query's logsumexp. The summary
half finds the at most T / C complete chunks (a static bound), gathers their
members (contiguous C-step spans at data-dependent starts) and pools them;
then every query reads the summaries of its episode up to the last chunk that
ended before its block began — an index bound a query, made from ``is_fir``,
which the splash kernels take as the queries' indices under their causal mask
function: ``parallel/sequence.summary_attention_lse``, a (T, T / C) rectangle
with key-side segment ids of its own (both under ``attn_flash_pallas``; the
second inside ``eva_summary`` with the merge). Where the kernels do not take
the shapes (off-TPU, a candidate count they cannot tile) ``read_summaries``
scores a block of queries at a time against the candidates that can lie before
it, under the mask written from the definition of ``S`` — the oracle of the
kernels' form. One normaliser; the kept pairs exactly ``E`` and ``S``; never a
(T, T) score matrix. Gradients reach ``mu``, ``phi``, k and v through the
pooling, which has a backward of its own (``_summaries_bwd``): complete chunks
are disjoint spans, so the members' gather has an inverse — a step lies in at
most one chunk, the one whose rank is the number of chunks that ended before
it — and a step's gradient is made where the step lies, from its own key and
value and its chunk's row of the summaries' cotangents; nothing is scattered
and no member is gathered a second time. Static shapes throughout; nothing
here reads a flag.

Acting (``step``): the worker zeroes the carry at an episode's first step, so
the step counter is ``p``. Per layer an exact ring ``(W, H, D)`` x 2 that
*restarts* every block (slot ``p mod W``; valid: slots ``<= p mod W``) beside a
summary store ``(ctx / C, H, D)`` x 2 that persists (slot ``p // C``, written
at a chunk's last step from the ring's last C slots; read from the next block
on). Past ``ctx`` steps the store wraps and the oldest summaries are
overwritten, as a K/V ring forgets its oldest keys.

``unroll_routed`` returns one record a layer: what the mask kept, under the
span names ``block`` (``attn-pairs``: the exact pairs; the kernels' tile
counters beside it) and ``summary`` (``attn-pairs``: the summaries read).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_rl.models.backbone import Backbone, ring
from tpu_rl.models.layers import RMSNorm, attention_counts, rope
from tpu_rl.parallel.sequence import _NEG_INF, flash_attention_lse, summary_attention_lse

QUERY_BLOCK = 2048  # queries scored against the summaries at a time


def head_dim(arch: dict) -> int:
    return arch["hidden_size"] // arch["num_attention_heads"]


def episode_grid(seg, block: int, chunk: int):
    """From ``seg`` (B, T) int, the window's episode ids: each step's position
    in its episode ``p`` (from the window's first step for the opening
    fragment), its block ``p // block``, the id of its (episode, block) —
    monotone, as the kernels' seam skip wants it — and whether it ends a
    complete chunk. All (B, T)."""
    t = jnp.arange(seg.shape[1], dtype=jnp.int32)
    starts = jnp.concatenate(
        [jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    pos = t - jax.lax.cummax(jnp.where(starts, t, 0), axis=1)
    fine = jnp.cumsum((pos % block == 0).astype(jnp.int32), axis=1)
    return pos, pos // block, fine, pos % chunk == chunk - 1


def pair_counts(seg, block: int, chunk: int) -> dict:
    """What one EVA layer's mask keeps over a batch of windows, as the record
    the layer hands back (``obs/learn.attention_scalars``): under ``block`` the
    exact pairs ``sum |E(t)| = sum (p mod W) + 1`` and the kernels' tiles on
    the (episode, block) ids, under ``summary`` ``sum |S(t)| = sum (W / C) b(t)``."""
    _, blk, fine, _ = episode_grid(seg, block, chunk)
    counts = attention_counts(fine, None, "block")
    counts["attn-pairs"]["summary"] = jnp.sum(blk.astype(jnp.float32)) * (block // chunk)
    return counts


def _pool(members_k, members_x, w, scale):
    """``sum_m softmax_m(scale w.k_m) x_m`` over axis -3 (a chunk's members):
    ``members_*`` (..., C, H, D), ``w`` (H, D); and the logits' logsumexp over
    the members (..., H), from which a member's weight is made again. Float32."""
    k32 = members_k.astype(jnp.float32)
    logits = scale * jnp.einsum("...chd,hd->...ch", k32, w)
    pooled = jnp.einsum(
        "...ch,...chd->...hd", jax.nn.softmax(logits, axis=-2), members_x.astype(jnp.float32))
    return pooled, jax.nn.logsumexp(logits, axis=-2)


def _summaries_forward(static, k, v, mu, phi, first, there, rank, inside):
    """``k~``, ``v~`` (B, n, H, D) in k's dtype: the members of candidate j are
    the steps ``first[j] .. first[j] + C - 1`` of ``k``, ``v`` (B, T, H, D) — one
    contiguous span a chunk —, an absent candidate (``there`` false) is zero.
    ``static``: (C, scale). ``rank``, ``inside`` (B, T): the inverse of the
    gather, which only the backward reads (:func:`_summaries_bwd`)."""
    return _summaries_fwd(static, k, v, mu, phi, first, there, rank, inside)[0]


_summaries = jax.custom_vjp(_summaries_forward, nondiff_argnums=(0,))


def _summaries_fwd(static, k, v, mu, phi, first, there, rank, inside):
    C, scale = static

    def members(x):  # (B, T, H, D) -> (B, n, C, H, D)
        span = lambda row, s: jax.lax.dynamic_slice_in_dim(row, s, C, axis=0)  # noqa: E731
        return jax.vmap(lambda row, ss: jax.vmap(lambda s: span(row, s))(ss))(x, first)

    mk, mv = members(k), members(v)
    (ks, lse_k), (vs, lse_v) = _pool(mk, mk, mu, scale), _pool(mk, mv, phi, scale)
    keep = there[..., None, None]
    out = tuple(jnp.where(keep, x, 0.0).astype(k.dtype) for x in (ks, vs))
    # kept for the backward: k and v, which the layer holds anyway, and what is as
    # small as a summary (float32 k~, v~ and the two logsumexps) — never the
    # float32 members, 2 x 268 MB a layer at the published widths
    return out, (k, v, mu, phi, ks, vs, lse_k, lse_v, rank, inside)


def _summaries_bwd(static, res, ct):
    """dk, dv, dmu, dphi without a scatter and without the members. Complete
    chunks are disjoint spans, so a step of k, v lies in at most one
    (``inside``), the ``rank``-th, and all a member's gradient needs of its
    chunk is a row of a (B, n, ...) array: the cotangents ``g`` of ``k~``,
    ``v~``, the two logsumexps (a member's weight is ``exp(logit - logsumexp)``,
    its logit a product of its own key) and the two sums of the softmax's
    backward, ``sum_m a_m (g.x_m) = g.x~``. Each step gathers those rows by its
    rank and the gradients are written where k and v lie; an absent candidate's
    rows are never pointed at, a step in no complete chunk gets zero. Float32
    throughout, as the forward."""
    _, scale = static
    k, v, mu, phi, ks, vs, lse_k, lse_v, rank, inside = res
    (B, T, H, D), n = k.shape, ks.shape[1]
    with jax.named_scope("eva_pool"):
        g_k, g_v = ct
        small = jnp.stack(  # (B, n, 4, H): what a step needs of its chunk beside g
            [lse_k, lse_v, jnp.sum(g_k.astype(jnp.float32) * ks, axis=-1),
             jnp.sum(g_v.astype(jnp.float32) * vs, axis=-1)], axis=2)
        rank = jnp.minimum(rank, n - 1)
        at_steps = lambda x: jnp.take_along_axis(  # noqa: E731
            x, rank.reshape(B, T, *(1,) * (x.ndim - 2)), axis=1)
        g_k, g_v = (at_steps(g).astype(jnp.float32) for g in (g_k, g_v))
        lse_k, lse_v, sum_k, sum_v = jnp.moveaxis(at_steps(small), 2, 0)
        k32, v32, inside = k.astype(jnp.float32), v.astype(jnp.float32), inside[..., None]
        dot = lambda x, y: jnp.sum(x * y, axis=-1)  # noqa: E731
        a = jnp.where(inside, jnp.exp(scale * dot(k32, mu) - lse_k), 0.0)
        b = jnp.where(inside, jnp.exp(scale * dot(k32, phi) - lse_v), 0.0)
        d_k = scale * a * (dot(g_k, k32) - sum_k)  # the logits' gradients
        d_v = scale * b * (dot(g_v, v32) - sum_v)
        dk = a[..., None] * g_k + d_k[..., None] * mu + d_v[..., None] * phi
        dv = b[..., None] * g_v
        dk, dv = dk.astype(k.dtype), dv.astype(k.dtype)
        dmu, dphi = (jnp.sum(d[..., None] * k32, axis=(0, 1)) for d in (d_k, d_v))
    return dk, dv, dmu, dphi, None, None, None, None


_summaries.defvjp(_summaries_fwd, _summaries_bwd)


class EvaAttention(nn.Module):
    """``__call__`` (training) takes a window and its episode ids, ``step``
    (acting) one step over the exact ring and the summary store."""

    hidden: int
    heads: int
    block: int
    chunk: int
    rope_theta: float
    init_std: float
    dtype: Any = None

    def setup(self):
        D = self.hidden // self.heads
        proj = dict(
            use_bias=False, dtype=self.dtype, kernel_init=nn.initializers.normal(self.init_std))
        self.q_proj = nn.Dense(self.hidden, name="q_proj", **proj)
        self.k_proj = nn.Dense(self.hidden, name="k_proj", **proj)
        self.v_proj = nn.Dense(self.hidden, name="v_proj", **proj)
        self.o_proj = nn.Dense(self.hidden, name="o_proj", **proj)
        pool = nn.initializers.truncated_normal(D ** -0.5)
        self.pool_k = self.param("pool_k", pool, (self.heads, D))  # mu
        self.pool_v = self.param("pool_v", pool, (self.heads, D))  # phi
        self.scale = D ** -0.5

    @nn.nowrap
    def _qkv(self, u, pos):
        """q, k, v as heads ``(..., H, D)``, q and k rotated at ``pos``."""
        with jax.named_scope("eva_qkv"):
            q, k, v = (
                p(u).reshape(*u.shape[:-1], self.heads, -1)
                for p in (self.q_proj, self.k_proj, self.v_proj))
        q, k = (rope(x, pos, self.rope_theta) for x in (q, k))
        return q, k, v

    @nn.nowrap
    def _out(self, o):
        with jax.named_scope("eva_o"):
            return self.o_proj(o.reshape(*o.shape[:-2], -1))

    @nn.nowrap
    def summaries(self, k, v, seg, blk, ends):
        """The window's complete chunks, pooled: ``k~``, ``v~`` (B, T / C, H, D)
        in k's dtype, in order of their last step, and each one's episode id and
        block (B, T / C); an absent one (fewer complete chunks than T / C) has
        episode id -1, which no query has."""
        B, T = seg.shape
        C, n = self.chunk, T // self.chunk
        nth = jnp.arange(1, n + 1, dtype=jnp.int32)
        upto = jnp.cumsum(ends.astype(jnp.int32), axis=1)  # complete chunks up to each step
        # the step that ends the j-th complete chunk; T where there is none
        last = jax.vmap(lambda c: jnp.searchsorted(c, nth, side="left"))(upto).astype(jnp.int32)
        there = last < T
        at = jnp.minimum(last, T - 1)
        first = jnp.clip(last - (C - 1), 0, T - C)
        # the way back: a step lies in a complete chunk iff one ends within the C steps
        # from it on, and that chunk's rank is the number that ended before the step
        rank = upto - ends
        ahead = jnp.concatenate([upto[:, C - 1:], jnp.repeat(upto[:, -1:], C - 1, axis=1)], axis=1)
        inside = ahead > rank
        ks, vs = _summaries(
            (C, self.scale), k, v, self.pool_k, self.pool_v, first, there, rank, inside)
        take = lambda x: jnp.take_along_axis(x, at, axis=1)  # noqa: E731
        return ks, vs, jnp.where(there, take(seg), -1), take(blk)

    @nn.nowrap
    def read_summaries(self, q, ks, vs, seg, blk, seg_c, blk_c):
        """The ``jnp`` form of the summaries' read (and the kernels' oracle):
        every query against the summaries it may read, a block of queries at
        a time: ``o`` (B, T, H, D) float32, normalised over ``S(t)`` alone, and
        the logsumexp over ``S(t)`` (B, H, T) (``_NEG_INF`` where ``S(t)`` is
        empty: the merge then gives this part no weight). The j-th complete
        chunk ends at step ``C j + C - 1`` at the earliest and a query reads
        only chunks that ended before it, so the queries before step ``e``
        meet the first ``e / C`` candidates at most; and a query of the
        window's first ``W`` steps is in block 0 and reads none."""
        B, T, H, D = q.shape

        @jax.checkpoint  # the backward keeps q and the summaries, not a block's scores
        def part(q, ks, vs, keep):
            s = jnp.einsum("bqhd,bnhd->bhqn", q, ks, preferred_element_type=jnp.float32)
            s = jnp.where(keep[:, None], s * jnp.float32(self.scale), _NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            o = jnp.einsum(
                "bhqn,bnhd->bqhd", (p / l).astype(vs.dtype), vs,
                preferred_element_type=jnp.float32)
            return o, (m + jnp.log(l))[..., 0]

        outs, lses = [], []
        for start in range(0, T, QUERY_BLOCK):
            stop = min(start + QUERY_BLOCK, T)
            n = min(stop // self.chunk, ks.shape[1])
            if stop <= self.block or n == 0:
                outs.append(jnp.zeros((B, stop - start, H, D), jnp.float32))
                lses.append(jnp.full((B, H, stop - start), _NEG_INF, jnp.float32))
                continue
            keep = (seg[:, start:stop, None] == seg_c[:, None, :n]) & (
                blk_c[:, None, :n] < blk[:, start:stop, None])
            o, lse = part(q[:, start:stop], ks[:, :n], vs[:, :n], keep)
            outs.append(o)
            lses.append(lse)
        return jnp.concatenate(outs, axis=1), jnp.concatenate(lses, axis=2)

    def __call__(self, u, seg, interpret: bool = False):
        pos, blk, fine, ends = episode_grid(seg, self.block, self.chunk)
        q, k, v = self._qkv(u, pos)
        o_e, lse_e = flash_attention_lse(
            q, k, v, pos, fine, sm_scale=self.scale, interpret=interpret)
        if u.shape[1] <= self.block:  # every query is in its episode's first block
            return self._out(o_e)
        with jax.named_scope("eva_pool"):
            ks, vs, seg_c, blk_c = self.summaries(k, v, seg, blk, ends)
        with jax.named_scope("eva_summary"):
            # a query reads the chunks that ended before its block began
            t = jnp.arange(u.shape[1], dtype=jnp.int32)
            ended = jnp.cumsum(ends.astype(jnp.int32), axis=1) - ends  # before each step
            reach = jnp.take_along_axis(ended, t - pos % self.block, axis=1) - 1
            read = summary_attention_lse(
                q, ks, vs, seg, seg_c, reach, self.scale, interpret=interpret)
            if read is None:
                read = self.read_summaries(q, ks, vs, seg, blk, seg_c, blk_c)
            o_s, lse_s = read
            lse = jnp.logaddexp(lse_e, lse_s)
            w_e, w_s = (jnp.exp(x - lse).transpose(0, 2, 1)[..., None] for x in (lse_e, lse_s))
            o = (o_e.astype(jnp.float32) * w_e + o_s.astype(jnp.float32) * w_s).astype(q.dtype)
        return self._out(o)

    def step(self, u, k_ring, v_ring, k_sum, v_sum, count):
        """One acting step. ``k_ring``, ``v_ring`` (B, W, H, D): the exact keys
        and values of the block so far, rotated at their own step; ``k_sum``,
        ``v_sum`` (B, ctx / C, H, D): the summaries of the episode's complete
        chunks; ``count`` (B,) int: steps of this episode already taken."""
        W, C, n = self.block, self.chunk, k_sum.shape[1]
        q, k_new, v_new = self._qkv(u, count)
        at = jnp.mod(count, W)
        slot = jnp.arange(W)[None]
        write = (slot == at[:, None])[..., None, None]
        k_ring = jnp.where(write, k_new[:, None].astype(k_ring.dtype), k_ring)
        v_ring = jnp.where(write, v_new[:, None].astype(v_ring.dtype), v_ring)
        with jax.named_scope("eva_pool"):
            # the chunk this step may end lies in the ring's slots at - C + 1 .. at
            first = jnp.maximum(at - (C - 1), 0)
            span = lambda ring: jax.vmap(  # noqa: E731
                lambda r, s: jax.lax.dynamic_slice_in_dim(r, s, C, axis=0))(ring, first)
            mk, mv = (span(x).astype(self.dtype or jnp.float32) for x in (k_ring, v_ring))
            ends = jnp.mod(count, C) == C - 1
            into = (jnp.arange(n)[None] == jnp.mod(count // C, n)[:, None]) & ends[:, None]
            into = into[..., None, None]
            rounded = lambda x: x.astype(self.dtype or jnp.float32).astype(k_sum.dtype)  # noqa: E731
            k_sum = jnp.where(
                into, rounded(_pool(mk, mk, self.pool_k, self.scale)[0])[:, None], k_sum)
            v_sum = jnp.where(
                into, rounded(_pool(mk, mv, self.pool_v, self.scale)[0])[:, None], v_sum)
        # slot j of the store holds the newest complete chunk whose index is j mod n;
        # it is read if that chunk lies in a block before the query's
        done = (count + 1) // C  # complete chunks so far
        j = jnp.arange(n)[None]
        held = j + n * ((done[:, None] - 1 - j) // n)
        readable = (done[:, None] > j) & (held < (W // C) * (count // W)[:, None])
        valid = jnp.concatenate([slot <= at[:, None], readable], axis=1)
        keys = jnp.concatenate([k_ring, k_sum], axis=1).astype(q.dtype)
        values = jnp.concatenate([v_ring, v_sum], axis=1).astype(q.dtype)
        scores = jnp.einsum(
            "bhd,bthd->bht", q, keys, preferred_element_type=jnp.float32
        ) * jnp.float32(self.scale)
        w = jax.nn.softmax(jnp.where(valid[:, None], scores, -jnp.inf), axis=-1)
        o = jnp.einsum(
            "bht,bthd->bhd", w.astype(q.dtype), values, preferred_element_type=jnp.float32)
        return self._out(o.astype(q.dtype)), k_ring, v_ring, k_sum, v_sum


class EvaByteLayer(nn.Module):
    """One published layer: EVA attention, then the SwiGLU MLP, each behind a
    ``1 + w`` RMSNorm."""

    arch: dict
    index: int  # the trunk's argument a layer; every layer is alike and reads none
    dtype: Any = None

    def setup(self):
        a = self.arch
        norm = dict(eps=a["rms_norm_eps"], dtype=self.dtype, zero_centered=True)
        self.input_norm = RMSNorm(name="input_layernorm", **norm)
        self.post_norm = RMSNorm(name="post_attention_layernorm", **norm)
        self.attention = EvaAttention(
            hidden=a["hidden_size"], heads=a["num_attention_heads"], block=a["window_size"],
            chunk=a["chunk_size"], rope_theta=float(a["rope_theta"]),
            init_std=float(a["init_std"]), dtype=self.dtype, name="attention")
        proj = dict(
            use_bias=False, dtype=self.dtype,
            kernel_init=nn.initializers.normal(float(a["init_std"])))
        self.gate_proj = nn.Dense(a["intermediate_size"], name="gate_proj", **proj)
        self.up_proj = nn.Dense(a["intermediate_size"], name="up_proj", **proj)
        self.down_proj = nn.Dense(a["hidden_size"], name="down_proj", **proj)

    @nn.nowrap
    def _mlp(self, x):
        with jax.named_scope("mlp"):
            u = self.post_norm(x)
            return x + self.down_proj(jax.nn.silu(self.gate_proj(u)) * self.up_proj(u))

    def __call__(self, x, seg):
        """Training window. Hands back ``x`` and the layer's record: the pairs
        its mask kept."""
        a = self.arch
        with jax.named_scope("eva"):
            x = x + self.attention(self.input_norm(x), seg)
        return self._mlp(x), pair_counts(seg, a["window_size"], a["chunk_size"])

    def step(self, x, *carry):
        with jax.named_scope("eva"):
            mixed, *carry = self.attention.step(self.input_norm(x), *carry)
        return (self._mlp(x + mixed), *carry)


class EvaByteActorCritic(Backbone):
    Layer = EvaByteLayer
    zero_centered = True

    @staticmethod
    def acting_state(arch, ctx):
        """Per layer an exact ring of one block, keys and values, that restarts
        every ``window_size`` steps, beside the summaries of the episode's
        chunks, one for every ``chunk_size`` steps of ``ctx``."""
        chunk = arch["chunk_size"]
        assert ctx % chunk == 0, f"act_ctx {ctx} is no whole number of {chunk}-step chunks"
        exact = (arch["window_size"], arch["num_attention_heads"], head_dim(arch))
        pooled = (ctx // chunk, arch["num_attention_heads"], head_dim(arch))
        return [ring(exact, exact, pooled, pooled)] * arch["num_hidden_layers"]


ActorCritic = EvaByteActorCritic
