"""What more than one catalog family builds its layers from, each with its
widths and forms as fields so that a family's file states only its own
arrangement: the norm, the seam-stopped convolution, rotary positions,
grouped-query attention, multi-head latent attention, the sparse-expert block
(over ``ops/moe.py``) and
what an attention layer's mask did, counted from the segment ids. A module
only one family uses stays in that family's file; Mamba-2 is ``mamba2.py``.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_rl.obs.learn import ATTENTION_COUNTERS
from tpu_rl.ops import moe
from tpu_rl.parallel.sequence import attention_tiles, flash_attention_tpu


def _rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


class RMSNorm(nn.Module):
    eps: float
    dtype: Any = None  # output dtype (statistics are float32)
    zero_centered: bool = False  # the leaf starts at 0 and scales by 1 + itself

    @nn.compact
    def __call__(self, x):
        if self.zero_centered:
            scale = 1.0 + self.param("scale", nn.initializers.zeros, (x.shape[-1],))
        else:
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return _rms_norm(x, scale, self.eps).astype(self.dtype or jnp.float32)


def seam_conv(xbc, tail, seg, weight, bias, scope: str = "ssd_conv"):
    """Causal depthwise convolution whose taps stop at an episode seam.
    ``xbc`` (B, T, C); ``tail`` (B, K-1, C) the steps before the window
    (segment 0); ``seg`` (B, T) int; ``weight`` (K, C). Float32. ``scope``
    names it in the device trace (Mamba-2's by default)."""
    K = weight.shape[0]
    T = xbc.shape[1]
    with jax.named_scope(scope):
        xp = jnp.concatenate([tail, xbc], axis=1).astype(jnp.float32)
        segp = jnp.concatenate([jnp.zeros_like(seg[:, : K - 1]), seg], axis=1)
        out = jnp.broadcast_to(bias, xbc.shape).astype(jnp.float32)
        for k in range(K):
            same = segp[:, k : k + T] == seg
            out = out + jnp.where(same[..., None], xp[:, k : k + T], 0.0) * weight[k]
        return out


@jax.named_scope("attn_rope")
def rope(x, pos, theta: float, rotary_dim: int | None = None):
    """Rotary positions, rotate-half pairing, no scaling: ``x`` (B, ..., H, D)
    with ``pos`` (B, ...) int. Over the whole head (feature ``i`` with
    ``i + D/2``), or with ``rotary_dim`` over the head's first ``rotary_dim``
    features alone (``i`` with ``i + rotary_dim/2``, frequencies
    ``theta^(-2i / rotary_dim)``) while the others pass as they are. Angles,
    sines and the rotation in float32; ``x``'s dtype comes back."""
    passed = None
    if rotary_dim is not None:
        x, passed = x[..., :rotary_dim], x[..., rotary_dim:]
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = pos.astype(jnp.float32)[..., None, None] * inv  # (B, ..., 1, D/2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)
    return turned if passed is None else jnp.concatenate([turned, passed], axis=-1)


class GQAttention(nn.Module):
    """Grouped-query attention, causal and masked to the episode. Widths as
    fields: the head size need not be ``hidden / n_q``. Two more fields, both
    off by default (granite's and nemotron's layers: no positions, ``nope``,
    and the whole episode so far): ``rope_theta`` rotates q and k (``rope``),
    ``window`` keeps the last ``window`` keys, the query's own among them.
    Three more, off by default too (``models/qwen3_next.py`` sets all three):
    ``rotary_dim`` rotates each head's first ``rotary_dim`` features alone;
    ``qk_norm`` (an epsilon) puts an RMSNorm over each head of q and of k
    before the rotation (leaves ``q_norm``, ``k_norm``), zero-centred unless
    ``qk_norm_zero_centered`` is off (``models/lfm2_moe.py``: the plain form,
    over heads of 64 rotated whole); ``gated``
    doubles ``q_proj`` — each head's columns are its query, then its gate —
    and multiplies the attention's output by ``sigmoid(gate)`` before
    ``o_proj``.

    The rotation's position is the step's index in the training window, and
    in acting the steps of the episode so far: the same scores, because the
    rotation enters a score only through ``q_pos - k_pos`` and the episode
    mask kills every pair that crosses a seam — within an episode the two
    count from different origins and differ by a constant."""

    hidden: int
    n_q: int
    n_kv: int
    head_dim: int
    scale: float  # of the scores, before the softmax
    bias: bool = False
    dtype: Any = None
    rope_theta: float | None = None
    window: int | None = None
    rotary_dim: int | None = None
    qk_norm: float | None = None
    gated: bool = False
    qk_norm_zero_centered: bool = True

    def setup(self):
        proj = dict(use_bias=self.bias, dtype=self.dtype)
        self.q_proj = nn.Dense(
            (2 if self.gated else 1) * self.n_q * self.head_dim, name="q_proj", **proj)
        if self.qk_norm is not None:
            norm = dict(
                eps=self.qk_norm, dtype=self.dtype, zero_centered=self.qk_norm_zero_centered)
            self.q_norm = RMSNorm(name="q_norm", **norm)
            self.k_norm = RMSNorm(name="k_norm", **norm)
        self.k_proj = nn.Dense(self.n_kv * self.head_dim, name="k_proj", **proj)
        self.v_proj = nn.Dense(self.n_kv * self.head_dim, name="v_proj", **proj)
        self.o_proj = nn.Dense(self.hidden, name="o_proj", **proj)

    @nn.nowrap
    def _queries(self, u, heads: tuple):
        """``q_proj(u)`` as heads ``(..., *heads, head_dim)`` and, where the
        layer is gated, each head's gate beside its query (else None)."""
        q = self.q_proj(u)
        if not self.gated:
            return q.reshape(*u.shape[:-1], *heads, self.head_dim), None
        q, gate = jnp.split(q.reshape(*u.shape[:-1], *heads, 2 * self.head_dim), 2, axis=-1)
        return q, gate

    @nn.nowrap
    def _positioned(self, q, k, pos):
        """q and k normed per head and rotated, as the fields say."""
        if self.qk_norm is not None:
            q, k = self.q_norm(q), self.k_norm(k)
        if self.rope_theta is not None:
            q, k = (rope(x, pos, self.rope_theta, self.rotary_dim) for x in (q, k))
        return q, k

    @staticmethod
    def _gate(o, gate):
        return o if gate is None else o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)

    def __call__(self, u, seg):
        B, T, _ = u.shape
        q, gate = self._queries(u, (self.n_q,))
        # every key/value head serves n_q // n_kv consecutive query heads
        k, v = (
            p(u).reshape(B, T, self.n_kv, self.head_dim)
            for p in (self.k_proj, self.v_proj)
        )
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        q, k = self._positioned(q, k, pos)
        o = flash_attention_tpu(
            q, k, v, pos, seg, causal=True, sm_scale=self.scale, window=self.window
        )
        return self.o_proj(self._gate(o, gate).reshape(B, T, -1))

    def step(self, u, k_cache, v_cache, count):
        """One acting step over a K/V ring of ``ctx`` slots (B, ctx, kv, D);
        ``count`` (B,) int: steps of this episode already cached. The ring is
        an exact sliding window of ``ctx`` keys (a ``window`` layer's ring has
        ``window`` slots): without positions because a key carries none, with
        them because a key is stored as rotated at its own step and a score
        reads only the difference to the query's."""
        B = u.shape[0]
        ctx = k_cache.shape[1]
        rep = self.n_q // self.n_kv
        q, gate = self._queries(u, (self.n_kv, rep))
        k_new, v_new = (
            p(u).reshape(B, 1, self.n_kv, self.head_dim) for p in (self.k_proj, self.v_proj)
        )
        q, k_new = self._positioned(q, k_new, count[:, None])
        write = (jnp.arange(ctx)[None] == jnp.mod(count, ctx)[:, None])[:, :, None, None]
        k_cache = jnp.where(write, k_new.astype(k_cache.dtype), k_cache)
        v_cache = jnp.where(write, v_new.astype(v_cache.dtype), v_cache)
        valid = jnp.arange(ctx)[None] <= count[:, None]
        scores = jnp.einsum(
            "bgrd,btgd->bgrt", q, k_cache.astype(q.dtype), preferred_element_type=jnp.float32
        ) * jnp.float32(self.scale)
        w = jax.nn.softmax(jnp.where(valid[:, None, None], scores, -jnp.inf), axis=-1)
        o = jnp.einsum(
            "bgrt,btgd->bgrd", w.astype(q.dtype), v_cache.astype(q.dtype),
            preferred_element_type=jnp.float32,
        )
        o = self._gate(o.astype(q.dtype), gate)
        return self.o_proj(o.reshape(B, -1)), k_cache, v_cache


class MLAttention(nn.Module):
    """``__call__`` (training) runs the expanded form through
    ``flash_attention_tpu``, ``step`` (acting) the absorbed form over the
    latent ring. ``q_rank=None`` (``models/ling_flash.py``): the queries come
    straight from the hidden state — one leaf ``q_proj``, no query latent and
    no ``q_a_norm``. ``v_dim`` need not be ``nope_dim + rope_dim``: scores run
    over the query/key size (their scale is its inverse root), outputs are
    ``v_dim`` wide, and on a TPU ``flash_attention_tpu`` pads both with zero
    features to one size the kernels take. ``head_gate``: one more leaf
    ``g_proj`` (hidden -> heads), and each head's output is multiplied by
    ``sigmoid`` of its scalar before ``o_proj``."""

    hidden: int
    heads: int
    q_rank: int | None
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    eps: float
    dtype: Any = None
    head_gate: bool = False

    def setup(self):
        proj = dict(use_bias=False, dtype=self.dtype)
        norm = dict(eps=self.eps, dtype=self.dtype)
        q_width = self.heads * (self.nope_dim + self.rope_dim)
        if self.q_rank:
            self.q_a_proj = nn.Dense(self.q_rank, name="q_a_proj", **proj)
            self.q_a_norm = RMSNorm(name="q_a_norm", **norm)
            self.q_b_proj = nn.Dense(q_width, name="q_b_proj", **proj)
        else:
            self.q_proj = nn.Dense(q_width, name="q_proj", **proj)
        if self.head_gate:
            self.g_proj = nn.Dense(self.heads, name="g_proj", **proj)
        self.kv_a_proj = nn.Dense(self.kv_rank + self.rope_dim, name="kv_a_proj", **proj)
        self.kv_a_norm = RMSNorm(name="kv_a_norm", **norm)
        self.kv_b_proj = nn.Dense(
            self.heads * (self.nope_dim + self.v_dim), name="kv_b_proj", **proj)
        self.o_proj = nn.Dense(self.hidden, name="o_proj", **proj)
        self.scale = (self.nope_dim + self.rope_dim) ** -0.5

    @nn.nowrap
    @jax.named_scope("mla_down")
    def _latents(self, u):
        """The normed query latent (the hidden state itself where the queries
        have none), the normed key/value latent and the shared key before its
        rotation."""
        c_kv, k_rope = jnp.split(self.kv_a_proj(u), [self.kv_rank], axis=-1)
        c_q = self.q_a_norm(self.q_a_proj(u)) if self.q_rank else u
        return c_q, self.kv_a_norm(c_kv), k_rope

    @nn.nowrap
    def _queries(self, c_q, pos):
        """Each head's unrotated and rotated query parts ``(..., heads, .)``."""
        with jax.named_scope("mla_up"):
            up = self.q_b_proj if self.q_rank else self.q_proj
            q = up(c_q).reshape(*c_q.shape[:-1], self.heads, -1)
            q_nope, q_rope = jnp.split(q, [self.nope_dim], axis=-1)
        return q_nope, rope(q_rope, pos, self.rope_theta)

    @nn.nowrap
    def _gated(self, o, u):
        """``o`` (..., heads, v_dim) under each head's gate, where there is one."""
        if not self.head_gate:
            return o
        gate = jax.nn.sigmoid(self.g_proj(u).astype(jnp.float32))
        return o * gate[..., None].astype(o.dtype)

    def __call__(self, u, seg):
        B, T, _ = u.shape
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        c_q, c_kv, k_rope = self._latents(u)
        q_nope, q_rope = self._queries(c_q, pos)
        k_rope = rope(k_rope[:, :, None, :], pos, self.rope_theta)  # one head: every head's
        with jax.named_scope("mla_up"):
            kv = self.kv_b_proj(c_kv).reshape(B, T, self.heads, -1)
            k_nope, v = jnp.split(kv, [self.nope_dim], axis=-1)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (B, T, self.heads, self.rope_dim))], axis=-1)
        o = flash_attention_tpu(q, k, v, pos, seg, causal=True, sm_scale=self.scale)
        with jax.named_scope("mla_o"):
            return self.o_proj(self._gated(o, u).reshape(B, T, -1))

    def step(self, u, ring, count):
        """One acting step over a latent ring of ``ctx`` slots (B, ctx,
        kv_rank + rope_dim); ``count`` (B,) int: steps of this episode already
        stored. The shared key is stored as rotated at its own step: a score
        reads only the difference to the query's."""
        B, ctx = ring.shape[:2]
        cd = self.dtype or jnp.float32
        c_q, c_kv, k_rope = self._latents(u)
        q_nope, q_rope = self._queries(c_q, count)
        k_rope = rope(k_rope[:, None, :], count, self.rope_theta)[:, 0]
        row = jnp.concatenate([c_kv, k_rope], axis=-1)
        write = (jnp.arange(ctx)[None] == jnp.mod(count, ctx)[:, None])[:, :, None]
        ring = jnp.where(write, row[:, None].astype(ring.dtype), ring)
        latent, keys = jnp.split(ring.astype(cd), [self.kv_rank], axis=-1)
        w_kv = self.kv_b_proj.variables["params"]["kernel"].astype(cd).reshape(
            self.kv_rank, self.heads, -1)
        w_uk, w_uv = jnp.split(w_kv, [self.nope_dim], axis=-1)
        f32 = dict(preferred_element_type=jnp.float32)
        absorbed = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk, **f32).astype(cd)
        scores = (
            jnp.einsum("bhr,btr->bht", absorbed, latent, **f32)
            + jnp.einsum("bhd,btd->bht", q_rope, keys, **f32)
        ) * jnp.float32(self.scale)
        valid = jnp.arange(ctx)[None] <= count[:, None]
        w = jax.nn.softmax(jnp.where(valid[:, None], scores, -jnp.inf), axis=-1)
        mixed = jnp.einsum("bht,btr->bhr", w.astype(cd), latent, **f32).astype(cd)
        o = jnp.einsum("bhr,rhv->bhv", mixed, w_uv, **f32).astype(cd)
        with jax.named_scope("mla_o"):
            return self.o_proj(self._gated(o, u).reshape(B, -1)), ring


def kept_pairs(seg, window: int | None):
    """Query-key pairs the mask of one attention layer keeps over a batch of
    windows, from ``seg`` (B, T) alone: a query sees the steps of its episode
    so far, its own among them, and of those at most ``window``. Float32."""
    t = jnp.arange(seg.shape[1], dtype=jnp.int32)
    starts = jnp.concatenate(
        [jnp.ones_like(seg[:, :1], bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    seen = t - jax.lax.cummax(jnp.where(starts, t, 0), axis=1) + 1
    if window is not None:
        seen = jnp.minimum(seen, window)
    return jnp.sum(seen.astype(jnp.float32))


def attention_counts(seg, window: int | None, span: str) -> dict:
    """What one attention layer's mask did over a batch of windows, to merge
    into the record the layer hands back beside ``x`` (counter -> the layer's
    span name, ``global`` or ``window`` -> the count): the query-key pairs it
    kept (``attn-pairs``) and, of the splash kernels' grid
    (``parallel/sequence.attention_tiles``), the tiles of the static band
    (``attn-tiles-band``), those of them no seam emptied, which the kernels
    compute (``attn-tiles-run``), and the grid steps the backward takes a head
    (``attn-bwd-steps``). ``obs/learn.attention_scalars`` sums them by span."""
    counts = (kept_pairs(seg, window), *attention_tiles(seg, window))
    return {c: {span: n} for c, n in zip(ATTENTION_COUNTERS, counts)}


def expert_share(arch: dict, held_key: str = "n_routed_experts") -> tuple[int, int, int]:
    """(experts the router scores, experts held here, global id of the first
    held): the rank's share of ``arch["expert_parallel"]``, or everything.
    ``held_key``: the source's name for the count of routed experts."""
    held = arch[held_key]
    share = arch.get("expert_parallel")
    if not share:
        return held, held, 0
    return share["published_n_routed_experts"], held, share["rank"] * held


def _correction_bias_init(key, shape, dtype=jnp.float32):
    """The published model trains this bias beside the loss, by a rule its
    ``config.json`` does not hold; here it is drawn once, at a scale (the
    spacing of the top scores) at which it moves some choices, and kept."""
    return 0.05 * jax.random.normal(key, shape, dtype)


_expert_init = nn.initializers.variance_scaling(
    1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1, batch_axis=(0,)
)


class ExpertBlock(nn.Module):
    """nemotron_h's block by default: a sigmoid router with a correction
    bias, ``relu2`` experts and a shared one of the same form. The fields
    after ``dtype`` give the other published blocks: ``form`` (a key of
    ``moe.EXPERT_FORMS``) is the routed experts' and the shared expert's alike
    — ``reglu`` (``models/smallthinker.py``) and ``swiglu``
    (``models/qwen3_next.py``) are gated, a third leaf ``w_gate`` and a third
    shared projection ``shared_gate`` —; ``score`` ``softmax`` routes without
    bias or scale; ``shared_width`` 0 is no shared expert; ``shared_gated``
    weighs the shared expert's output by ``sigmoid(w_s^T u)`` (a ``(d, 1)``
    leaf ``shared_weight``)."""

    hidden: int
    n_experts: int  # the router's width: every published expert
    held: int  # routed experts this rank holds ...
    first: int  # ... from this global id on
    top_k: int
    expert_width: int
    shared_width: int
    scale: float
    dtype: Any = None
    form: str = "relu2"
    score: str = "sigmoid"
    shared_gated: bool = False
    n_group: int = 1
    topk_group: int = 1

    def setup(self):
        self.router = self.param(
            "router", nn.initializers.lecun_normal(), (self.hidden, self.n_experts))
        self.router_bias = (
            self.param("router_bias", _correction_bias_init, (self.n_experts,))
            if self.score == "sigmoid" else None
        )
        first = (self.held, self.hidden, self.expert_width)
        gated = self.form != "relu2"
        self.w_gate = self.param("w_gate", _expert_init, first) if gated else None
        self.w_in = self.param("w_in", _expert_init, first)
        self.w_out = self.param("w_out", _expert_init, (self.held, self.expert_width, self.hidden))
        if self.shared_width:
            dense = dict(use_bias=False, dtype=self.dtype)
            if gated:
                self.shared_gate = nn.Dense(self.shared_width, name="shared_gate", **dense)
            self.shared_in = nn.Dense(self.shared_width, name="shared_in", **dense)
            self.shared_out = nn.Dense(self.hidden, name="shared_out", **dense)
            if self.shared_gated:
                self.shared_weight = nn.Dense(1, name="shared_weight", **dense)

    def _route(self, rows):
        """The chosen experts, their weights and (a group-limited router) the
        kept groups, else None."""
        return moe.route(
            rows, self.router, self.router_bias, self.top_k, self.scale, self.score,
            self.n_group, self.topk_group, with_groups=True)

    def _add_shared(self, u, routed):
        """The block's output for ``u`` from its rows' routed part."""
        if not self.shared_width:
            return routed.reshape(u.shape)
        with jax.named_scope("moe_shared"):
            act, _ = moe.EXPERT_FORMS[self.form]
            gate = () if self.form == "relu2" else (self.shared_gate,)
            first = tuple(p(u) for p in (*gate, self.shared_in))
            shared = self.shared_out(act(first)).astype(jnp.float32)
            if self.shared_gated:
                shared = jax.nn.sigmoid(self.shared_weight(u).astype(jnp.float32)) * shared
        return shared + routed.reshape(u.shape)

    def __call__(self, u, scored=None):
        """``u`` (B, T, d). Returns the block's output (float32) and its
        routing: the chosen experts (B, T, top_k) and the counters. The router
        reads ``scored`` (B, T, d) where the model routes on another state
        than the experts compute on."""
        rows = u.reshape(-1, self.hidden)
        choice, weight, kept = self._route(
            rows if scored is None else scored.reshape(rows.shape))
        chunk = moe.chunk_rows(rows.shape[0], self.top_k, self.held, self.n_experts)
        routed = moe.routed_experts(
            rows, choice, weight, self.w_in, self.w_out, self.first, self.dtype, chunk=chunk,
            w_gate=self.w_gate, form=self.form)
        route = {
            "choice": choice.reshape(*u.shape[:-1], self.top_k),
            "stats": moe.route_stats(
                choice, self.first, self.held, chunk, kept, self.n_experts // self.n_group),
        }
        return self._add_shared(u, routed), route

    def step(self, u, scored=None):
        """One acting step: ``u`` (B, d)."""
        choice, weight, _ = self._route(u if scored is None else scored)
        routed = moe.routed_experts_dense(
            u, choice, weight, self.w_in, self.w_out, self.first, self.dtype, self.w_gate,
            self.form)
        return self._add_shared(u, routed)
