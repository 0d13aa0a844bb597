"""Transformer actor-critic for long-context training.

New TPU-native capability with no reference equivalent (the reference's only
sequence model is a 5-step LSTM window, ``/root/reference/networks/models.py:71-75``;
SURVEY.md §5.7 records sequence parallelism as absent). This module exposes the
SAME unroll contract as ``DiscreteActorCritic`` —
``(obs, carry0, firsts) -> (log-softmax logits, value, carry)`` — so the
existing PPO / IMPALA / V-MPO train steps work unchanged with a transformer
policy; the carry is accepted and returned untouched (attention needs no
recurrent state).

Long sequences shard over the mesh's ``"seq"`` axis: the attention primitive
is ``shard_map``-wrapped ring attention (or Ulysses all-to-all) from
``tpu_rl.parallel.sequence``, embedded inside the surrounding GSPMD program —
XLA partitions the elementwise/Dense compute from the batch sharding while the
ring rotates K/V blocks over ICI. Episode seams (``is_fir``) become attention
segment masks, computed globally before sharding, so no token attends across
an episode boundary.

Acting uses ``decode`` — incremental decoding with per-layer K/V caches — so a
worker env step costs O(ctx·d + d²) instead of the O(ctx²·d) full-window
recompute (the reference's acting path is a single LSTM step,
``/root/reference/networks/models.py:37-56``; this is its transformer
equivalent). For episodes that fit the context window the cached and
full-recompute paths are numerically equivalent (``tests/test_transformer.py``);
past the window the cache keeps each token's K/V as computed when it entered
(sliding re-positioning is impossible without recompute) — a policy-lag-like
bias absorbed by the IS/V-trace corrections, same as the window path's
truncation bias.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from tpu_rl.parallel.sequence import (
    ATTENTION_IMPLS,
    DATA_AXIS,
    SEQ_AXIS,
    full_attention,
    segment_ids_from_firsts,
)


def sinusoidal_embedding(pos: jax.Array, dim: int) -> jax.Array:
    """(B, T) int positions -> (B, T, dim) sinusoidal embeddings. Parameter-
    free, so context length is unbounded (no learned table to outgrow)."""
    half = dim // 2
    freqs = np.exp(-np.log(10000.0) * np.arange(half) / max(half - 1, 1))
    ang = pos[..., None].astype(jnp.float32) * freqs  # (B, T, half)
    emb = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, ((0, 0), (0, 0), (0, 1)))
    return emb


class MultiHeadAttention(nn.Module):
    """Causal segment-masked MHA with a pluggable (possibly sequence-sharded)
    attention primitive, plus a single-token cached decode path."""

    hidden: int
    n_heads: int
    attention_impl: str = "full"  # full | ring | ulysses
    mesh: Any = None  # jax Mesh when impl is sharded
    dtype: Any = None  # computation dtype (bfloat16 feeds the MXU natively)

    def setup(self):
        assert self.hidden % self.n_heads == 0, (
            f"d_model {self.hidden} not divisible by heads {self.n_heads}"
        )
        self.qkv = nn.Dense(3 * self.hidden, name="qkv", dtype=self.dtype)
        self.out = nn.Dense(self.hidden, name="out", dtype=self.dtype)

    def __call__(self, x: jax.Array, pos: jax.Array, seg: jax.Array):
        B, T, C = x.shape
        H = self.n_heads
        qkv = self.qkv(x).reshape(B, T, 3, H, C // H)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        impl = ATTENTION_IMPLS[self.attention_impl]
        # Shapes are static under tracing: only enter the shard_map island
        # when they tile the mesh (param init traces with B=1; acting traces
        # with T=ctx — both fall back to the mathematically identical full
        # attention on a single device).
        tiles_mesh = self.mesh is not None and (
            B % self.mesh.shape[DATA_AXIS] == 0
            and T % self.mesh.shape[SEQ_AXIS] == 0
        )
        if tiles_mesh and self.attention_impl in ("ring", "ulysses"):
            qs = P(DATA_AXIS, SEQ_AXIS, None, None)
            ps = P(DATA_AXIS, SEQ_AXIS)
            attn = jax.shard_map(
                functools.partial(impl, axis_name=SEQ_AXIS, causal=True),
                mesh=self.mesh,
                in_specs=(qs, qs, qs, ps, ps),
                out_specs=qs,
            )
            o = attn(q, k, v, pos, seg)
        elif self.attention_impl in ("blockwise", "flash"):
            # Single-device paths: blockwise = O(block^2) transients instead
            # of the (T, T) score matrix; flash = the Pallas TPU fused kernel
            # (falls back to full attention off-TPU).
            o = impl(q, k, v, pos, seg, causal=True)
        else:
            o = full_attention(q, k, v, pos, seg, causal=True)
        return self.out(o.reshape(B, T, C))

    def decode(
        self,
        x_t: jax.Array,  # (B, 1, C) — the newest token only
        k_cache: jax.Array,  # (B, ctx, H, D)
        v_cache: jax.Array,  # (B, ctx, H, D)
        count: jax.Array,  # (B,) int32: tokens already cached, per row
    ):
        """One incremental step: project the new token, ring-write its K/V
        into the cache at ``count % ctx``, attend the query over the valid
        cache entries. All cached tokens precede the query, so causality is
        exactly the validity mask. ``count`` is per-row so a vectorized
        worker can carry envs at different episode steps in one batch."""
        B, _, C = x_t.shape
        H = self.n_heads
        ctx = k_cache.shape[1]
        qkv = self.qkv(x_t).reshape(B, 1, 3, H, C // H)
        q, k_new, v_new = qkv[:, 0, 0], qkv[:, 0, 1], qkv[:, 0, 2]  # (B,H,D)
        slot = jnp.mod(count, ctx)  # (B,)
        # Per-row ring write via boolean select (dynamic_update_slice cannot
        # take per-row start indices; a where() is a true overwrite, so a
        # transient NaN projection cannot poison the slot the way an
        # arithmetic 0*NaN blend would). The worker carry (and thus the
        # caches) is float32; bf16 projections round-trip exactly through the
        # f32 store, so casting back to the compute dtype below reproduces
        # the training path's inputs bit-for-bit.
        write = (jnp.arange(ctx)[None, :] == slot[:, None])[:, :, None, None]
        k_cache = jnp.where(write, k_new.astype(k_cache.dtype)[:, None], k_cache)
        v_cache = jnp.where(write, v_new.astype(v_cache.dtype)[:, None], v_cache)
        # ring not yet wrapped: prefix only, per row
        valid = jnp.arange(ctx)[None, :] <= count[:, None]  # (B, ctx)
        # Mixed-precision recipe mirrors full_attention/_masked_block_scores:
        # compute-dtype (possibly bf16) operands into the MXU, float32
        # accumulation and softmax.
        kc = k_cache.astype(q.dtype)
        vc = v_cache.astype(q.dtype)
        scores = jnp.einsum(
            "bhd,bthd->bht", q, kc, preferred_element_type=jnp.float32
        ) * jnp.float32(1.0 / np.sqrt(C / H))
        scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum(
            "bht,bthd->bhd", w, vc, preferred_element_type=jnp.float32
        )
        return self.out(o.reshape(B, 1, C)), k_cache, v_cache


class Block(nn.Module):
    hidden: int
    n_heads: int
    ff_mult: int = 4
    attention_impl: str = "full"
    mesh: Any = None
    dtype: Any = None

    def setup(self):
        self.attn = MultiHeadAttention(
            self.hidden, self.n_heads, self.attention_impl, self.mesh,
            self.dtype, name="attn",
        )
        self.ln1 = nn.LayerNorm(name="ln1")
        self.ln2 = nn.LayerNorm(name="ln2")
        self.ff1 = nn.Dense(self.ff_mult * self.hidden, name="ff1", dtype=self.dtype)
        self.ff2 = nn.Dense(self.hidden, name="ff2", dtype=self.dtype)

    def _ff(self, x):
        return self.ff2(nn.gelu(self.ff1(self.ln2(x))))

    def __call__(self, x, pos, seg):
        x = x + self.attn(self.ln1(x), pos, seg)
        return x + self._ff(x)

    def decode(self, x_t, k_cache, v_cache, count):
        a, k_cache, v_cache = self.attn.decode(
            self.ln1(x_t), k_cache, v_cache, count
        )
        x_t = x_t + a
        return x_t + self._ff(x_t), k_cache, v_cache


class TransformerActorCritic(nn.Module):
    """Decoder-only causal transformer with categorical + value heads.

    Same unroll contract as ``DiscreteActorCritic.unroll``; ``carry0`` is
    passed through untouched so the LSTM-shaped plumbing (batch hx/cx fields,
    worker carries) keeps working."""

    n_actions: int
    hidden: int = 64  # d_model; reuses cfg.hidden_size
    n_heads: int = 4
    n_layers: int = 2
    ff_mult: int = 4
    attention_impl: str = "full"
    mesh: Any = None
    # Computation dtype: bfloat16 halves HBM traffic and doubles MXU rate;
    # params stay float32 (flax mixed precision), heads return float32.
    dtype: Any = None
    reset_on_first: bool = True  # interface parity; attention always resets
    # via segment masking (a transformer cannot "carry state across seams")

    def setup(self):
        self.embed = nn.Dense(self.hidden, name="embed", dtype=self.dtype)
        self.blocks = [
            Block(
                self.hidden,
                self.n_heads,
                self.ff_mult,
                self.attention_impl,
                self.mesh,
                self.dtype,
                name=f"block{i}",
            )
            for i in range(self.n_layers)
        ]
        self.ln_f = nn.LayerNorm(name="ln_f")
        self.logits_head = nn.Dense(self.n_actions, name="logits")
        self.value_head = nn.Dense(1, name="value")

    def _heads(self, x):
        h = self.ln_f(x)
        # Heads in float32: log-probs and values feed loss math directly.
        h = h.astype(jnp.float32)
        return jax.nn.log_softmax(self.logits_head(h)), self.value_head(h)

    def __call__(
        self,
        obs: jax.Array,
        carry0,
        firsts: jax.Array,
        pos: jax.Array | None = None,
        seg: jax.Array | None = None,
    ):
        B, T = obs.shape[0], obs.shape[1]
        if seg is None:
            # Global cumsum: correct under jit/GSPMD (sharding is invisible
            # to program semantics); shard_map callers must pass seg shards.
            seg = segment_ids_from_firsts(firsts)
        if pos is None:
            # Segment-relative positions (restart at episode seams): keeps
            # training positions consistent with the worker's acting
            # positions, which count from the episode start.
            idx = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
            seam = jax.lax.cummax(
                jnp.where(firsts[..., 0] > 0, idx, 0), axis=1
            )
            pos = idx - seam
        x = self.embed(obs)
        x = x + sinusoidal_embedding(pos, self.hidden).astype(x.dtype)
        for block in self.blocks:
            x = block(x, pos, seg)
        logits, value = self._heads(x)
        return logits, value, carry0

    unroll = __call__

    def decode(
        self,
        obs_t: jax.Array,  # (B, obs_dim) — the newest observation
        k_caches: jax.Array,  # (B, n_layers, ctx, H, D)
        v_caches: jax.Array,  # (B, n_layers, ctx, H, D)
        count: jax.Array,  # (B,) int32: tokens already cached, per row
    ):
        """Incremental acting step. The position is episode-relative
        (= ``count``), matching the training unroll's segment-relative
        positions while the episode fits the window. Per-row counts let a
        vectorized worker batch envs at different episode steps."""
        pos = count[:, None].astype(jnp.int32)
        x = self.embed(obs_t[:, None, :])
        x = x + sinusoidal_embedding(pos, self.hidden).astype(x.dtype)
        new_k, new_v = [], []
        for i, block in enumerate(self.blocks):
            x, k_i, v_i = block.decode(
                x, k_caches[:, i], v_caches[:, i], count
            )
            new_k.append(k_i)
            new_v.append(v_i)
        logits, value = self._heads(x)
        return (
            logits[:, 0],
            value[:, 0],
            jnp.stack(new_k, axis=1),
            jnp.stack(new_v, axis=1),
        )
