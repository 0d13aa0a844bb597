"""GLM-4.7-Flash (``glm4_moe_lite``) policy core: every layer is multi-head
latent attention; the first ``first_k_dense_replace`` layers follow it with a
dense SwiGLU MLP, the others with a sparse-expert block and its shared expert.

Widths come from ``Config.arch``, the model's own ``config.json`` under its
published key names (``config.GLM4_MOE_LITE_ARCH_KEYS``). The trunk (the
embedding, the unroll and act loops, the acting carry, the heads) is
``models/backbone.py``'s; the experts are ``models/layers.py``'s ``ExpertBlock``
(``swiglu`` under the sigmoid router with its correction bias and scale, an
ungated shared expert); latent attention is this file's.

    x = Dense(obs)
    per layer i:  x = x + MLA(N(x))
                  x = x + W_down(silu(W_gate N(x)) * W_up N(x))     i < first_k_dense_replace
                  x = x + Experts(N(x))                             elsewhere
    logits = log_softmax(Dense(N(x)));  value = Dense(N(x))

``N`` is the plain RMSNorm (``x rsqrt(mean x^2 + eps) w``, ``w`` starting at 1).

Latent attention (``MLAttention``, scope ``mla``), no bias anywhere:

    c_q = N(W_qa u);  q_h = [q_h^nope ; q_h^rope] = (W_qb c_q)_h          mla_down, mla_up
    [c_kv ; k^r] = W_kva u;  c_kv = N(c_kv)                               mla_down
    [k_h^nope ; v_h] = (W_kvb c_kv)_h                                     mla_up
    q_h^rope, k^r = R_t q_h^rope, R_t k^r                                 attn_rope
    k_h = [k_h^nope ; k^r]      the one rotated key, the same in every head
    o_h = softmax_s(q_h . k_{h,s} (d_nope + d_rope)^-1/2) v_{h,s}         attn_flash_pallas
    MLA(u) = W_o [o_1 .. o_H]                                             mla_o

The rotation is rotate-half over the whole ``qk_rope_head_dim``, which is the
*last* part of a head's query and key. Training runs this expanded form, as
the published modelling code does: ``k^r`` is broadcast to the heads and
``flash_attention_tpu`` takes equal query/key and value head sizes
(``config._check_glm4_moe_lite_arch`` refuses an arch whose sizes differ).

Acting (``MLAttention.step``) runs the absorbed form over a *latent ring*:
a step stores ``[c_kv,t ; R_t k^r_t]`` (``kv_lora_rank + qk_rope_head_dim``
numbers, whatever the head count) and with ``W_kvb`` split per head into
``W^UK_h`` (keys) and ``W^UV_h`` (values)

    q~_h = W^UK_h q_h^nope
    score_{h,s} = (q~_h . c_kv,s + q_h^rope . k^r_s) (d_nope + d_rope)^-1/2
    o_h = W^UV_h^T sum_s w_{h,s} c_kv,s

which are the expanded form's numbers in exact arithmetic. The ring is an
exact window of ``act_ctx`` steps, as ``GQAttention.step``'s.

Acting carry: no recurrent state (``h`` has width 0); ``c`` holds one latent
ring per layer and a step counter.

``unroll_routed`` returns one routing record per *expert* layer (the choices
and ``ops/moe.route_stats``) and, in the records, what every layer's attention
mask did under the span name ``global`` (``layers.attention_counts``); a dense
layer's counts ride with the first expert layer's record (the trunk's rule).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_rl.models.backbone import Backbone, ring
from tpu_rl.models.layers import ExpertBlock, RMSNorm, attention_counts, expert_share, rope
from tpu_rl.parallel.sequence import flash_attention_tpu


def ring_width(arch: dict) -> int:
    """Numbers a step leaves in a layer's latent ring."""
    return arch["kv_lora_rank"] + arch["qk_rope_head_dim"]


class MLAttention(nn.Module):
    """``__call__`` (training) runs the expanded form through
    ``flash_attention_tpu``, ``step`` (acting) the absorbed form over the
    latent ring."""

    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    eps: float
    dtype: Any = None

    def setup(self):
        proj = dict(use_bias=False, dtype=self.dtype)
        norm = dict(eps=self.eps, dtype=self.dtype)
        self.q_a_proj = nn.Dense(self.q_rank, name="q_a_proj", **proj)
        self.q_a_norm = RMSNorm(name="q_a_norm", **norm)
        self.q_b_proj = nn.Dense(
            self.heads * (self.nope_dim + self.rope_dim), name="q_b_proj", **proj)
        self.kv_a_proj = nn.Dense(self.kv_rank + self.rope_dim, name="kv_a_proj", **proj)
        self.kv_a_norm = RMSNorm(name="kv_a_norm", **norm)
        self.kv_b_proj = nn.Dense(
            self.heads * (self.nope_dim + self.v_dim), name="kv_b_proj", **proj)
        self.o_proj = nn.Dense(self.hidden, name="o_proj", **proj)
        self.scale = (self.nope_dim + self.rope_dim) ** -0.5

    @nn.nowrap
    @jax.named_scope("mla_down")
    def _latents(self, u):
        """The normed query latent, the normed key/value latent and the shared
        key before its rotation."""
        c_kv, k_rope = jnp.split(self.kv_a_proj(u), [self.kv_rank], axis=-1)
        return self.q_a_norm(self.q_a_proj(u)), self.kv_a_norm(c_kv), k_rope

    @nn.nowrap
    def _queries(self, c_q, pos):
        """Each head's unrotated and rotated query parts ``(..., heads, .)``."""
        with jax.named_scope("mla_up"):
            q = self.q_b_proj(c_q).reshape(*c_q.shape[:-1], self.heads, -1)
            q_nope, q_rope = jnp.split(q, [self.nope_dim], axis=-1)
        return q_nope, rope(q_rope, pos, self.rope_theta)

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
            return self.o_proj(o.reshape(B, T, -1))

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
            return self.o_proj(o.reshape(B, -1)), ring


def build_mixer(a: dict, dtype=None, name: str | None = None) -> MLAttention:
    """Latent attention at ``a``'s widths."""
    return MLAttention(
        hidden=a["hidden_size"], heads=a["num_attention_heads"], q_rank=a["q_lora_rank"],
        kv_rank=a["kv_lora_rank"], nope_dim=a["qk_nope_head_dim"], rope_dim=a["qk_rope_head_dim"],
        v_dim=a["v_head_dim"], rope_theta=float(a["rope_theta"]), eps=a["rms_norm_eps"],
        dtype=dtype, name=name,
    )


class Glm4MoeLiteLayer(nn.Module):
    """One published layer: latent attention, then the dense MLP (a leading
    layer) or the expert block, each behind an RMSNorm."""

    arch: dict
    index: int
    dtype: Any = None

    def setup(self):
        a = self.arch
        self.dense = self.index < a["first_k_dense_replace"]
        self.input_norm = RMSNorm(a["rms_norm_eps"], self.dtype, name="input_norm")
        # float32 out of an expert layer's second norm: the router reads it as
        # it is, the experts round it to their operands' dtype themselves
        self.post_norm = RMSNorm(
            a["rms_norm_eps"], self.dtype if self.dense else None, name="post_norm")
        self.attention = build_mixer(a, self.dtype, name="attention")
        if self.dense:
            proj = dict(use_bias=False, dtype=self.dtype)
            self.gate_proj = nn.Dense(a["intermediate_size"], name="gate_proj", **proj)
            self.up_proj = nn.Dense(a["intermediate_size"], name="up_proj", **proj)
            self.down_proj = nn.Dense(a["hidden_size"], name="down_proj", **proj)
        else:
            n_experts, held, first = expert_share(a)
            self.experts = ExpertBlock(
                hidden=a["hidden_size"], n_experts=n_experts, held=held, first=first,
                top_k=a["num_experts_per_tok"], expert_width=a["moe_intermediate_size"],
                shared_width=a["n_shared_experts"] * a["moe_intermediate_size"],
                scale=a["routed_scaling_factor"], dtype=self.dtype, form="swiglu",
                score="sigmoid", name="experts",
            )

    @nn.nowrap
    def _mlp(self, x):
        with jax.named_scope("mlp"):
            u = self.post_norm(x)
            return x + self.down_proj(jax.nn.silu(self.gate_proj(u)) * self.up_proj(u))

    def __call__(self, x, seg):
        """Training window. Hands back, beside ``x``, what its attention mask
        did and (an expert layer) its routing."""
        with jax.named_scope("mla"):
            x = x + self.attention(self.input_norm(x), seg)
        record = attention_counts(seg, None, "global")
        if self.dense:
            return self._mlp(x), record
        with jax.named_scope("moe"):
            mixed, route = self.experts(self.post_norm(x))
        return x + mixed, {**route, **record}

    def step(self, x, ring, count):
        with jax.named_scope("mla"):
            mixed, ring = self.attention.step(self.input_norm(x), ring, count)
        x = x + mixed
        if self.dense:
            return self._mlp(x), ring
        with jax.named_scope("moe"):
            return x + self.experts.step(self.post_norm(x)), ring


class Glm4MoeLiteActorCritic(Backbone):
    Layer = Glm4MoeLiteLayer

    @staticmethod
    def acting_state(arch, ctx):
        # a latent ring is one array: [c_kv ; k^r] a step
        return [ring((ctx, ring_width(arch)))] * arch["num_hidden_layers"]


ActorCritic = Glm4MoeLiteActorCritic
