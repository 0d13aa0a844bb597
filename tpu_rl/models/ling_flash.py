"""Ling-3.0-flash (``ling_flash``) policy core: Kimi Delta Attention — the
delta rule with a decay per key channel — in five layers of six, multi-head
latent attention in the sixth; a leading dense SwiGLU MLP, then sparse experts
under a group-limited sigmoid router and one shared expert.

Widths come from ``Config.arch``, the model's own ``config.json`` under its
published key names (``config.LING_FLASH_ARCH_KEYS``). The trunk (the
embedding, the unroll and act loops, the acting carry, the heads) is
``models/backbone.py``'s; latent attention is ``models/layers.py``'s
``MLAttention`` with the two fields this family sets (queries without a
latent, a head-wise output gate) at 192-wide queries and keys against
128-wide values, the experts its ``ExpertBlock`` with a group stage
(``ops/moe.route``: ``n_group``, ``topk_group``); the linear mixer is this
file's.

    x = Dense(obs)
    per layer i:  x = x + Mixer_i(N(x))
                  x = x + W_down(silu(W_gate N(x)) * W_up N(x))     a leading dense layer
                  x = x + Experts(N(x))                             elsewhere
    logits = log_softmax(Dense(N(x)));  value = Dense(N(x))

``N`` is the plain RMSNorm. The layer at place ``j`` of the stack is the
published layer ``i = j + layer_offset``: its mixer is latent attention where
``(i + 1) % layer_group_size == 0`` and KDA elsewhere; the first
``first_k_dense_replace`` layers *of the stack* carry the dense MLP.

Linear mixer (``KimiDeltaAttention``, scope ``kda``), no bias anywhere:

    [q, k, v] = silu(conv(W_qkv u))            kda_in, kda_conv: depthwise, causal, its taps
                                               stop at an episode seam
    a = W_a u + dt_bias                        one number a head and key channel (full rank)
    g = kda_lower_bound * sigmoid(exp(A_log_h) a)        kda_gate: the log decay, in (bound, 0)
    [b, z] = W_bz u;  beta = sigmoid(b)        one write strength and one output gate a head
    o = KDA(l2norm(q) d_k^-1/2, l2norm(k), v, g, beta)   kda_scan (``ops/kda.py``: on a TPU
                                               at lane-multiple widths its Pallas pair,
                                               ``kda_pallas``; the jax.numpy body elsewhere)
    KDA(u) = W_o [sigmoid(z_h) * RMSNorm(o_h) w_n]_h     kda_out: the norm over each head's
                                               features, one weight vector for all heads

Acting carry: ``h`` holds each KDA layer's state (heads x key size x value
size, float32) and the last ``short_conv_kernel_size - 1`` inputs of its
convolution; ``c`` one latent ring ``(act_ctx, kv_lora_rank +
qk_rope_head_dim)`` per latent layer and a step counter.

``unroll_routed`` returns one routing record per *expert* layer (the choices
and ``ops/moe.route_stats``, ``group-hit-share`` among them); the records also
hold what the latent layer's mask did under the span name ``global``
(``layers.attention_counts``) and each KDA layer's share of gates within 1% of
the bound (``kda-decay-floor-share``, already divided by the count of KDA
layers: ``obs/learn.route_scalars`` adds them up). The dense layer's ride with
the first expert layer's record (the trunk's rule).
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_rl.models.backbone import Backbone, recurrent, ring
from tpu_rl.models.layers import (
    ExpertBlock,
    MLAttention,
    RMSNorm,
    _rms_norm,
    attention_counts,
    expert_share,
    seam_conv,
)
from tpu_rl.models.mamba2 import _a_log_init, _dt_bias_init
from tpu_rl.ops.kda import kda_chunked, kda_step

# Steps a chunk of the training form takes: the family's published kernels'
# convention, not a key of its config.json.
CHUNK = 64


def layer_kinds(arch: dict) -> list[tuple[str, bool]]:
    """(mixer ``"kda"`` | ``"mla"``, feed-forward part dense?) for each layer
    of the stack, in order."""
    offset, every = arch.get("layer_offset", 0), arch["layer_group_size"]
    return [
        ("mla" if (j + offset + 1) % every == 0 else "kda", j < arch["first_k_dense_replace"])
        for j in range(arch["num_hidden_layers"])
    ]


class KimiDeltaAttention(nn.Module):
    """``__call__`` (training) runs the chunked rule — ``ops/kda.kda_chunked``
    chooses its form by what it can observe: one Pallas kernel per pass
    (``ops/pallas_kda.py``) on a TPU at key and value sizes that are lane
    multiples, the ``jax.numpy`` body everywhere else — ``step`` (acting) the
    one-step rule."""

    hidden: int
    heads: int
    key_dim: int
    value_dim: int
    d_conv: int
    bound: float  # of the log decay a step: g in (bound, 0)
    eps: float
    chunk: int
    dtype: Any = None

    def setup(self):
        self.key_width = self.heads * self.key_dim
        self.conv_ch = 2 * self.key_width + self.heads * self.value_dim
        proj = dict(use_bias=False, dtype=self.dtype)
        self.in_proj_qkv = nn.Dense(self.conv_ch, name="in_proj_qkv", **proj)
        # float32 out of bf16 operands: exp(A_log) multiplies what this product's
        # rounding leaves, and a bf16 result's (2^-8 of |a|) would move a decay by tenths
        self.a_proj = nn.Dense(
            self.key_width, name="a_proj", **proj,
            dot_general=functools.partial(jax.lax.dot_general, preferred_element_type=jnp.float32))
        self.in_proj_bz = nn.Dense(2 * self.heads, name="in_proj_bz", **proj)
        self.o_proj = nn.Dense(self.hidden, name="o_proj", **proj)
        self.conv_weight = self.param(
            "conv_weight", nn.initializers.variance_scaling(1.0, "fan_in", "uniform", in_axis=0),
            (self.d_conv, self.conv_ch),
        )
        self.dt_bias = self.param("dt_bias", _dt_bias_init, (self.key_width,))
        self.A_log = self.param("A_log", _a_log_init, (self.heads,))
        self.norm_scale = self.param("norm_scale", nn.initializers.ones, (self.value_dim,))

    @nn.nowrap
    def _split(self, u):
        """The convolution's input; per head the write strength ``beta``, the
        output gate's logit ``z`` and, a key channel, the log decay ``g``
        (float32)."""
        with jax.named_scope("kda_in"):
            qkv = self.in_proj_qkv(u)
            a = self.a_proj(u)
            b, z = jnp.split(self.in_proj_bz(u).astype(jnp.float32), 2, axis=-1)
        with jax.named_scope("kda_gate"):
            a = (a + self.dt_bias).reshape(*u.shape[:-1], self.heads, self.key_dim)
            g = self.bound * jax.nn.sigmoid(jnp.exp(self.A_log)[:, None] * a)
        return qkv, z, jax.nn.sigmoid(b), g

    @nn.nowrap
    def _heads(self, conv):
        """Convolved ``[q, k, v]`` -> activated q, k (..., heads, key size) and
        v (..., heads, value size), in the operands' dtype."""
        q, k, v = jnp.split(
            jax.nn.silu(conv).astype(self.dtype or jnp.float32),
            [self.key_width, 2 * self.key_width], axis=-1)
        return tuple(x.reshape(*conv.shape[:-1], self.heads, -1) for x in (q, k, v))

    @nn.nowrap
    @jax.named_scope("kda_out")
    def _out(self, o, z):
        """``o`` float32 (..., heads, value size): each head normed and
        multiplied by ``sigmoid`` of its scalar, then the output projection."""
        y = _rms_norm(o, self.norm_scale, self.eps) * jax.nn.sigmoid(z)[..., None]
        return self.o_proj(y.reshape(*o.shape[:-2], -1).astype(self.dtype or jnp.float32))

    def __call__(self, u, seg, state0, tail0):
        """``u`` (B, T, d); ``state0`` (B, heads, key size, value size),
        ``tail0`` (B, K-1, C): the carry the window starts from. Returns the
        output, the carry after the last step and the share of gates within 1%
        of the bound."""
        qkv, z, beta, g = self._split(u)

        @jax.checkpoint  # the backward keeps qkv, not the convolution's float32 taps
        def convolved(qkv, tail0, weight):
            no_bias = jnp.zeros((self.conv_ch,))
            return self._heads(seam_conv(qkv, tail0, seg, weight, no_bias, scope="kda_conv"))

        q, k, v = convolved(qkv, tail0, self.conv_weight)
        o, state = kda_chunked(q, k, v, g, beta, seg, state0, self.chunk, self.dtype)
        K = self.d_conv
        keep = (seg[:, -(K - 1):] == seg[:, -1:])[..., None]  # taps of the last episode only
        tail = jnp.where(keep, qkv[:, -(K - 1):].astype(jnp.float32), 0.0)
        at_floor = jnp.mean((jax.lax.stop_gradient(g) < 0.99 * self.bound).astype(jnp.float32))
        return self._out(o, z), state, tail, at_floor

    def step(self, u, state, tail):
        """One acting step: ``u`` (B, d)."""
        qkv, z, beta, g = self._split(u)
        window = jnp.concatenate([tail, qkv[:, None].astype(jnp.float32)], axis=1)
        q, k, v = self._heads(jnp.einsum("bkc,kc->bc", window, self.conv_weight))
        o, state = kda_step(q, k, v, g, beta, state)
        return self._out(o, z), state, window[:, 1:]


def build_mixer(a: dict, kind: str, dtype=None) -> nn.Module:
    """The mixer of a ``"kda"`` or an ``"mla"`` layer at ``a``'s widths, under
    the name its leaves have in the parameter tree."""
    if kind == "kda":
        return KimiDeltaAttention(
            hidden=a["hidden_size"], heads=a["num_attention_heads"], key_dim=a["head_dim"],
            value_dim=a["head_dim"], d_conv=a["short_conv_kernel_size"],
            bound=float(a["kda_lower_bound"]), eps=a["rms_norm_eps"], chunk=CHUNK, dtype=dtype,
            name="linear_attn",
        )
    return MLAttention(
        hidden=a["hidden_size"], heads=a["num_attention_heads"], q_rank=None,
        kv_rank=a["kv_lora_rank"], nope_dim=a["qk_nope_head_dim"], rope_dim=a["qk_rope_head_dim"],
        v_dim=a["v_head_dim"], rope_theta=float(a["rope_theta"]), eps=a["rms_norm_eps"],
        dtype=dtype, head_gate=True, name="attention",
    )


class LingFlashLayer(nn.Module):
    """One published layer: the mixer of its kind, then the dense MLP (a
    leading layer) or the expert block, each behind an RMSNorm."""

    arch: dict
    kind: tuple  # (mixer: "kda" | "mla", feed-forward part dense?)
    dtype: Any = None

    def setup(self):
        a = self.arch
        self.mixer_kind, self.dense = self.kind
        self.input_norm = RMSNorm(a["rms_norm_eps"], self.dtype, name="input_norm")
        # float32 out of an expert layer's second norm: the router reads it as
        # it is, the experts round it to their operands' dtype themselves
        self.post_norm = RMSNorm(
            a["rms_norm_eps"], self.dtype if self.dense else None, name="post_norm")
        self.mixer = build_mixer(a, self.mixer_kind, self.dtype)
        self.kda_layers = sum(mixer == "kda" for mixer, _ in layer_kinds(a))
        if self.dense:
            proj = dict(use_bias=False, dtype=self.dtype)
            self.gate_proj = nn.Dense(a["intermediate_size"], name="gate_proj", **proj)
            self.up_proj = nn.Dense(a["intermediate_size"], name="up_proj", **proj)
            self.down_proj = nn.Dense(a["hidden_size"], name="down_proj", **proj)
        else:
            n_experts, held, first = expert_share(a, "num_experts")
            self.experts = ExpertBlock(
                hidden=a["hidden_size"], n_experts=n_experts, held=held, first=first,
                top_k=a["num_experts_per_tok"], expert_width=a["moe_intermediate_size"],
                shared_width=a["moe_shared_expert_intermediate_size"],
                scale=float(a["routed_scaling_factor"]), dtype=self.dtype, form="swiglu",
                score="sigmoid", n_group=a["n_group"], topk_group=a["topk_group"], name="experts",
            )

    @nn.nowrap
    def _mlp(self, x):
        with jax.named_scope("mlp"):
            u = self.post_norm(x)
            return x + self.down_proj(jax.nn.silu(self.gate_proj(u)) * self.up_proj(u))

    def __call__(self, x, seg, *carry):
        """Training window. ``carry``: a KDA layer's (state0, tail0). Hands back
        ``x``, a KDA layer's carry after the window, and the layer's record:
        what its mixer counted and (an expert layer) its routing."""
        u = self.input_norm(x)
        if self.mixer_kind == "kda":
            with jax.named_scope("kda"):
                mixed, *carry, at_floor = self.mixer(u, seg, *carry)
            record = {"kda-decay-floor-share": {"kda": at_floor / self.kda_layers}}
        else:
            with jax.named_scope("mla"):
                mixed = self.mixer(u, seg)
            record = attention_counts(seg, None, "global")
        x = x + mixed
        if self.dense:
            return (self._mlp(x), *carry, record)
        with jax.named_scope("moe"):
            mixed, route = self.experts(self.post_norm(x))
        return (x + mixed, *carry, {**route, **record})

    def step(self, x, *carry):
        with jax.named_scope(self.mixer_kind):
            mixed, *carry = self.mixer.step(self.input_norm(x), *carry)
        x = x + mixed
        if self.dense:
            return (self._mlp(x), *carry)
        with jax.named_scope("moe"):
            return (x + self.experts.step(self.post_norm(x)), *carry)


class LingFlashActorCritic(Backbone):
    Layer = LingFlashLayer
    layer_args = staticmethod(layer_kinds)

    @staticmethod
    def acting_state(arch, ctx):
        heads, size = arch["num_attention_heads"], arch["head_dim"]
        linear = recurrent(
            (heads, size, size), (arch["short_conv_kernel_size"] - 1, 3 * heads * size))
        # a latent ring is one array: [c_kv ; k^r] a step
        latent = ring((ctx, arch["kv_lora_rank"] + arch["qk_rope_head_dim"]))
        return [linear if mixer == "kda" else latent for mixer, _ in layer_kinds(arch)]


ActorCritic = LingFlashActorCritic
