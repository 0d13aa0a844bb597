"""Qwen3-Next policy core: three Gated-DeltaNet linear-attention layers to
every gated full-attention layer, each followed by a sparse-expert block with a
gated shared expert.

Widths come from ``Config.arch``, the model's own ``config.json`` under its
published key names (``config.QWEN3_NEXT_ARCH_KEYS``). The trunk (the
embedding, the unroll and act loops, the acting carry, the heads) is
``models/backbone.py``'s; attention is ``models/layers.py``'s ``GQAttention``
with the three fields this family sets, the experts its ``ExpertBlock``; the
linear mixer is this file's.

    x = Dense(obs)
    per layer i:  x = x + Mixer_i(N(x));  x = x + Experts(N(x))
    logits = log_softmax(Dense(N(x)));  value = Dense(N(x))

``N`` is the published zero-centred RMSNorm (``x rsqrt(mean x^2 + eps) (1 +
w)``, ``w`` starting at 0). Layer ``i`` is *full* where ``(i + 1) %
full_attention_interval == 0`` and *linear* elsewhere.

Linear mixer (``GatedDeltaNet``, scope ``gdn``): ``[q, k, v, z] = W_qkvz u``,
``[b, a] = W_ba u``; a causal depthwise convolution whose taps stop at an
episode seam, then SiLU, over ``[q, k, v]`` (``gdn_conv``); per value head the
gated delta rule (``ops/gated_delta.py``: ``gdn_scan``) on L2-normalised q and
k with ``beta = sigmoid(b)`` and the log decay ``g = -exp(A_log) softplus(a +
dt_bias)``; ``W_out (RMSNorm(o) w_n * silu(z))``, the norm over each head's
``linear_value_head_dim`` features with one plain weight ``w_n`` for all heads.

Full mixer (``GQAttention``, scope ``attn_global``): ``q_proj`` yields each
head's query and its gate; q and k are normed per head (zero-centred) and
rotated over the first ``partial_rotary_factor`` of the head (rotate-half);
causal same-episode softmax attention; the output times ``sigmoid(gate)``.

Experts (``ExpertBlock``): a float32 softmax router over every published
expert, the ``num_experts_per_tok`` largest weighed by the softmax over the
chosen logits; ``swiglu`` experts; plus ``sigmoid(w_s^T h)`` times a shared
``swiglu`` expert. ``arch["expert_parallel"]`` states the deployment this
chip is one rank of, as for ``nemotron_h``: ``num_experts`` counts what one
rank holds, and the absent experts' part of the sum is left out.

Acting carry: ``h`` holds each linear layer's state (value heads x key size x
value size, float32) and the last ``linear_conv_kernel_dim - 1`` inputs of its
convolution; ``c`` one K/V ring of ``act_ctx`` slots per full layer (keys
stored normed and rotated at their own step) and a step counter.

``unroll_routed`` also returns each layer's routing record and, in a full
layer's, what its attention mask did under the span name ``global``
(``layers.attention_counts``).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_rl.models.backbone import Backbone, recurrent, ring
from tpu_rl.models.layers import (
    ExpertBlock,
    GQAttention,
    RMSNorm,
    _rms_norm,
    attention_counts,
    expert_share,
    seam_conv,
)
from tpu_rl.models.mamba2 import _a_log_init, _dt_bias_init
from tpu_rl.ops.gated_delta import gated_delta_chunked, gated_delta_step

# Steps a chunk of the training form takes: the family's convention, not a
# key of its config.json.
CHUNK = 64


def layer_kinds(arch: dict) -> list[str]:
    """``"linear"`` or ``"attention"`` for each layer, in order."""
    every = arch["full_attention_interval"]
    return [
        "attention" if (i + 1) % every == 0 else "linear"
        for i in range(arch["num_hidden_layers"])
    ]


class GatedDeltaNet(nn.Module):
    """``__call__`` (training) runs the chunked rule, ``step`` (acting) the
    one-step rule."""

    hidden: int
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    d_conv: int
    eps: float
    chunk: int
    dtype: Any = None

    def setup(self):
        self.key_width = self.key_heads * self.key_dim
        self.value_width = self.value_heads * self.value_dim
        self.conv_ch = 2 * self.key_width + self.value_width
        proj = dict(use_bias=False, dtype=self.dtype)
        self.in_proj_qkvz = nn.Dense(self.conv_ch + self.value_width, name="in_proj_qkvz", **proj)
        self.in_proj_ba = nn.Dense(2 * self.value_heads, name="in_proj_ba", **proj)
        self.out_proj = nn.Dense(self.hidden, name="out_proj", **proj)
        self.conv_weight = self.param(
            "conv_weight", nn.initializers.variance_scaling(1.0, "fan_in", "uniform", in_axis=0),
            (self.d_conv, self.conv_ch),
        )
        self.dt_bias = self.param("dt_bias", _dt_bias_init, (self.value_heads,))
        self.A_log = self.param("A_log", _a_log_init, (self.value_heads,))
        self.norm_scale = self.param("norm_scale", nn.initializers.ones, (self.value_dim,))

    def _split(self, u):
        """The convolution's input, the output gate ``z``, and per value head
        the write strength ``beta`` and the log decay ``g`` (float32)."""
        qkv, z = jnp.split(self.in_proj_qkvz(u), [self.conv_ch], axis=-1)
        b, a = jnp.split(self.in_proj_ba(u).astype(jnp.float32), 2, axis=-1)
        g = -jnp.exp(self.A_log) * jax.nn.softplus(a + self.dt_bias)
        return qkv, z, jax.nn.sigmoid(b), g

    def _heads(self, conv):
        """Convolved ``[q, k, v]`` -> activated q, k (..., key heads, key
        size) and v (..., value heads, value size), in the operands' dtype."""
        q, k, v = jnp.split(
            jax.nn.silu(conv).astype(self.dtype or jnp.float32),
            [self.key_width, 2 * self.key_width], axis=-1)
        lead = conv.shape[:-1]
        return (
            q.reshape(*lead, self.key_heads, self.key_dim),
            k.reshape(*lead, self.key_heads, self.key_dim),
            v.reshape(*lead, self.value_heads, self.value_dim),
        )

    def _out(self, o, z):
        """``o`` float32 (..., value heads, value size): each head normed,
        gated by ``silu(z)``, then the output projection."""
        gate = jax.nn.silu(z.astype(jnp.float32)).reshape(o.shape)
        y = _rms_norm(o, self.norm_scale, self.eps) * gate
        return self.out_proj(y.reshape(*o.shape[:-2], -1).astype(self.dtype or jnp.float32))

    def __call__(self, u, seg, state0, tail0):
        """``u`` (B, T, d); ``state0`` (B, value heads, key size, value size),
        ``tail0`` (B, K-1, C): the carry the window starts from. Returns the
        output and the carry after the last step."""
        qkv, z, beta, g = self._split(u)

        @jax.checkpoint  # the backward keeps qkv, not the convolution's float32 taps
        def convolved(qkv, tail0, weight):
            no_bias = jnp.zeros((self.conv_ch,))
            return self._heads(seam_conv(qkv, tail0, seg, weight, no_bias, scope="gdn_conv"))

        q, k, v = convolved(qkv, tail0, self.conv_weight)
        o, state = gated_delta_chunked(q, k, v, g, beta, seg, state0, self.chunk, self.dtype)
        K = self.d_conv
        keep = (seg[:, -(K - 1):] == seg[:, -1:])[..., None]  # taps of the last episode only
        tail = jnp.where(keep, qkv[:, -(K - 1):].astype(jnp.float32), 0.0)
        return self._out(o, z), state, tail

    def step(self, u, state, tail):
        """One acting step: ``u`` (B, d)."""
        qkv, z, beta, g = self._split(u)
        window = jnp.concatenate([tail, qkv[:, None].astype(jnp.float32)], axis=1)
        q, k, v = self._heads(jnp.einsum("bkc,kc->bc", window, self.conv_weight))
        o, state = gated_delta_step(q, k, v, g, beta, state)
        return self._out(o, z), state, window[:, 1:]


def build_mixer(a: dict, kind: str, dtype=None) -> nn.Module:
    """The mixer of a ``"linear"`` or an ``"attention"`` layer at ``a``'s
    widths, under the name its leaves have in the parameter tree."""
    if kind == "linear":
        return GatedDeltaNet(
            hidden=a["hidden_size"], key_heads=a["linear_num_key_heads"],
            value_heads=a["linear_num_value_heads"], key_dim=a["linear_key_head_dim"],
            value_dim=a["linear_value_head_dim"], d_conv=a["linear_conv_kernel_dim"],
            eps=a["rms_norm_eps"], chunk=CHUNK, dtype=dtype, name="linear_attn",
        )
    return GQAttention(
        hidden=a["hidden_size"], n_q=a["num_attention_heads"],
        n_kv=a["num_key_value_heads"], head_dim=a["head_dim"],
        scale=a["head_dim"] ** -0.5, dtype=dtype, name="attention",
        rope_theta=float(a["rope_theta"]),
        rotary_dim=int(a["head_dim"] * a["partial_rotary_factor"]),
        qk_norm=a["rms_norm_eps"], gated=True,
    )


class Qwen3NextLayer(nn.Module):
    """One published layer: the mixer of its ``kind``, then the expert block,
    each behind a zero-centred RMSNorm."""

    arch: dict
    kind: str  # "linear" | "attention"
    dtype: Any = None

    def setup(self):
        a = self.arch
        norm = dict(eps=a["rms_norm_eps"], zero_centered=True)
        self.input_norm = RMSNorm(dtype=self.dtype, name="input_norm", **norm)
        # float32 out of the second norm: the router reads it as it is, the
        # experts round it to their operands' dtype themselves
        self.post_norm = RMSNorm(name="post_norm", **norm)
        self.mixer = build_mixer(a, self.kind, self.dtype)
        n_experts, held, first = expert_share(a, "num_experts")
        self.experts = ExpertBlock(
            hidden=a["hidden_size"], n_experts=n_experts, held=held, first=first,
            top_k=a["num_experts_per_tok"], expert_width=a["moe_intermediate_size"],
            shared_width=a["shared_expert_intermediate_size"], scale=1.0, dtype=self.dtype,
            form="swiglu", score="softmax", shared_gated=True, name="experts",
        )

    def __call__(self, x, seg, *carry):
        """Training window. ``carry``: a linear layer's (state0, tail0). Hands
        back ``x``, a linear layer's carry after the window, and the routing."""
        u = self.input_norm(x)
        if self.kind == "linear":
            with jax.named_scope("gdn"):
                mixed, *carry = self.mixer(u, seg, *carry)
        else:
            with jax.named_scope("attn_global"):
                mixed = self.mixer(u, seg)
        x = x + mixed
        with jax.named_scope("moe"):
            mixed, route = self.experts(self.post_norm(x))
        if self.kind == "attention":
            route.update(attention_counts(seg, None, "global"))
        return (x + mixed, *carry, route)

    def step(self, x, *carry):
        with jax.named_scope("gdn" if self.kind == "linear" else "attn_global"):
            mixed, *carry = self.mixer.step(self.input_norm(x), *carry)
        x = x + mixed
        with jax.named_scope("moe"):
            x = x + self.experts.step(self.post_norm(x))
        return (x, *carry)


class Qwen3NextActorCritic(Backbone):
    Layer = Qwen3NextLayer
    layer_args = staticmethod(layer_kinds)
    zero_centered = True

    @staticmethod
    def acting_state(arch, ctx):
        keys = arch["linear_num_key_heads"] * arch["linear_key_head_dim"]
        heads, value_dim = arch["linear_num_value_heads"], arch["linear_value_head_dim"]
        linear = recurrent(
            (heads, arch["linear_key_head_dim"], value_dim),
            (arch["linear_conv_kernel_dim"] - 1, 2 * keys + heads * value_dim))
        kv = (ctx, arch["num_key_value_heads"], arch["head_dim"])
        return [linear if kind == "linear" else ring(kv, kv) for kind in layer_kinds(arch)]


ActorCritic = Qwen3NextActorCritic
