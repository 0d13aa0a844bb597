"""LFM2-MoE (``lfm2_moe``) policy core: gated short convolutions around
grouped-query attention — a layer's mixer is one or the other, as the published
``layer_types`` says — behind ``num_dense_layers`` leading layers whose
feed-forward part is a dense SwiGLU MLP; every other layer's is a sparse-expert
block with no shared expert.

Widths come from ``Config.arch``, the model's own ``config.json`` under its
published key names (``config.LFM2_MOE_ARCH_KEYS``). The trunk (the embedding,
the unroll and act loops, the acting carry, the heads) is ``models/backbone.py``'s;
attention is ``models/layers.py``'s ``GQAttention`` with the plain per-head q/k
norms and the rotation over the whole head, the experts its ``ExpertBlock``
(``swiglu`` under the sigmoid router with its expert bias, scale
``routed_scaling_factor``, no shared expert); the convolution mixer is this
file's.

    x = Dense(obs)
    per layer i:  x = x + Op_i(N(x))                       layer_types[i]
                  x = x + W_2(silu(W_1 N(x)) * W_3 N(x))   i < num_dense_layers
                  x = x + Experts(N(x))                    elsewhere
    logits = log_softmax(Dense(N(x)));  value = Dense(N(x))

``N`` is the plain RMSNorm (``x rsqrt(mean x^2 + eps) w``, ``w`` starting at 1).

Short convolution (``ShortConv``, scope ``shortconv``), no bias anywhere:

    [b ; c ; x~] = W_in u                                  shortconv_in
    z = b * x~                                             shortconv_gate
    h_t = sum_j w_j * z_{t-(K-1)+j}     K = conv_L_cache taps, depthwise, causal,
                                        none across an episode seam, no activation
    ShortConv(u) = W_out (c * h)                           shortconv_gate, shortconv_out

``b``, ``c`` and ``x~`` leave ``W_in`` in the operands' dtype; their product,
the taps' sum and ``c * h`` are float32 and the result is rounded once for
``W_out``. A mixer with no scan behind it: what a step leaves for the next is
the last ``K - 1`` values of ``z`` — not of ``u``, not of ``x~`` — and nothing
else.

Attention (``GQAttention``, scope ``attn_global``): heads of ``hidden_size /
num_attention_heads``; q and k normed per head (plain, one weight vector for
all query heads and one for all key heads), rotated over the whole head
(rotate-half), causal same-episode softmax attention.

Acting carry: ``h`` holds each convolution layer's tail (``conv_L_cache - 1``
rows of ``hidden_size``, float32) — a tail and no state —; ``c`` one K/V ring
of ``act_ctx`` slots per attention layer (keys stored normed and rotated at
their own step) and a step counter.

``unroll_routed`` returns one routing record per *expert* layer (the choices
and ``ops/moe.route_stats``) and, in an attention layer's, what its mask did
under the span name ``global`` (``layers.attention_counts``). A convolution
does no data-dependent work and counts nothing.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_rl.models.backbone import Backbone, ring, tail
from tpu_rl.models.layers import (
    ExpertBlock,
    GQAttention,
    RMSNorm,
    attention_counts,
    expert_share,
    seam_conv,
)


def layer_kinds(arch: dict) -> list[tuple[str, bool]]:
    """Per layer, in order: its mixer (``"conv"`` or ``"full_attention"``) and
    whether its feed-forward part is the dense MLP."""
    return [(kind, i < arch["num_dense_layers"]) for i, kind in enumerate(arch["layer_types"])]


def head_dim(arch: dict) -> int:
    return arch["hidden_size"] // arch["num_attention_heads"]


class ShortConv(nn.Module):
    """``__call__`` (training) convolves a window from the tail it is handed,
    ``step`` (acting) one step over ``[tail ; z]``."""

    hidden: int
    taps: int
    dtype: Any = None

    def setup(self):
        proj = dict(use_bias=False, dtype=self.dtype)
        self.in_proj = nn.Dense(3 * self.hidden, name="in_proj", **proj)
        self.out_proj = nn.Dense(self.hidden, name="out_proj", **proj)
        self.conv_weight = self.param(
            "conv_weight", nn.initializers.variance_scaling(1.0, "fan_in", "uniform", in_axis=0),
            (self.taps, self.hidden),
        )

    @nn.nowrap
    @jax.named_scope("shortconv_in")
    def _split(self, u):
        return jnp.split(self.in_proj(u), 3, axis=-1)  # b, c, x~

    @nn.nowrap
    @jax.named_scope("shortconv_out")
    def _out(self, y):
        return self.out_proj(y.astype(self.dtype or jnp.float32))

    def __call__(self, u, seg, tail0):
        """``u`` (B, T, d); ``tail0`` (B, K-1, d): the ``z`` of the steps before
        the window. Returns the output and the tail after the last step."""
        b, c, x = self._split(u)
        K = self.taps

        @jax.named_scope("shortconv_gate")
        @jax.checkpoint  # the backward keeps b, c, x~, not the float32 products and taps
        def gated(b, c, x, tail0, weight):
            z = b.astype(jnp.float32) * x.astype(jnp.float32)
            no_bias = jnp.zeros((self.hidden,))
            h = seam_conv(z, tail0, seg, weight, no_bias, scope="shortconv_conv")
            keep = (seg[:, -(K - 1):] == seg[:, -1:])[..., None]  # of the last episode only
            return c.astype(jnp.float32) * h, jnp.where(keep, z[:, -(K - 1):], 0.0)

        y, tail = gated(b, c, x, tail0, self.conv_weight)
        return self._out(y), tail

    def step(self, u, tail):
        """One acting step: ``u`` (B, d), ``tail`` (B, K-1, d) float32."""
        b, c, x = self._split(u)
        with jax.named_scope("shortconv_gate"):
            z = b.astype(jnp.float32) * x.astype(jnp.float32)
            window = jnp.concatenate([tail, z[:, None]], axis=1)
            y = c.astype(jnp.float32) * jnp.einsum("bkc,kc->bc", window, self.conv_weight)
        return self._out(y), window[:, 1:]


def build_mixer(a: dict, kind: str, dtype=None) -> nn.Module:
    """The mixer of a ``"conv"`` or a ``"full_attention"`` layer at ``a``'s
    widths, under the name its leaves have in the parameter tree."""
    if kind == "conv":
        return ShortConv(hidden=a["hidden_size"], taps=a["conv_L_cache"], dtype=dtype, name="conv")
    return GQAttention(
        hidden=a["hidden_size"], n_q=a["num_attention_heads"], n_kv=a["num_key_value_heads"],
        head_dim=head_dim(a), scale=head_dim(a) ** -0.5, dtype=dtype, name="attention",
        rope_theta=float(a["rope_parameters"]["rope_theta"]), qk_norm=a["norm_eps"],
        qk_norm_zero_centered=False,
    )


class Lfm2MoeLayer(nn.Module):
    """One published layer: the mixer of its kind, then the dense MLP (a
    leading layer) or the expert block, each behind an RMSNorm."""

    arch: dict
    kind: tuple  # (mixer: "conv" | "full_attention", feed-forward part dense?)
    dtype: Any = None

    def setup(self):
        a = self.arch
        self.mixer_kind, self.dense = self.kind
        self.operator_norm = RMSNorm(a["norm_eps"], self.dtype, name="operator_norm")
        # float32 out of an expert layer's second norm: the router reads it as
        # it is, the experts round it to their operands' dtype themselves
        self.ffn_norm = RMSNorm(a["norm_eps"], self.dtype if self.dense else None, name="ffn_norm")
        self.mixer = build_mixer(a, self.mixer_kind, self.dtype)
        if self.dense:
            proj = dict(use_bias=False, dtype=self.dtype)
            self.w1 = nn.Dense(a["intermediate_size"], name="w1", **proj)
            self.w3 = nn.Dense(a["intermediate_size"], name="w3", **proj)
            self.w2 = nn.Dense(a["hidden_size"], name="w2", **proj)
        else:
            n_experts, held, first = expert_share(a, "num_experts")
            self.experts = ExpertBlock(
                hidden=a["hidden_size"], n_experts=n_experts, held=held, first=first,
                top_k=a["num_experts_per_tok"], expert_width=a["moe_intermediate_size"],
                shared_width=0, scale=float(a["routed_scaling_factor"]), dtype=self.dtype,
                form="swiglu", score="sigmoid", name="experts",
            )

    @nn.nowrap
    def _mlp(self, x):
        with jax.named_scope("mlp"):
            u = self.ffn_norm(x)
            return x + self.w2(jax.nn.silu(self.w1(u)) * self.w3(u))

    def __call__(self, x, seg, *carry):
        """Training window. ``carry``: a convolution layer's tail. Hands back
        ``x``, a convolution layer's tail after the window, and (an expert or
        an attention layer) its record: the routing, what the mask did."""
        u = self.operator_norm(x)
        if self.mixer_kind == "conv":
            with jax.named_scope("shortconv"):
                mixed, *carry = self.mixer(u, seg, *carry)
            record = {}
        else:
            with jax.named_scope("attn_global"):
                mixed = self.mixer(u, seg)
            record = attention_counts(seg, None, "global")
        x = x + mixed
        if self.dense:
            return (self._mlp(x), *carry, *([record] if record else []))
        with jax.named_scope("moe"):
            mixed, route = self.experts(self.ffn_norm(x))
        return (x + mixed, *carry, {**route, **record})

    def step(self, x, *carry):
        with jax.named_scope("shortconv" if self.mixer_kind == "conv" else "attn_global"):
            mixed, *carry = self.mixer.step(self.operator_norm(x), *carry)
        x = x + mixed
        if self.dense:
            return (self._mlp(x), *carry)
        with jax.named_scope("moe"):
            return (x + self.experts.step(self.ffn_norm(x)), *carry)


class Lfm2MoeActorCritic(Backbone):
    Layer = Lfm2MoeLayer
    layer_args = staticmethod(layer_kinds)
    eps_key = "norm_eps"

    @staticmethod
    def acting_state(arch, ctx):
        conv = tail((arch["conv_L_cache"] - 1, arch["hidden_size"]))  # a tail and no state
        kv = (ctx, arch["num_key_value_heads"], head_dim(arch))
        return [conv if kind == "conv" else ring(kv, kv) for kind in arch["layer_types"]]


ActorCritic = Lfm2MoeActorCritic
