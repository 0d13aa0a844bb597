"""Granite-4.0-H policy core: Mamba-2 layers with grouped-query attention
layers between them, as ``GraniteMoeHybrid`` arranges them (dense: no experts).

Widths come from ``Config.arch``, the model's own ``config.json`` under its
published key names (``config.GRANITE_ARCH_KEYS``). The trunk (the unroll and
act loops, the acting carry) is ``models/backbone.py``'s; the mixers are
``models/mamba2.py``'s ``Mamba2Mixer`` and ``models/layers.py``'s ``GQAttention``
without positions, ``attention_multiplier`` as the softmax scale. This family
scales the embedding, each residual branch and the logits:

    x = embedding_multiplier * Dense(obs)
    per layer:  x = x + residual_multiplier * mixer(RMSNorm(x))
                x = x + residual_multiplier * W_out(silu(a) * b),  [a, b] = W_in RMSNorm(x)
    logits = log_softmax(Dense(RMSNorm(x)) / logits_scaling);  value = Dense(RMSNorm(x))

Acting carry: ``h`` holds each Mamba layer's state and convolution tail;
``c`` one K/V ring of ``act_ctx`` slots per attention layer and a step counter.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_rl.models.backbone import Backbone, recurrent, ring
from tpu_rl.models.layers import GQAttention, RMSNorm
from tpu_rl.models.mamba2 import Mamba2Mixer


class HybridLayer(nn.Module):
    """One published layer: the mixer of its kind, then the shared gated MLP,
    each behind an RMSNorm and scaled by ``residual_multiplier``."""

    arch: dict
    kind: str  # "mamba" | "attention"
    dtype: Any = None

    def setup(self):
        a = self.arch
        norm = dict(eps=a["rms_norm_eps"], dtype=self.dtype)
        self.input_norm = RMSNorm(name="input_norm", **norm)
        self.post_norm = RMSNorm(name="post_norm", **norm)
        if self.kind == "mamba":
            self.mixer = Mamba2Mixer(
                hidden=a["hidden_size"], heads=a["mamba_n_heads"], d_head=a["mamba_d_head"],
                groups=a["mamba_n_groups"], d_state=a["mamba_d_state"], d_conv=a["mamba_d_conv"],
                chunk=a["mamba_chunk_size"], eps=a["rms_norm_eps"],
                conv_bias=bool(a["mamba_conv_bias"]), proj_bias=bool(a["mamba_proj_bias"]),
                dtype=self.dtype, name="mamba",
            )
        else:
            self.mixer = GQAttention(
                hidden=a["hidden_size"], n_q=a["num_attention_heads"],
                n_kv=a["num_key_value_heads"],
                head_dim=a["hidden_size"] // a["num_attention_heads"],
                scale=a["attention_multiplier"], bias=bool(a["attention_bias"]),
                dtype=self.dtype, name="attention",
            )
        self.mlp_in = nn.Dense(
            2 * a["intermediate_size"], use_bias=False, dtype=self.dtype, name="mlp_in"
        )
        self.mlp_out = nn.Dense(a["hidden_size"], use_bias=False, dtype=self.dtype, name="mlp_out")

    def _mlp(self, x):
        a, b = jnp.split(self.mlp_in(self.post_norm(x)), 2, axis=-1)
        return x + self.arch["residual_multiplier"] * self.mlp_out(jax.nn.silu(a) * b)

    def __call__(self, x, seg, *carry):
        """Training window. ``carry``: the Mamba layer's (state0, tail0)."""
        mixed, *carry = (
            self.mixer(self.input_norm(x), seg, *carry)
            if self.kind == "mamba" else (self.mixer(self.input_norm(x), seg),)
        )
        x = x + self.arch["residual_multiplier"] * mixed
        return (self._mlp(x), *carry)

    def step(self, x, *carry):
        mixed, *carry = self.mixer.step(self.input_norm(x), *carry)
        x = x + self.arch["residual_multiplier"] * mixed
        return (self._mlp(x), *carry)


class GraniteHybridActorCritic(Backbone):
    Layer = HybridLayer
    routed = False  # no expert layer: the algos take the plain unroll
    layer_args = staticmethod(lambda arch: arch["layer_types"])

    @staticmethod
    def acting_state(arch, ctx):
        heads, d_head, d_state = arch["mamba_n_heads"], arch["mamba_d_head"], arch["mamba_d_state"]
        conv_ch = heads * d_head + 2 * arch["mamba_n_groups"] * d_state
        mamba = recurrent((heads, d_head, d_state), (arch["mamba_d_conv"] - 1, conv_ch))
        kv = (ctx, arch["num_key_value_heads"], arch["hidden_size"] // arch["num_attention_heads"])
        return [mamba if kind == "mamba" else ring(kv, kv) for kind in arch["layer_types"]]

    def _embed(self, obs):
        return self.arch["embedding_multiplier"] * self.embed(obs).astype(jnp.float32)

    def _heads(self, x):
        h = self.norm_f(x)
        logits = self.logits_head(h) / self.arch["logits_scaling"]
        return jax.nn.log_softmax(logits), self.value_head(h)


ActorCritic = GraniteHybridActorCritic
