"""GLM-4.7-Flash (``glm4_moe_lite``) policy core: every layer is multi-head
latent attention; the first ``first_k_dense_replace`` layers follow it with a
dense SwiGLU MLP, the others with a sparse-expert block and its shared expert.

Widths come from ``Config.arch``, the model's own ``config.json`` under its
published key names (``config.GLM4_MOE_LITE_ARCH_KEYS``). The trunk (the
embedding, the unroll and act loops, the acting carry, the heads) is
``models/backbone.py``'s; the experts are ``models/layers.py``'s ``ExpertBlock``
(``swiglu`` under the sigmoid router with its correction bias and scale, an
ungated shared expert) and latent attention its ``MLAttention``
(``models/ling_flash.py`` builds it too, with two fields this family leaves off).

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
the published modelling code does: ``k^r`` is broadcast to the heads. This
family's query/key and value head sizes are equal
(``config._check_glm4_moe_lite_arch`` refuses an arch whose sizes differ, or
whose queries have no latent: ``MLAttention`` builds both, for
``models/ling_flash.py``, and this family's published model has neither).

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

from tpu_rl.models.backbone import Backbone, ring
from tpu_rl.models.layers import ExpertBlock, MLAttention, RMSNorm, attention_counts, expert_share


def ring_width(arch: dict) -> int:
    """Numbers a step leaves in a layer's latent ring."""
    return arch["kv_lora_rank"] + arch["qk_rope_head_dim"]


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
