"""SmallThinker policy core: every layer is grouped-query attention *and* a
sparse-expert block, and the layers differ in their attention — the published
layouts make layer 0 of every four global and without positions (NoPE) and
the other three a sliding window over rotated queries and keys (RoPE).

Widths come from ``Config.arch``, the model's own ``config.json`` under its
published key names (``config.SMALLTHINKER_ARCH_KEYS``). The trunk (the
embedding, the unroll and act loops, the acting carry, the heads) is
``models/backbone.py``'s; attention is ``models/layers.py``'s ``GQAttention``
(positions and window as fields), the experts its ``ExpertBlock`` (gated).

    x = Dense(obs)
    per layer i:
        a = RMSNorm_1(x)
        x = x + Attention_i(a)      window and RoPE where the layouts say so
        x = x + Experts(RMSNorm_2(x); routed on a)
    logits = log_softmax(Dense(RMSNorm(x)));  value = Dense(RMSNorm(x))

The router reads ``a``, the normed state **before** attention, in float32; the
experts compute on the normed state after it. It takes the
``moe_num_active_primary_experts`` largest of its logits over all the
published experts and weighs them by the softmax over the chosen logits; the
experts are gated (``relu(W_gate h) * W_in h``, then ``W_out``) and there is
no shared expert. ``arch["expert_parallel"]`` states the deployment this chip
is one rank of, as for ``nemotron_h``: ``moe_num_primary_experts`` counts what
one rank holds, and the absent experts' part of the sum is left out.

Acting carry: no recurrent state (``h`` has width 0); ``c`` holds one K/V
ring per layer and a step counter. A global layer's ring has ``act_ctx``
slots, a window layer's ``sliding_window_size`` whatever ``act_ctx`` is; a
RoPE layer's ring stores its keys as rotated at their own step.

``unroll_routed`` also returns each layer's routing record and, in it, what
the layer's attention mask did under its span name, ``global`` or ``window``
(``layers.attention_counts``).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax

from tpu_rl.models.backbone import Backbone, ring
from tpu_rl.models.layers import (
    ExpertBlock,
    GQAttention,
    RMSNorm,
    attention_counts,
    expert_share,
)


def ring_slots(arch: dict, ctx: int) -> list[int]:
    """Slots of each layer's acting K/V ring."""
    return [
        arch["sliding_window_size"] if windowed else ctx
        for windowed in arch["sliding_window_layout"]
    ]


class SmallThinkerLayer(nn.Module):
    """One published layer: attention of the kind the layouts give layer
    ``index``, then the expert block routed on the state before attention."""

    arch: dict
    index: int
    dtype: Any = None

    def setup(self):
        a = self.arch
        # float32 out of the first norm: the router reads it as it is, the
        # projections round it to their operands' dtype themselves
        self.input_norm = RMSNorm(a["rms_norm_eps"], name="input_norm")
        self.post_norm = RMSNorm(a["rms_norm_eps"], self.dtype, name="post_norm")
        self.window = a["sliding_window_size"] if a["sliding_window_layout"][self.index] else None
        self.span = "global" if self.window is None else "window"  # names its scope and counter
        self.attention = GQAttention(
            hidden=a["hidden_size"], n_q=a["num_attention_heads"],
            n_kv=a["num_key_value_heads"], head_dim=a["head_dim"],
            scale=a["head_dim"] ** -0.5, dtype=self.dtype, name="attention",
            rope_theta=float(a["rope_theta"]) if a["rope_layout"][self.index] else None,
            window=self.window,
        )
        n_experts, held, first = expert_share(a, "moe_num_primary_experts")
        self.experts = ExpertBlock(
            hidden=a["hidden_size"], n_experts=n_experts, held=held, first=first,
            top_k=a["moe_num_active_primary_experts"], expert_width=a["moe_ffn_hidden_size"],
            shared_width=0, scale=1.0, dtype=self.dtype, form="reglu", score="softmax",
            name="experts",
        )

    def __call__(self, x, seg):
        """Training window. Hands its routing back beside ``x``."""
        a = self.input_norm(x)
        with jax.named_scope(f"attn_{self.span}"):
            x = x + self.attention(a, seg)
        with jax.named_scope("moe"):
            mixed, route = self.experts(self.post_norm(x), scored=a)
        route.update(attention_counts(seg, self.window, self.span))
        return x + mixed, route

    def step(self, x, k_cache, v_cache, count):
        a = self.input_norm(x)
        with jax.named_scope(f"attn_{self.span}"):
            mixed, k_cache, v_cache = self.attention.step(a, k_cache, v_cache, count)
        x = x + mixed
        with jax.named_scope("moe"):
            x = x + self.experts.step(self.post_norm(x), scored=a)
        return x, k_cache, v_cache


class SmallThinkerActorCritic(Backbone):
    Layer = SmallThinkerLayer

    @staticmethod
    def acting_state(arch, ctx):
        heads, size = arch["num_key_value_heads"], arch["head_dim"]
        return [ring((slots, heads, size), (slots, heads, size)) for slots in ring_slots(arch, ctx)]


ActorCritic = SmallThinkerActorCritic
