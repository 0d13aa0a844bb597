"""SmallThinker policy core: every layer is grouped-query attention *and* a
sparse-expert block, and the layers differ in their attention — the published
layouts make layer 0 of every four global and without positions (NoPE) and
the other three a sliding window over rotated queries and keys (RoPE).

Widths come from ``Config.arch``, the model's own ``config.json`` under its
published key names (``config.SMALLTHINKER_ARCH_KEYS``). The unroll / act
loops, the acting carry and its packing are ``GraniteHybridActorCritic``'s,
attention is its ``GQAttention`` (positions and window as fields), the expert
block, the observation projection and the heads ``models/nemotron_h.py``'s
(the block at its other published form). As there, an
observation projection replaces the token embedding and a policy and a value
head replace the LM head.

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

``unroll_routed`` also returns each layer's routing record, and beside the
routing in it what the layer's attention mask did (``global`` or ``window`` ->
the count; ``obs/learn.attention_scalars``): the query-key pairs it kept
(``attn-pairs``), and of the splash kernels' grid the tiles of the static band
(``attn-tiles-band``), those of them that no seam emptied, which the
kernels compute (``attn-tiles-run``), and the grid steps the backward takes a
head (``attn-bwd-steps``; ``parallel/sequence.attention_tiles``).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_rl.models.granite_hybrid import GQAttention, RMSNorm
from tpu_rl.models.nemotron_h import ExpertBlock, NemotronHActorCritic, expert_share
from tpu_rl.obs.learn import ATTENTION_COUNTERS
from tpu_rl.parallel.sequence import attention_tiles


def ring_slots(arch: dict, ctx: int) -> list[int]:
    """Slots of each layer's acting K/V ring."""
    return [
        arch["sliding_window_size"] if windowed else ctx
        for windowed in arch["sliding_window_layout"]
    ]


def carry_widths(arch: dict, ctx: int) -> tuple[int, int]:
    """Widths of the flattened acting carry ``(h, c)``."""
    per_slot = 2 * arch["num_key_value_heads"] * arch["head_dim"]
    return 0, sum(ring_slots(arch, ctx)) * per_slot + 1


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


class SmallThinkerLayer(nn.Module):
    """One published layer: attention of the kind the layouts give layer
    ``index``, then the expert block routed on the state before attention."""

    arch: dict
    index: int
    dtype: Any = None
    kind = "attention"  # to the unroll / act loops: a K/V ring, no state

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
        counts = (kept_pairs(seg, self.window), *attention_tiles(seg, self.window))
        route.update({c: {self.span: n} for c, n in zip(ATTENTION_COUNTERS, counts)})
        return x + mixed, route

    def step(self, x, k_cache, v_cache, count):
        a = self.input_norm(x)
        with jax.named_scope(f"attn_{self.span}"):
            mixed, k_cache, v_cache = self.attention.step(a, k_cache, v_cache, count)
        x = x + mixed
        with jax.named_scope("moe"):
            x = x + self.experts.step(self.post_norm(x), scored=a)
        return x, k_cache, v_cache


class SmallThinkerActorCritic(NemotronHActorCritic):
    def setup(self):
        a = self.arch
        self.embed = nn.Dense(a["hidden_size"], name="embed", dtype=self.dtype)
        layer = nn.remat(SmallThinkerLayer) if self.remat else SmallThinkerLayer
        self.layers = [
            layer(a, i, self.dtype, name=f"layer{i}") for i in range(a["num_hidden_layers"])
        ]
        self.norm_f = RMSNorm(a["rms_norm_eps"], name="norm_f")
        self.logits_head = nn.Dense(self.n_actions, name="logits")
        self.value_head = nn.Dense(1, name="value")
        self.h_width, self.c_width = carry_widths(a, self.act_ctx)
        self.kv_shapes = [
            (slots, a["num_key_value_heads"], a["head_dim"])
            for slots in ring_slots(a, self.act_ctx)
        ]
