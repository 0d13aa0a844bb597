"""Nemotron-H policy core: every layer is one mixer behind one norm — a
Mamba-2 mixer (``M``), a grouped-query attention mixer (``*``) or a
sparse-expert block (``E``) — in the order ``hybrid_override_pattern`` gives.

Widths come from ``Config.arch``, the model's own ``config.json`` under its
published key names (``config.NEMOTRON_ARCH_KEYS``). The trunk (the embedding,
the unroll and act loops, the acting carry, the heads) is
``models/backbone.py``'s; the mixers are ``models/mamba2.py``'s ``Mamba2Mixer``
and ``models/layers.py``'s ``GQAttention`` (no positions) and ``ExpertBlock``.

    x = Dense(obs)
    per layer:  x = x + mixer(RMSNorm(x))
    logits = log_softmax(Dense(RMSNorm(x)));  value = Dense(RMSNorm(x))

The expert block (``layers.ExpertBlock`` over ``ops/moe.py``): a float32 sigmoid router over all the
published experts with a correction bias that only the choice reads, the
``num_experts_per_tok`` largest chosen, their scores normalised and scaled by
``routed_scaling_factor``; non-gated ``relu(.)^2`` experts and one shared
expert. ``arch["expert_parallel"]`` states the deployment this chip is one
rank of: ``published_n_routed_experts`` experts over ``chips`` ranks, this one
``rank``; ``n_routed_experts`` is what one rank holds. The block adds the
shared expert's output and the held experts' part of the routed sum; the
absent experts' part is left out (no exchange, nothing in its place). Without
the key every expert is held. In training the held assignments, sorted by
expert, are walked in chunks of ``moe.chunk_rows(tokens, top_k, held, experts)`` rows —
twice what a fair router sends this rank, under one cap — as often as the routing's
own counts say: once most updates for a rank that holds a sixteenth, and as
often as it takes, dropping nothing, when more arrives.

``unroll_routed`` also returns, per expert layer, the experts each step chose
and the routing counters (``ops/moe.route_stats``): the learner's diagnostics
and the benchmark's routed comparison read them. Acting (``act``) applies the
held experts densely under a mask: a few rows a step.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax

from tpu_rl.models.backbone import NOTHING, Backbone, recurrent, ring
from tpu_rl.models.layers import ExpertBlock, GQAttention, RMSNorm, expert_share
from tpu_rl.models.mamba2 import Mamba2Mixer

KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


def layer_kinds(arch: dict) -> list[str]:
    return [KINDS[c] for c in arch["hybrid_override_pattern"]]


class NemotronLayer(nn.Module):
    """One published layer: ``x + mixer(RMSNorm(x))``."""

    arch: dict
    kind: str  # "mamba" | "attention" | "experts"
    dtype: Any = None

    def setup(self):
        a = self.arch
        self.norm = RMSNorm(a["layer_norm_epsilon"], self.dtype, name="norm")
        if self.kind == "mamba":
            self.mixer = Mamba2Mixer(
                hidden=a["hidden_size"], heads=a["mamba_num_heads"],
                d_head=a["mamba_head_dim"], groups=a["n_groups"],
                d_state=a["ssm_state_size"], d_conv=a["conv_kernel"], chunk=a["chunk_size"],
                eps=a["layer_norm_epsilon"], conv_bias=bool(a["use_conv_bias"]),
                proj_bias=bool(a["mamba_proj_bias"]), dtype=self.dtype, name="mamba",
            )
        elif self.kind == "attention":
            self.mixer = GQAttention(
                hidden=a["hidden_size"], n_q=a["num_attention_heads"],
                n_kv=a["num_key_value_heads"], head_dim=a["head_dim"],
                scale=a["head_dim"] ** -0.5, bias=bool(a["attention_bias"]),
                dtype=self.dtype, name="attention",
            )
        else:
            n_experts, held, first = expert_share(a)
            self.mixer = ExpertBlock(
                hidden=a["hidden_size"], n_experts=n_experts, held=held, first=first,
                top_k=a["num_experts_per_tok"], expert_width=a["moe_intermediate_size"],
                shared_width=a["moe_shared_expert_intermediate_size"],
                scale=a["routed_scaling_factor"],
                dtype=self.dtype, name="experts",
            )

    def __call__(self, x, seg, *carry):
        """Training window. ``carry``: the Mamba layer's (state0, tail0). An
        expert layer hands its routing back beside ``x``."""
        u = self.norm(x)
        if self.kind == "mamba":
            mixed, *rest = self.mixer(u, seg, *carry)
        elif self.kind == "attention":
            mixed, rest = self.mixer(u, seg), []
        else:
            with jax.named_scope("moe"):
                mixed, route = self.mixer(u)
            rest = [route]
        return (x + mixed, *rest)

    def step(self, x, *carry):
        u = self.norm(x)
        if self.kind == "experts":
            with jax.named_scope("moe"):
                return (x + self.mixer.step(u),)
        mixed, *carry = self.mixer.step(u, *carry)
        return (x + mixed, *carry)


class NemotronHActorCritic(Backbone):
    Layer = NemotronLayer
    layer_args = staticmethod(layer_kinds)
    eps_key = "layer_norm_epsilon"

    @staticmethod
    def acting_state(arch, ctx):
        heads, d_head, d_state = (
            arch["mamba_num_heads"], arch["mamba_head_dim"], arch["ssm_state_size"])
        conv_ch = heads * d_head + 2 * arch["n_groups"] * d_state
        kv = (ctx, arch["num_key_value_heads"], arch["head_dim"])
        carried = {
            "mamba": recurrent((heads, d_head, d_state), (arch["conv_kernel"] - 1, conv_ch)),
            "attention": ring(kv, kv),
            "experts": NOTHING,
        }
        return [carried[kind] for kind in layer_kinds(arch)]


ActorCritic = NemotronHActorCritic
