"""Nemotron-H policy core: every layer is one mixer behind one norm — a
Mamba-2 mixer (``M``), a grouped-query attention mixer (``*``) or a
sparse-expert block (``E``) — in the order ``hybrid_override_pattern`` gives.

Widths come from ``Config.arch``, the model's own ``config.json`` under its
published key names (``config.NEMOTRON_ARCH_KEYS``). The unroll / act loops,
the acting carry and its packing are ``GraniteHybridActorCritic``'s; the
Mamba-2 and attention mixers are the same modules at other widths (an inner
width that is not ``expand x hidden``, eight B/C groups, an explicit head
size). As there, an observation projection replaces the token embedding and a
policy and a value head replace the LM head.

    x = Dense(obs)
    per layer:  x = x + mixer(RMSNorm(x))
    logits = log_softmax(Dense(RMSNorm(x)));  value = Dense(RMSNorm(x))

The expert block (``ops/moe.py``): a float32 sigmoid router over all the
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
import jax.numpy as jnp

from tpu_rl.models.granite_hybrid import (
    GQAttention,
    GraniteHybridActorCritic,
    Mamba2Mixer,
    RMSNorm,
)
from tpu_rl.ops import moe

KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


def layer_kinds(arch: dict) -> list[str]:
    return [KINDS[c] for c in arch["hybrid_override_pattern"]]


def expert_share(arch: dict, held_key: str = "n_routed_experts") -> tuple[int, int, int]:
    """(experts the router scores, experts held here, global id of the first
    held): the rank's share of ``arch["expert_parallel"]``, or everything.
    ``held_key``: the source's name for the count of routed experts."""
    held = arch[held_key]
    share = arch.get("expert_parallel")
    if not share:
        return held, held, 0
    return share["published_n_routed_experts"], held, share["rank"] * held


def _conv_channels(arch: dict) -> int:
    inner = arch["mamba_num_heads"] * arch["mamba_head_dim"]
    return inner + 2 * arch["n_groups"] * arch["ssm_state_size"]


def carry_widths(arch: dict, ctx: int) -> tuple[int, int]:
    """Widths of the flattened acting carry ``(h, c)``, laid out as
    ``granite_hybrid.carry_widths`` lays them out."""
    per_mamba = (
        arch["mamba_num_heads"] * arch["mamba_head_dim"] * arch["ssm_state_size"]
        + (arch["conv_kernel"] - 1) * _conv_channels(arch)
    )
    per_attn = 2 * ctx * arch["num_key_value_heads"] * arch["head_dim"]
    kinds = layer_kinds(arch)
    return kinds.count("mamba") * per_mamba, kinds.count("attention") * per_attn + 1


def _correction_bias_init(key, shape, dtype=jnp.float32):
    """The published model trains this bias beside the loss, by a rule its
    ``config.json`` does not hold; here it is drawn once, at a scale (the
    spacing of the top scores) at which it moves some choices, and kept."""
    return 0.05 * jax.random.normal(key, shape, dtype)


_expert_init = nn.initializers.variance_scaling(
    1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1, batch_axis=(0,)
)


class ExpertBlock(nn.Module):
    """This model's block by default: a sigmoid router with a correction
    bias, ``relu2`` experts and a shared one of the same form. The fields
    after ``dtype`` give the other published blocks: ``form`` (a key of
    ``moe.EXPERT_FORMS``) is the routed experts' and the shared expert's alike
    — ``reglu`` (``models/smallthinker.py``) and ``swiglu``
    (``models/qwen3_next.py``) are gated, a third leaf ``w_gate`` and a third
    shared projection ``shared_gate`` —; ``score`` ``softmax`` routes without
    bias or scale; ``shared_width`` 0 is no shared expert; ``shared_gated``
    weighs the shared expert's output by ``sigmoid(w_s^T u)`` (a ``(d, 1)``
    leaf ``shared_weight``)."""

    hidden: int
    n_experts: int  # the router's width: every published expert
    held: int  # routed experts this rank holds ...
    first: int  # ... from this global id on
    top_k: int
    expert_width: int
    shared_width: int
    scale: float
    dtype: Any = None
    form: str = "relu2"
    score: str = "sigmoid"
    shared_gated: bool = False

    def setup(self):
        self.router = self.param(
            "router", nn.initializers.lecun_normal(), (self.hidden, self.n_experts))
        self.router_bias = (
            self.param("router_bias", _correction_bias_init, (self.n_experts,))
            if self.score == "sigmoid" else None
        )
        first = (self.held, self.hidden, self.expert_width)
        gated = self.form != "relu2"
        self.w_gate = self.param("w_gate", _expert_init, first) if gated else None
        self.w_in = self.param("w_in", _expert_init, first)
        self.w_out = self.param("w_out", _expert_init, (self.held, self.expert_width, self.hidden))
        if self.shared_width:
            dense = dict(use_bias=False, dtype=self.dtype)
            if gated:
                self.shared_gate = nn.Dense(self.shared_width, name="shared_gate", **dense)
            self.shared_in = nn.Dense(self.shared_width, name="shared_in", **dense)
            self.shared_out = nn.Dense(self.hidden, name="shared_out", **dense)
            if self.shared_gated:
                self.shared_weight = nn.Dense(1, name="shared_weight", **dense)

    def _route(self, rows):
        return moe.route(
            rows, self.router, self.router_bias, self.top_k, self.scale, self.score)

    def _add_shared(self, u, routed):
        """The block's output for ``u`` from its rows' routed part."""
        if not self.shared_width:
            return routed.reshape(u.shape)
        with jax.named_scope("moe_shared"):
            act, _ = moe.EXPERT_FORMS[self.form]
            gate = () if self.form == "relu2" else (self.shared_gate,)
            first = tuple(p(u) for p in (*gate, self.shared_in))
            shared = self.shared_out(act(first)).astype(jnp.float32)
            if self.shared_gated:
                shared = jax.nn.sigmoid(self.shared_weight(u).astype(jnp.float32)) * shared
        return shared + routed.reshape(u.shape)

    def __call__(self, u, scored=None):
        """``u`` (B, T, d). Returns the block's output (float32) and its
        routing: the chosen experts (B, T, top_k) and the counters. The router
        reads ``scored`` (B, T, d) where the model routes on another state
        than the experts compute on."""
        rows = u.reshape(-1, self.hidden)
        choice, weight = self._route(rows if scored is None else scored.reshape(rows.shape))
        chunk = moe.chunk_rows(rows.shape[0], self.top_k, self.held, self.n_experts)
        routed = moe.routed_experts(
            rows, choice, weight, self.w_in, self.w_out, self.first, self.dtype, chunk=chunk,
            w_gate=self.w_gate, form=self.form)
        route = {
            "choice": choice.reshape(*u.shape[:-1], self.top_k),
            "stats": moe.route_stats(choice, self.first, self.held, chunk),
        }
        return self._add_shared(u, routed), route

    def step(self, u, scored=None):
        """One acting step: ``u`` (B, d)."""
        choice, weight = self._route(u if scored is None else scored)
        routed = moe.routed_experts_dense(
            u, choice, weight, self.w_in, self.w_out, self.first, self.dtype, self.w_gate,
            self.form)
        return self._add_shared(u, routed)


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


class NemotronHActorCritic(GraniteHybridActorCritic):
    def setup(self):
        a = self.arch
        self.embed = nn.Dense(a["hidden_size"], name="embed", dtype=self.dtype)
        layer = nn.remat(NemotronLayer) if self.remat else NemotronLayer
        self.layers = [
            layer(a, kind, self.dtype, name=f"layer{i}")
            for i, kind in enumerate(layer_kinds(a))
        ]
        self.norm_f = RMSNorm(a["layer_norm_epsilon"], name="norm_f")
        self.logits_head = nn.Dense(self.n_actions, name="logits")
        self.value_head = nn.Dense(1, name="value")
        self.h_width, self.c_width = carry_widths(a, self.act_ctx)
        self.state_shape = (a["mamba_num_heads"], a["mamba_head_dim"], a["ssm_state_size"])
        self.tail_shape = (a["conv_kernel"] - 1, _conv_channels(a))
        ring = (self.act_ctx, a["num_key_value_heads"], a["head_dim"])
        self.kv_shapes = [ring] * layer_kinds(a).count("attention")

    def _embed(self, obs):
        return self.embed(obs).astype(jnp.float32)

    def _heads(self, x):
        h = self.norm_f(x)
        return jax.nn.log_softmax(self.logits_head(h)), self.value_head(h)

    def unroll_routed(self, obs, carry0, firsts):
        """The unroll, and each expert layer's routing in layer order."""
        *out, routes = self._unroll(obs, carry0, firsts)
        return tuple(out), routes
