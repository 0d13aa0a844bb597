"""The trunk every catalog family (``config.ARCH_CHECKS``) unrolls and acts
through: an observation projection in place of the token embedding, the
family's layers in order, a final RMSNorm, and a policy and a value head in
place of the LM head. Same ``unroll`` / ``act`` contract as the LSTM and
transformer families, so PPO / IMPALA / V-MPO take it unchanged.

A family's file (``models/<Config.model>.py``) holds its layer and one class
derived from ``Backbone`` — ``ActorCritic`` there — that supplies what
differs: ``Layer`` and ``layer_args`` (the layer's class and what tells one
layer from the others), ``eps_key`` / ``zero_centered`` (the final norm),
``routed``, and ``acting_state(arch, ctx)``: per layer, in order, what it
carries from step to step — ``recurrent(state shape, tail shape)``,
``tail(shape)``, ``ring(shape, ...)`` or ``NOTHING``. Everything about the
acting carry follows from that one statement. The carry is worker-local
(``store_carry=False``) and flat: ``h`` holds the recurrent layers' ``[state ;
tail]`` (a convolution layer's tail alone) in layer order, float32; ``c`` the
rings' arrays in layer order and, last, one step counter,
as the transformer family packs its caches. A training window starts from the
``h`` it is handed (zeros when the batch carries a placeholder) and from an
empty attention context — the truncation ``models/transformer.py`` documents.
Every layer is rematerialised in the backward pass: one layer keeps ~150 KB
per token.
"""

from __future__ import annotations

import itertools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_rl.models.layers import RMSNorm
from tpu_rl.parallel.sequence import segment_ids_from_firsts


def recurrent(state: tuple, tail: tuple) -> tuple:
    """A layer that carries a state and its convolution's last inputs
    (Mamba-2's; ``models/qwen3_next.py``'s linear attention), packed into ``h``."""
    return "h", (state, tail)


def tail(shape: tuple) -> tuple:
    """A layer whose mixer is a convolution and nothing else
    (``models/lfm2_moe.py``'s): it carries its last inputs and no state."""
    return "h", (shape,)


def ring(*arrays: tuple) -> tuple:
    """An attention layer's acting ring, packed into ``c``: keys and values,
    ``(slots, kv heads, head size)`` each, or for latent attention one array."""
    return "c", arrays


NOTHING = (None, ())  # a layer that carries nothing from step to step


def state_widths(carried: list) -> tuple[int, int]:
    """Widths of the flattened acting carry ``(h, c)`` of a family whose
    ``acting_state`` is ``carried``."""
    width = {"h": 0, "c": 1}  # the step counter ends c
    for where, shapes in carried:
        if where:
            width[where] += sum(math.prod(s) for s in shapes)
    return width["h"], width["c"]


class Backbone(nn.Module):
    n_actions: int
    arch: dict
    act_ctx: int  # slots of the acting ring
    dtype: Any = None  # matmul operand dtype; the residual stream is float32
    remat: bool = True  # tests only: the gradients must not depend on it

    Layer = None  # the family's layer: Layer(arch, one of layer_args(arch), dtype)
    eps_key = "rms_norm_eps"  # the family's name for the final norm's epsilon
    zero_centered = False  # the final norm's form (``layers.RMSNorm``)
    routed = True  # the layers hand records back (routing, counters): ``ModelFamily.route_unroll``

    @staticmethod
    def layer_args(arch: dict):
        """What tells a layer from the others, in layer order: by default its index."""
        return range(arch["num_hidden_layers"])

    def setup(self):
        a = self.arch
        self.embed = nn.Dense(a["hidden_size"], name="embed", dtype=self.dtype)
        layer = nn.remat(self.Layer) if self.remat else self.Layer
        self.layers = [
            layer(a, arg, self.dtype, name=f"layer{i}")
            for i, arg in enumerate(self.layer_args(a))
        ]
        self.norm_f = RMSNorm(a[self.eps_key], zero_centered=self.zero_centered, name="norm_f")
        self.logits_head = nn.Dense(self.n_actions, name="logits")
        self.value_head = nn.Dense(1, name="value")
        self.carried = self.acting_state(a, self.act_ctx)
        self.h_width, self.c_width = state_widths(self.carried)

    def _embed(self, obs):
        return self.embed(obs).astype(jnp.float32)

    def _heads(self, x):
        h = self.norm_f(x)
        return jax.nn.log_softmax(self.logits_head(h)), self.value_head(h)

    def _unpack_h(self, h):
        """(B, h_width) -> per recurrent layer what it carries, (state, tail)
        or (tail,), float32, by one reshape: a family's recurrent layers carry
        the same shapes."""
        if not self.h_width:
            return []
        (shapes,) = {s for where, s in self.carried if where == "h"}
        ends = list(itertools.accumulate(math.prod(shape) for shape in shapes))
        per = h.reshape(h.shape[0], -1, ends[-1])
        return [
            tuple(
                per[:, i, start:end].reshape(-1, *shape)
                for start, end, shape in zip([0, *ends], ends, shapes)
            )
            for i in range(per.shape[1])
        ]

    def _unpack_c(self, c):
        """(B, c_width) -> each attention layer's ring, a tuple of its arrays,
        and the step counter (B,) int."""
        rings, at = [], 0
        for where, shapes in self.carried:
            if where != "c":
                continue
            arrays = []
            for shape in shapes:
                n = math.prod(shape)
                arrays.append(c[:, at: at + n].reshape(-1, *shape))
                at += n
            rings.append(tuple(arrays))
        return rings, c[:, -1].astype(jnp.int32)

    @staticmethod
    def _pack(pairs, B):
        return jnp.concatenate(
            [jnp.zeros((B, 0), jnp.float32)]
            + [a.reshape(B, -1).astype(jnp.float32) for pair in pairs for a in pair],
            axis=1,
        )

    def _unroll(self, obs, carry0, firsts):
        """``carry0 = (h, c)``: ``h`` of the acting width is the state the
        window starts from; any other width (the batch's 1-float placeholder)
        means zeros. ``c`` is returned as it came. Also returns what the
        layers handed back beside their output and their carry."""
        B = obs.shape[0]
        h0, c0 = carry0
        if h0.shape[-1] != self.h_width:
            h0 = jnp.zeros((B, self.h_width), jnp.float32)
        seg = segment_ids_from_firsts(firsts)
        x = self._embed(obs)
        states = iter(self._unpack_h(h0))
        carried, extras = [], []
        for layer, (where, shapes) in zip(self.layers, self.carried):
            if where == "h":
                x, *more = layer(x, seg, *next(states))
                carried.append(more[: len(shapes)])
                more = more[len(shapes):]
            else:
                x, *more = layer(x, seg)
            extras.extend(more)
        logits, value = self._heads(x)
        return logits, value, (self._pack(carried, B), c0), extras

    def __call__(self, obs, carry0, firsts):
        return self._unroll(obs, carry0, firsts)[:3]

    def unroll_routed(self, obs, carry0, firsts):
        """The unroll, and one record per expert layer in layer order: its
        routing (the choices and ``ops/moe.route_stats``) and what else the
        layer counted. What a layer without experts counted (glm4_moe_lite's
        leading dense layers: their attention) is added into the first expert
        layer's record; a family with no expert layer at all (evabyte) gets
        its layers' records back as they are."""
        *out, records = self._unroll(obs, carry0, firsts)
        routes = [r for r in records if "choice" in r]
        if not routes:
            return tuple(out), records
        for other in (r for r in records if "choice" not in r):
            for counter, spans in other.items():
                into = routes[0].setdefault(counter, {})
                for span, n in spans.items():
                    into[span] = into[span] + n if span in into else n
        return tuple(out), routes

    def act(self, obs, h, c):
        """One step for every row of ``obs`` (B, obs_dim)."""
        B = obs.shape[0]
        rings, count = self._unpack_c(c)
        x = self._embed(obs)
        states, rings = iter(self._unpack_h(h)), iter(rings)
        carried, caches = [], []
        for layer, (where, _) in zip(self.layers, self.carried):
            if where == "h":
                x, *carry = layer.step(x, *next(states))
                carried.append(carry)
            elif where == "c":
                x, *cache = layer.step(x, *next(rings), count)
                caches.append(cache)
            else:
                (x,) = layer.step(x)
        logits, value = self._heads(x)
        c2 = jnp.concatenate(
            [self._pack(caches, B), (count + 1).astype(jnp.float32)[:, None]], axis=1
        )
        return logits, value, (self._pack(carried, B), c2)
