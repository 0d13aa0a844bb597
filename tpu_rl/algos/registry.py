"""Algorithm registry.

One declarative table replaces the reference's three parallel switch dicts
(``/root/reference/main.py:98-110`` model/learner classes, ``:215-222``
learning-chain coroutines, ``:310-321`` shared-memory factories).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax

from tpu_rl.algos import impala, ppo, sac, vmpo
from tpu_rl.algos.base import make_train_state
from tpu_rl.config import Config, is_off_policy
from tpu_rl.models.families import ALGOS, ModelFamily, build_family
from tpu_rl.obs.trace import span_of


@dataclass(frozen=True)
class AlgoSpec:
    name: str
    on_policy: bool  # on-policy ring vs off-policy replay (main.py:310-321)
    make_train_step: Callable[[Config, ModelFamily], Callable]

    def build(self, cfg: Config, key: jax.Array, mesh=None, span=None):
        """Returns (family, initial_state, train_step). ``mesh`` is only
        needed for sequence-parallel transformer families. ``span`` is the
        caller's ``TraceRecorder.span``, for a chip owner that times its
        start-up: the three lines are three spans of its lane ``startup``
        (``train-state`` is the eager ``init_params`` and the optimizer's
        ``init``: minutes at a catalog model's widths in a cold cache)."""
        span = span_of(None) if span is None else span
        with span("family", tid="startup"):
            family = build_family(cfg, mesh=mesh)
        with span("train-state", tid="startup"):
            state = make_train_state(cfg, family, key)
        with span("step-build", tid="startup"):
            train_step = self.make_train_step(cfg, family)
        return family, state, train_step


_REGISTRY: dict[str, AlgoSpec] = {
    "PPO": AlgoSpec("PPO", True, ppo.make_train_step),
    "PPO-Continuous": AlgoSpec("PPO-Continuous", True, ppo.make_train_step),
    "IMPALA": AlgoSpec("IMPALA", True, impala.make_train_step),
    "V-MPO": AlgoSpec("V-MPO", True, vmpo.make_train_step),
    "SAC": AlgoSpec("SAC", False, sac.make_train_step),
    "SAC-Continuous": AlgoSpec("SAC-Continuous", False, sac.make_train_step),
}

assert set(_REGISTRY) == set(ALGOS)
# The storage-semantics table in config.py must agree with the specs here.
assert all(spec.on_policy != is_off_policy(name) for name, spec in _REGISTRY.items())


def get_algo(name: str) -> AlgoSpec:
    if name not in _REGISTRY:
        raise ValueError(f"unknown algo {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]
