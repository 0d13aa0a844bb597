"""Loss primitives."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpu_rl.ops.distributions import categorical_kl  # re-export  # noqa: F401


def smooth_l1(pred: jax.Array, target: jax.Array, beta: float = 1.0) -> jax.Array:
    """Elementwise smooth-L1 (Huber) loss, mean-reduced — semantics of
    ``F.smooth_l1_loss(...)`` as used by every reference update loop
    (e.g. ``/root/reference/agents/learner_module/ppo/learning.py:74``)."""
    diff = jnp.abs(pred - target)
    loss = jnp.where(diff < beta, 0.5 * diff * diff / beta, diff - 0.5 * beta)
    return jnp.mean(loss)


def clip_subtree_by_global_norm(grads, max_norm: float, subtree: str | None = None):
    """Clip gradients by global norm, optionally only a named top-level subtree.

    The reference clips only the model parameters, not auxiliary scalars like
    V-MPO's Lagrange temperatures (``v_mpo/learning.py:111-114`` clips
    ``model.actor.parameters()`` while ``log_eta``/``log_alpha`` share the
    optimizer, ``learner.py:331-338``). ``subtree=None`` clips everything.

    Returns ``(clipped, gnorm, scale)``: the clip's factor goes to
    ``obs/learn.module_grad_norms`` with the *raw* gradients, so that the
    clipped ones have one reader, the optimizer.
    """
    if subtree is None:
        tree = grads
    else:
        tree = grads[subtree]
    leaves = jax.tree_util.tree_leaves(tree)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / (gnorm + 1e-6))
    clipped = jax.tree_util.tree_map(lambda g: g * scale, tree)
    if subtree is None:
        return clipped, gnorm, scale
    out = dict(grads)
    out[subtree] = clipped
    return out, gnorm, scale
