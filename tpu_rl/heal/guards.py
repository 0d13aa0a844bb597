"""In-jit numerical-fault guards for ``train_step`` update loops.

Both helpers trace into the algo's jitted update, so the rules of the
hot-path checker apply: allocation-free by construction (a scalar ``&``
and one select a leaf, which XLA folds into the optimizer's own pass over
that leaf), no Python-level formatting, no containers. No conditional:
a branch is a fusion edge, and XLA would write out the clipped gradients
before it, run the diagnostics' norms as passes of their own after it and
copy every donated parameter that is read past it.

The guard contract every algo implements with these:

- ``cfg.update_guard`` off -> the update code is literally the pre-guard
  code (bit-identity is pinned per-algo in ``tests/test_heal.py``).
- guard on, clean step -> every leaf selects the applied value, which
  the ungated ops computed -> still bit-identical.
- guard on, non-finite loss or global grad-norm -> every leaf selects the
  *incoming* params/opt state, untouched whatever the applied side holds
  (a select does not propagate the unselected side's NaN), and the step's
  ``nonfinite-updates`` metric counts one skipped update.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def update_ok(loss, gnorm):
    """Scalar bool: this update's loss and global grad-norm are finite."""
    return jnp.isfinite(loss) & jnp.isfinite(gnorm)


def guarded(ok, apply_fn, fallback):
    """``apply_fn()`` where ``ok``, else ``fallback`` untouched: leaf by
    leaf, one select. ``apply_fn`` is an argless closure over the loop-local
    grads/state that returns a tree of ``fallback``'s structure; a skipped
    update's arithmetic is computed and dropped."""
    return jax.tree.map(lambda new, old: jnp.where(ok, new, old), apply_fn(), fallback)
