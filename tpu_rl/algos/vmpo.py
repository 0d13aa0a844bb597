"""V-MPO train step.

Functional re-design of ``/root/reference/agents/learner_module/v_mpo/
learning.py:14-144`` plus the Lagrange-temperature machinery of
``LearnerSingleVMPO`` (``agents/learner.py:320-348``):

- GAE advantages (no-grad), then **top-half selection over the batch axis**
  per time step (``v_mpo/learning.py:60-64``),
- softmax weights psi over the flattened selected advantages / eta
  (``:66-74``), weighted maximum-likelihood policy loss,
- temperature loss ``eta*coef_eta + eta*log(mean(exp(ratio)))`` (``:82-85``),
- KL Lagrange loss with a per-update log-uniform-sampled KL bound
  (``:87-92``, ``learner.py:340-348``) — sampled inside the step from the
  explicit RNG key,
- one RMSprop over model + log_eta + log_alpha, grad-clip on the model
  subtree only (``:108-114``, ``learner.py:331-338``).

The top-k runs over the *global* batch inside ``jit``, so under a data-sharded
mesh XLA inserts the cross-chip gather — the per-batch statistics stay exact
(BASELINE.md config 5 stresses exactly this).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import optax

from tpu_rl.algos.base import TrainState, rmsprop
from tpu_rl.algos.ppo import policy_outputs_routed, td_target_and_gae
from tpu_rl.config import Config
from tpu_rl.heal.guards import guarded, update_ok
from tpu_rl.models.families import ModelFamily
from tpu_rl.obs.learn import (
    attention_scalars,
    route_scalars,
    rows_mean,
    update_scalars,
)
from tpu_rl.ops.distributions import categorical_kl
from tpu_rl.ops.losses import clip_subtree_by_global_norm, smooth_l1
from tpu_rl.types import Batch


def top_half_mask(adv: jax.Array, k: int) -> jax.Array:
    """0/1 mask over the batch axis selecting the per-timestep top-``k``
    advantages, for ``adv`` of shape (B, T, 1).

    Replaces ``torch.topk(x, k, dim=0)`` + index gather
    (``v_mpo/learning.py:60-64``): the k-th largest value per timestep is
    found with one plain value sort, then membership is a broadcast
    compare. Same selection, but no ``top_k`` variadic sort and no
    ``take_along_axis`` gather — both lower poorly on TPU (measured 10x
    step-time anomaly vs sibling algos at the reference quantum, round 4).

    Exact-tie corner: where several batch entries share the threshold
    value the mask keeps all of them (>k selected) while ``topk`` keeps an
    arbitrary k. Tied entries have identical ratios, so psi mass shifts
    only between equally-weighted terms; GAE advantages are continuous so
    measure-zero in practice. The temperature dual normalizes by the
    ACTUAL mask count (sum(mask), not a static k*T), so over-selection
    under ties does not bias eta.
    """
    kth_largest = -jnp.sort(-adv, axis=0)[k - 1]  # (T, 1)
    return (adv >= kth_largest).astype(adv.dtype)  # (B, T, 1)


def make_train_step(cfg: Config, family: ModelFamily):
    opt = rmsprop(cfg)

    def loss_fn(params, batch: Batch, key: jax.Array):
        log_probs, _entropy, value, logits, routes = policy_outputs_routed(family, params, batch)
        td_target, advantage = td_target_and_gae(cfg, batch, value)

        eta = jnp.exp(params["log_eta"])
        alpha = jnp.exp(params["log_alpha"])

        # top 50% of the *actual* batch per time step (v_mpo/learning.py:60-64),
        # selected by threshold mask instead of topk+gather (see top_half_mask)
        k = math.ceil(batch.batch_size / 2)
        mask = top_half_mask(advantage, k)
        ratio = advantage / (jax.lax.stop_gradient(eta) + 1e-7)  # no-grad

        # psi = softmax over the selected (b, t) entries, flattened — computed
        # in place via a masked logsumexp (unselected entries get zero weight)
        lse = jax.nn.logsumexp(jnp.where(mask > 0, ratio, -jnp.inf))
        psi = mask * jnp.exp(ratio - lse)
        # where() (not psi*lp) so a -inf log-prob outside the mask can't 0*inf
        loss_policy = -jnp.sum(psi * jnp.where(mask > 0, log_probs[:, :-1], 0.0))

        loss_value = smooth_l1(value[:, :-1], td_target)

        # Temperature dual. The reference computes ``ratio.exp().mean().log()``
        # (``v_mpo/learning.py:84``), which overflows to inf -> NaN once any
        # ratio exceeds ~88 (observed in long K_epoch>1 runs when eta anneals
        # low while advantages spike). logsumexp(r) - log(N) is the same
        # quantity in exact arithmetic, stable for any ratio magnitude —
        # documented divergence, numerics only.
        # N must be the ACTUAL selected count: the tie-keeping mask can
        # select more than k entries (see top_half_mask), and a static k*T
        # would then misnormalize the dual toward a too-large eta. Counting
        # the mask keeps the dual exact under ties; stop_gradient because N
        # is a set size, not a function to differentiate through.
        n_selected = jax.lax.stop_gradient(jnp.sum(mask))
        loss_temperature = eta * cfg.coef_eta + eta * (lse - jnp.log(n_selected))

        # per-update KL bound, log-uniform in [coef_alpha_below, coef_alpha_upper]
        lo, hi = math.log(cfg.coef_alpha_below), math.log(cfg.coef_alpha_upper)
        coef_alpha = jnp.exp(jax.random.uniform(key, (), minval=lo, maxval=hi))

        kl = categorical_kl(batch.logits[:, :-1], logits[:, :-1])
        loss_alpha = jnp.mean(
            alpha * (coef_alpha - jax.lax.stop_gradient(kl))
            + jax.lax.stop_gradient(alpha) * kl
        )

        loss = (
            cfg.policy_loss_coef * loss_policy
            + cfg.value_loss_coef * loss_value
            + loss_temperature
            + loss_alpha
        )
        metrics = {
            "loss": loss,
            "policy-loss": loss_policy,
            "value-loss": loss_value,
            "loss-temperature": loss_temperature,
            "loss-alpha": loss_alpha,
            "eta": eta,
            "vmpo-alpha": alpha,
            "kl": jnp.mean(kl),
        }
        if cfg.learn_diag:
            # Learning-dynamics diag (tpu_rl.obs.learn): action-level k1
            # approx-KL / importance weights vs the behavior policy (the
            # full-distribution KL above is the trust-region dual's input;
            # this one is the cross-algo-comparable staleness channel).
            lr = jax.lax.stop_gradient(
                log_probs[:, :-1] - batch.log_prob[:, :-1]
            )
            w = jnp.exp(lr)
            err = td_target - jax.lax.stop_gradient(value[:, :-1])
            metrics["diag"] = {
                "rows": {
                    "ent": rows_mean(
                        jax.lax.stop_gradient(_entropy[:, :-1])
                    ),
                    "kl": rows_mean(-lr),
                    "w": rows_mean(w),
                    "w2": rows_mean(jnp.square(w)),
                    "adv": rows_mean(advantage),
                    "adv2": rows_mean(jnp.square(advantage)),
                    "ret": rows_mean(td_target),
                    "ret2": rows_mean(jnp.square(td_target)),
                    "err": rows_mean(err),
                    "err2": rows_mean(jnp.square(err)),
                },
                "scalars": {
                    # Temperature / trust-region Lagrange state: the knobs
                    # V-MPO self-tunes, surfaced next to the curves they
                    # shape.
                    "eta": jax.lax.stop_gradient(eta),
                    "vmpo-alpha": jax.lax.stop_gradient(alpha),
                    **route_scalars(routes),
                    **attention_scalars(routes),
                },
            }
        return loss, metrics

    guard = cfg.update_guard

    def train_step(state: TrainState, batch: Batch, key: jax.Array):
        params0 = state.params
        metrics = {}
        raw = scale = None
        nf = 0.0
        for e in range(cfg.K_epoch):
            ekey = jax.random.fold_in(key, e)
            (_, metrics), raw = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch, ekey
            )
            with jax.named_scope("opt_update"):  # as algos/ppo.py's
                grads, gnorm, scale = clip_subtree_by_global_norm(
                    raw, cfg.max_grad_norm, subtree="actor"
                )
                if guard:
                    ok = update_ok(metrics["loss"], gnorm)

                    def _apply(grads=grads, state=state):
                        updates, opt_state = opt.update(
                            grads, state.opt_state, state.params
                        )
                        params = optax.apply_updates(state.params, updates)
                        # The eta floor projection belongs to the applied
                        # side: a skipped update must leave params bitwise
                        # untouched.
                        params["log_eta"] = jnp.maximum(
                            params["log_eta"], jnp.log(1e-6)
                        )
                        return params, opt_state

                    params, opt_state = guarded(
                        ok, _apply, (state.params, state.opt_state)
                    )
                    nf = nf + (1.0 - ok.astype(jnp.float32))
                else:
                    updates, opt_state = opt.update(
                        grads, state.opt_state, state.params
                    )
                    params = optax.apply_updates(state.params, updates)
                    # Projected floor on the temperature: eta -> 0 makes the
                    # psi weights one-hot and the advantage ratios arbitrarily
                    # large. Projection after the step (not clipping inside
                    # the loss, which would zero the dual's gradient and
                    # freeze it below the floor).
                    params["log_eta"] = jnp.maximum(
                        params["log_eta"], jnp.log(1e-6)
                    )
            state = state.replace(params=params, opt_state=opt_state)
            metrics["grad-norm"] = gnorm
        if guard:
            metrics["nonfinite-updates"] = nf
        if cfg.learn_diag:
            with jax.named_scope("opt_update"):
                metrics["diag"]["scalars"].update(
                    # the clip scaled the actor subtree alone
                    update_scalars(raw, {"actor": scale}, state.params, params0)
                )
        return state.replace(step=state.step + 1), metrics

    return train_step
