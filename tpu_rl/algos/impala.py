"""IMPALA (V-trace actor-critic) train step.

Functional re-design of ``/root/reference/agents/learner_module/impala/
learning.py:13-114``: V-trace targets/advantages computed no-grad
(rho in [0.1, 0.8], c_bar = 1.0, ``compute_loss.py:22-66``), policy-gradient
loss ``-(log_probs * advantages)``, smooth-L1 value loss to the V-trace
targets, entropy bonus — one jitted step with the V-trace recursion as a
reverse ``lax.scan``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from tpu_rl.algos.base import TrainState, rmsprop
from tpu_rl.algos.ppo import policy_outputs_routed
from tpu_rl.config import Config
from tpu_rl.heal.guards import guarded, update_ok
from tpu_rl.models.families import ModelFamily
from tpu_rl.obs.learn import (
    attention_scalars,
    route_scalars,
    rows_mean,
    update_scalars,
)
from tpu_rl.ops.losses import clip_subtree_by_global_norm, smooth_l1
from tpu_rl.ops.returns import vtrace
from tpu_rl.types import Batch


def make_train_step(cfg: Config, family: ModelFamily):
    opt = rmsprop(cfg)

    def loss_fn(params, batch: Batch):
        log_probs, entropy, value, logits, routes = policy_outputs_routed(family, params, batch)

        v_lo, v_hi = cfg.value_target_clip or (None, None)
        ratio, advantages, values_target = vtrace(
            behav_log_probs=batch.log_prob,
            target_log_probs=jax.lax.stop_gradient(log_probs),
            is_fir=batch.is_fir,
            rewards=batch.rew,
            values=jax.lax.stop_gradient(value),
            gamma=cfg.gamma,
            rho_bar=cfg.rho_bar,
            rho_min=cfg.rho_min,
            c_bar=cfg.c_bar,
            v_min=v_lo,
            v_max=v_hi,
        )

        loss_policy = -jnp.mean(log_probs[:, :-1] * advantages)
        loss_value = smooth_l1(value[:, :-1], values_target[:, :-1])
        policy_entropy = jnp.mean(entropy[:, :-1])

        loss = (
            cfg.policy_loss_coef * loss_policy
            + cfg.value_loss_coef * loss_value
            - cfg.entropy_coef * policy_entropy
        )
        metrics = {
            "loss": loss,
            "policy-loss": loss_policy,
            "value-loss": loss_value,
            "policy-entropy": policy_entropy,
            "min-ratio": jnp.min(ratio),
            "max-ratio": jnp.max(ratio),
            "avg-ratio": jnp.mean(ratio),
            # Saturation diagnostics: a categorical policy hits entropy
            # exactly 0 once logit gaps exceed ~90 (float32 one-hot); these
            # localize whether a collapse is advantage-driven or a logit
            # runaway (observed while diagnosing the async-cluster runs).
            "max-abs-logit": jnp.max(jnp.abs(logits)),
            "mean-value": jnp.mean(value),
            "max-abs-advantage": jnp.max(jnp.abs(advantages)),
            "mean-advantage": jnp.mean(advantages),
        }
        if cfg.learn_diag:
            # Learning-dynamics diag (tpu_rl.obs.learn). The UNCLIPPED
            # importance ratio drives ESS/KL and the clip-rate channels
            # (vtrace returns the clipped rho, which hides exactly the
            # tail the staleness curves are meant to expose).
            lr = jax.lax.stop_gradient(
                log_probs[:, :-1] - batch.log_prob[:, :-1]
            )
            w = jnp.exp(lr)
            vt = values_target[:, :-1]
            err = vt - jax.lax.stop_gradient(value[:, :-1])
            metrics["diag"] = {
                "rows": {
                    "ent": rows_mean(
                        jax.lax.stop_gradient(entropy[:, :-1])
                    ),
                    "kl": rows_mean(-lr),
                    "rho-clip": rows_mean(
                        (w >= cfg.rho_bar).astype(jnp.float32)
                    ),
                    "c-clip": rows_mean(
                        (w >= cfg.c_bar).astype(jnp.float32)
                    ),
                    "w": rows_mean(w),
                    "w2": rows_mean(jnp.square(w)),
                    "adv": rows_mean(advantages),
                    "adv2": rows_mean(jnp.square(advantages)),
                    "ret": rows_mean(vt),
                    "ret2": rows_mean(jnp.square(vt)),
                    "err": rows_mean(err),
                    "err2": rows_mean(jnp.square(err)),
                },
                "scalars": {**route_scalars(routes), **attention_scalars(routes)},
            }
        return loss, metrics

    guard = cfg.update_guard

    def train_step(state: TrainState, batch: Batch, key: jax.Array):
        params0 = state.params
        metrics = {}
        raw = scale = None
        nf = 0.0
        for _ in range(cfg.K_epoch):
            (_, metrics), raw = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch
            )
            with jax.named_scope("opt_update"):  # as algos/ppo.py's
                grads, gnorm, scale = clip_subtree_by_global_norm(
                    raw, cfg.max_grad_norm
                )
                if guard:
                    ok = update_ok(metrics["loss"], gnorm)

                    def _apply(grads=grads, state=state):
                        updates, opt_state = opt.update(
                            grads, state.opt_state, state.params
                        )
                        return optax.apply_updates(state.params, updates), opt_state

                    params, opt_state = guarded(
                        ok, _apply, (state.params, state.opt_state)
                    )
                    nf = nf + (1.0 - ok.astype(jnp.float32))
                else:
                    updates, opt_state = opt.update(
                        grads, state.opt_state, state.params
                    )
                    params = optax.apply_updates(state.params, updates)
            state = state.replace(params=params, opt_state=opt_state)
            metrics["grad-norm"] = gnorm
        if guard:
            metrics["nonfinite-updates"] = nf
        if cfg.learn_diag:
            with jax.named_scope("opt_update"):
                metrics["diag"]["scalars"].update(
                    update_scalars(raw, scale, state.params, params0)
                )
        return state.replace(step=state.step + 1), metrics

    return train_step
