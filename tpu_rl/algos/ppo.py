"""PPO (discrete and continuous) train step.

Functional re-design of ``/root/reference/agents/learner_module/ppo/learning.py:13-126``:
the clipped-surrogate update with TD(lambda)/GAE advantages masked by
``(1 - is_fir[:, 1:])``, smooth-L1 value loss against a no-grad TD target,
entropy bonus, global-norm grad clip, RMSprop — all fused into one jitted step.
``K_epoch`` epochs unroll statically inside the step (reference ``:36``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from tpu_rl.algos.base import TrainState, rmsprop
from tpu_rl.config import Config
from tpu_rl.heal.guards import guarded, update_ok
from tpu_rl.models.families import ModelFamily
from tpu_rl.obs.learn import (
    attention_scalars,
    route_scalars,
    rows_mean,
    update_scalars,
)
from tpu_rl.ops import distributions as D
from tpu_rl.ops.losses import clip_subtree_by_global_norm, smooth_l1
from tpu_rl.ops.returns import gae
from tpu_rl.types import Batch


def policy_outputs(family: ModelFamily, params, batch: Batch):
    """Shared-torso forward for the on-policy families. Returns
    (log_probs (B,S,Alp), entropy (B,S,1), value (B,S,1), logits (B,S,A))."""
    return policy_outputs_routed(family, params, batch)[:4]


def policy_outputs_routed(family: ModelFamily, params, batch: Batch):
    """``policy_outputs`` and, fifth, each expert layer's routing (the chosen
    experts per step and the counters; ``models/backbone.py``): an empty
    list for a family without expert layers."""
    carry0 = (batch.hx[:, 0], batch.cx[:, 0])
    routes = []
    if family.continuous:
        mu, std, value, _ = family.actor_unroll(
            params["actor"], batch.obs, carry0, batch.is_fir
        )
        log_probs = D.normal_log_prob(mu, std, batch.act)  # per-dim (B,S,A)
        entropy = jnp.mean(D.normal_entropy(std), axis=-1, keepdims=True)
        logits = jnp.zeros_like(mu)
    else:
        if family.route_unroll is not None:
            (logits, value, _), routes = family.route_unroll(
                params["actor"], batch.obs, carry0, batch.is_fir
            )
        else:
            logits, value, _ = family.actor_unroll(
                params["actor"], batch.obs, carry0, batch.is_fir
            )
        acts = batch.act[..., 0]
        log_probs = D.categorical_log_prob(logits, acts)[..., None]
        entropy = D.categorical_entropy(logits)[..., None]
    return log_probs, entropy, value, logits, routes


def td_target_and_gae(cfg: Config, batch: Batch, value: jax.Array):
    """No-grad TD target and GAE advantages (reference ``ppo/learning.py:48-57``)."""
    v = jax.lax.stop_gradient(value)
    td_target = batch.rew[:, :-1] + cfg.gamma * (1.0 - batch.is_fir[:, 1:]) * v[:, 1:]
    delta = td_target - v[:, :-1]
    return td_target, gae(delta, cfg.gamma, cfg.lmbda)


def make_train_step(cfg: Config, family: ModelFamily):
    opt = rmsprop(cfg)

    def loss_fn(params, batch: Batch):
        log_probs, entropy, value, _, routes = policy_outputs_routed(family, params, batch)
        td_target, advantage = td_target_and_gae(cfg, batch, value)

        ratio = jnp.exp(log_probs[:, :-1] - batch.log_prob[:, :-1])
        surr1 = ratio * advantage
        surr2 = (
            jnp.clip(ratio, 1.0 - cfg.eps_clip, 1.0 + cfg.eps_clip) * advantage
        )
        loss_policy = -jnp.mean(jnp.minimum(surr1, surr2))
        loss_value = smooth_l1(value[:, :-1], td_target)
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
        }
        if cfg.learn_diag:
            # Learning-dynamics diag (tpu_rl.obs.learn): per-row moment
            # means of quantities the loss already computed — all no-grad,
            # never fed back (bit-identity pinned in tests).
            lr = jax.lax.stop_gradient(
                log_probs[:, :-1] - batch.log_prob[:, :-1]
            )
            w = jnp.exp(lr)
            ent = jax.lax.stop_gradient(entropy[:, :-1])
            err = td_target - jax.lax.stop_gradient(value[:, :-1])
            metrics["diag"] = {
                "rows": {
                    "ent": rows_mean(ent),
                    # k1 approx-KL estimator: E[logp_behav - logp_new]
                    "kl": rows_mean(-lr),
                    "clip": rows_mean(
                        (jnp.abs(w - 1.0) > cfg.eps_clip).astype(jnp.float32)
                    ),
                    "w": rows_mean(w),
                    "w2": rows_mean(jnp.square(w)),
                    "adv": rows_mean(advantage),
                    "adv2": rows_mean(jnp.square(advantage)),
                    "ret": rows_mean(td_target),
                    "ret2": rows_mean(jnp.square(td_target)),
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
            # Clip + guard + RMSprop under one scope: the device trace reads
            # the optimizer's share of an update from it (one pass over every
            # parameter and its second moment, bandwidth-bound; the
            # diagnostics' norms below ride the same pass).
            with jax.named_scope("opt_update"):
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
