"""SAC train steps (discrete and continuous).

Functional re-design of ``/root/reference/agents/learner_module/sac/
learning.py:13-163`` and ``sac_continuous/learning.py:13-151`` plus the
``LearnerSeperate`` setup (``agents/learner.py:351-367``): three sequential
optimizer updates per step (actor, temperature, twin critic), soft TD targets
from a *separate* target critic (fixing the reference's self-aliasing no-op
target, ``learner.py:355-358``), Polyak update tau=0.005
(``compute_loss.py:69-71``), target entropy = action-space size
(``learner.py:363-365``). All three updates fuse into one jitted step; the
continuous variant reparameterizes through the tanh-squashed Gaussian with
explicit RNG keys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from tpu_rl.algos.base import SACState, adam
from tpu_rl.config import Config
from tpu_rl.heal.guards import guarded, update_ok
from tpu_rl.models.families import ModelFamily
from tpu_rl.obs.learn import (
    module_grad_norms,
    rows_mean,
    tree_delta_norm,
    tree_norm,
)
from tpu_rl.ops.distributions import normal_log_prob, tanh_normal_sample
from tpu_rl.ops.losses import clip_subtree_by_global_norm, smooth_l1
from tpu_rl.ops.target import polyak_update
from tpu_rl.types import Batch

sg = jax.lax.stop_gradient


def make_train_step(cfg: Config, family: ModelFamily):
    opt_actor, opt_critic = adam(cfg), adam(cfg)
    opt_alpha = (
        adam(cfg)
        if cfg.alpha_lr is None
        else adam(cfg.replace(lr=cfg.alpha_lr))
    )
    continuous = family.continuous
    # Target entropy — documented divergence from the reference, which sets
    # target = +action_space for BOTH variants (``learner.py:363-365``).
    # That target is unreachable for a tanh-squashed Gaussian (support
    # (-1,1)^A caps differential entropy at A*log2 < A), and together with
    # the reference's alpha-loss sign (below) the temperature never
    # equilibrates. Standard practice instead: continuous -dim(A)
    # (Haarnoja et al. 2018), discrete 0.98*log|A| (Christodoulou 2019).
    # cfg.target_entropy overrides the rule when set.
    if cfg.sac_reference_alpha:
        # Strict parity (Config.sac_reference_alpha): the reference's exact
        # rule, +action_space for both variants (``learner.py:363-365``).
        target_entropy = float(cfg.action_space)
    elif cfg.target_entropy is not None:
        target_entropy = float(cfg.target_entropy)
    elif continuous:
        target_entropy = -float(cfg.action_space)
    else:
        target_entropy = 0.98 * float(jnp.log(cfg.action_space))

    guard = cfg.update_guard

    def _critic_apply(cp, batch: Batch, act, carry0):
        if continuous:
            return family.critic_unroll(cp, batch.obs, act, carry0, batch.is_fir)
        return family.critic_unroll(cp, batch.obs, carry0, batch.is_fir)

    def one_epoch(state: SACState, batch: Batch, key: jax.Array):
        carry0 = (batch.hx[:, 0], batch.cx[:, 0])
        fir = batch.is_fir
        k_pol, k_cri = jax.random.split(key)

        # ---- 1) actor update (sac/learning.py:36-62, sac_continuous:35-55)
        alpha_d = sg(jnp.exp(state.log_alpha))

        def actor_loss(ap):
            if continuous:
                mu, log_std = family.actor_unroll(ap, batch.obs, carry0, fir)
                a_pol, logp = tanh_normal_sample(k_pol, mu, jnp.exp(log_std))
                q1, q2 = _critic_apply(state.critic_params, batch, a_pol, carry0)
                min_q = jnp.minimum(q1, q2)
                # total log-prob: per-dim log-probs summed over action dims,
                # so the entropy coefficient the policy feels and the
                # -dim(A) target the controller tunes against agree for any
                # action dimensionality
                logp_tot = jnp.sum(logp, axis=-1, keepdims=True)
                loss_policy = jnp.mean((alpha_d * logp_tot - min_q)[:, :-1])
                ent_neg = logp_tot[:, :-1, 0]
            else:
                probs, logp = family.actor_unroll(ap, batch.obs, carry0, fir)
                q1, q2 = _critic_apply(state.critic_params, batch, None, carry0)
                min_q = jnp.minimum(q1, q2)
                loss_policy = jnp.mean(
                    jnp.sum((probs * (alpha_d * logp - min_q))[:, :-1], axis=-1)
                )
                ent_neg = jnp.sum((probs * logp)[:, :-1], axis=-1)
            return loss_policy, ent_neg

        (loss_policy, ent_neg), raw_actor = jax.value_and_grad(
            actor_loss, has_aux=True
        )(state.actor_params)
        with jax.named_scope("opt_update"):  # as algos/ppo.py's
            g_actor, gn_actor, sc_actor = clip_subtree_by_global_norm(
                raw_actor, cfg.max_grad_norm
            )
            if guard:
                ok_a = update_ok(loss_policy, gn_actor)

                def _apply_actor():
                    up, actor_opt = opt_actor.update(
                        g_actor, state.actor_opt, state.actor_params
                    )
                    return optax.apply_updates(state.actor_params, up), actor_opt

                actor_params, actor_opt = guarded(
                    ok_a, _apply_actor, (state.actor_params, state.actor_opt)
                )
            else:
                up, actor_opt = opt_actor.update(
                    g_actor, state.actor_opt, state.actor_params
                )
                actor_params = optax.apply_updates(state.actor_params, up)

        # ---- 2) temperature update (sac/learning.py:64-74). Documented
        # divergence: the reference computes +alpha*(logpi + target), whose
        # feedback runs BACKWARDS (an entropy deficit shrinks alpha toward 0,
        # killing exploration — measured on MountainCarContinuous: 2/3 seeds
        # collapse, greedy as low as -69). Standard SAC minimizes
        # -alpha*(logpi + target): deficit -> alpha grows -> more entropy
        # pressure; surplus -> alpha shrinks.
        ref_sign = 1.0 if cfg.sac_reference_alpha else -1.0

        def alpha_loss_fn(log_alpha):
            return ref_sign * jnp.mean(
                jnp.exp(log_alpha) * (sg(ent_neg) + target_entropy)
            )

        loss_alpha, g_alpha = jax.value_and_grad(alpha_loss_fn)(state.log_alpha)
        if guard:
            ok_al = jnp.isfinite(loss_alpha)

            def _apply_alpha():
                up, alpha_opt = opt_alpha.update(
                    g_alpha, state.alpha_opt, state.log_alpha
                )
                la = optax.apply_updates(state.log_alpha, up)
                if cfg.alpha_min > 0.0:
                    la = jnp.maximum(la, jnp.log(cfg.alpha_min))
                return la, alpha_opt

            log_alpha, alpha_opt = guarded(
                ok_al, _apply_alpha, (state.log_alpha, state.alpha_opt)
            )
        else:
            up, alpha_opt = opt_alpha.update(
                g_alpha, state.alpha_opt, state.log_alpha
            )
            log_alpha = optax.apply_updates(state.log_alpha, up)
            if cfg.alpha_min > 0.0:
                # Exploration floor (Config.alpha_min): clamp post-update so the
                # controller can still raise alpha freely but cannot extinguish
                # exploration on sparse-goal envs.
                log_alpha = jnp.maximum(log_alpha, jnp.log(cfg.alpha_min))

        # ---- 3) critic update with updated actor + alpha (sac/learning.py:76-120)
        alpha2 = sg(jnp.exp(log_alpha))
        if continuous:
            mu, log_std = family.actor_unroll(actor_params, batch.obs, carry0, fir)
            a_cri, logp_cri = tanh_normal_sample(k_cri, mu, jnp.exp(log_std))
            tq1, tq2 = _critic_apply(
                state.target_critic_params, batch, a_cri, carry0
            )
            # total log-prob (see the actor loss): keeps the TD target's
            # entropy bonus dimension-correct and leaves soft_q (B, T, 1),
            # so the shared sum() below is a no-op for this branch
            soft_q = jnp.minimum(tq1, tq2) - alpha2 * jnp.sum(
                logp_cri, axis=-1, keepdims=True
            )
        else:
            probs_cri, logp_cri = family.actor_unroll(
                actor_params, batch.obs, carry0, fir
            )
            tq1, tq2 = _critic_apply(state.target_critic_params, batch, None, carry0)
            soft_q = probs_cri * (jnp.minimum(tq1, tq2) - alpha2 * logp_cri)
        soft_q = sg(soft_q)
        td_target = batch.rew[:, :-1] + (1.0 - fir[:, 1:]) * cfg.gamma * jnp.sum(
            soft_q[:, 1:], axis=-1, keepdims=True
        )

        def critic_loss(cp):
            if continuous:
                q1, q2 = _critic_apply(cp, batch, batch.act, carry0)
            else:
                q1, q2 = _critic_apply(cp, batch, None, carry0)
                a_idx = batch.act.astype(jnp.int32)
                q1 = jnp.take_along_axis(q1, a_idx, axis=-1)
                q2 = jnp.take_along_axis(q2, a_idx, axis=-1)
            return smooth_l1(q1[:, :-1], td_target) + smooth_l1(
                q2[:, :-1], td_target
            )

        loss_value, raw_critic = jax.value_and_grad(critic_loss)(state.critic_params)
        with jax.named_scope("opt_update"):
            g_critic, gn_critic, sc_critic = clip_subtree_by_global_norm(
                raw_critic, cfg.max_grad_norm
            )
            if guard:
                ok_c = update_ok(loss_value, gn_critic)

                def _apply_critic():
                    up, critic_opt = opt_critic.update(
                        g_critic, state.critic_opt, state.critic_params
                    )
                    cp = optax.apply_updates(state.critic_params, up)
                    # Polyak tracks only APPLIED critic steps: a skipped update
                    # must leave the target frozen too, or the twin targets
                    # drift toward a never-taken critic.
                    return cp, critic_opt, polyak_update(
                        cp, state.target_critic_params, cfg.tau
                    )

                critic_params, critic_opt, target_critic_params = guarded(
                    ok_c,
                    _apply_critic,
                    (state.critic_params, state.critic_opt, state.target_critic_params),
                )
            else:
                up, critic_opt = opt_critic.update(
                    g_critic, state.critic_opt, state.critic_params
                )
                critic_params = optax.apply_updates(state.critic_params, up)

                # ---- 4) Polyak target update (a real one — see module docstring)
                target_critic_params = polyak_update(
                    critic_params, state.target_critic_params, cfg.tau
                )

        metrics = {
            "loss": cfg.policy_loss_coef * loss_policy
            + cfg.value_loss_coef * loss_value,
            "policy-loss": loss_policy,
            "value-loss": loss_value,
            "loss_alpha": loss_alpha,
            "alpha": jnp.exp(log_alpha),
        }
        if cfg.learn_diag:
            # Learning-dynamics diag (tpu_rl.obs.learn), off-policy flavor:
            # KL / importance weights compare the CURRENT actor's log-prob
            # of the replayed action against the behavior log-prob stored
            # with it — the staleness channel for a replay-fed learner —
            # plus the soft TD target moments (the "target-Q stats" row of
            # the diag table). Everything reuses the critic-section
            # forward; nothing feeds back (bit-identity pinned in tests).
            if continuous:
                pre = jnp.arctanh(
                    jnp.clip(batch.act, -1.0 + 1e-6, 1.0 - 1e-6)
                )
                logp_act = normal_log_prob(
                    mu, jnp.exp(log_std), pre
                ) - jnp.log(1.0 - jnp.square(batch.act) + 1e-7)
                lr = jnp.sum(logp_act - batch.log_prob, axis=-1)[:, :-1]
            else:
                logp_new = jnp.take_along_axis(
                    logp_cri, batch.act.astype(jnp.int32), axis=-1
                )
                lr = (logp_new - batch.log_prob)[:, :-1, 0]
            # Entropy rows come from the ACTOR section's ``ent_neg`` aux —
            # it is already materialized for the alpha loss, so the diag
            # adds no new consumer to the critic-section forward (a fresh
            # ``probs * logp`` product there refuses XLA's critic-update
            # kernels and breaks the bitwise contract by ~1 ulp; measured).
            ent_rows = -ent_neg
            lr = sg(lr)
            w = jnp.exp(lr)
            # optimization_barrier: the diag's extra reductions over
            # td_target must not refuse into the update's own kernels
            # (measured: without the barrier XLA reassociates the critic
            # update by ~1 ulp, breaking the bitwise contract). The module
            # norms need none: they read the raw gradients with each clip's
            # factor, so the clipped ones keep one reader, their optimizer
            # (obs/learn.py).
            tq_rows = jax.lax.optimization_barrier(td_target)
            with jax.named_scope("opt_update"):
                g_norms = module_grad_norms(
                    {"actor": raw_actor, "critic": raw_critic},
                    {"actor": sc_actor, "critic": sc_critic},
                )
            metrics["diag"] = {
                "rows": {
                    "ent": rows_mean(sg(ent_rows)),
                    "kl": rows_mean(-lr),
                    "w": rows_mean(w),
                    "w2": rows_mean(jnp.square(w)),
                    "tq": rows_mean(tq_rows),
                    "tq2": rows_mean(jnp.square(tq_rows)),
                },
                "scalars": {
                    "alpha": jnp.exp(log_alpha),
                    **{f"grad-norm-{k}": v for k, v in g_norms.items()},
                },
            }
        if guard:
            metrics["grad-norm"] = gn_actor + gn_critic
            metrics["nonfinite-updates"] = 1.0 - (
                ok_a & ok_al & ok_c
            ).astype(jnp.float32)
        return (
            state.replace(
                actor_params=actor_params,
                critic_params=critic_params,
                target_critic_params=target_critic_params,
                log_alpha=log_alpha,
                actor_opt=actor_opt,
                critic_opt=critic_opt,
                alpha_opt=alpha_opt,
            ),
            metrics,
        )

    def train_step(state: SACState, batch: Batch, key: jax.Array):
        params0 = (state.actor_params, state.critic_params, state.log_alpha)
        metrics = {}
        nf = 0.0
        for e in range(cfg.K_epoch):
            state, metrics = one_epoch(state, batch, jax.random.fold_in(key, e))
            if guard:
                nf = nf + metrics.pop("nonfinite-updates")
        if guard:
            metrics["nonfinite-updates"] = nf
        if cfg.learn_diag:
            params1 = (
                state.actor_params, state.critic_params, state.log_alpha,
            )
            with jax.named_scope("opt_update"):
                metrics["diag"]["scalars"]["update-norm"] = tree_delta_norm(
                    params1, params0
                )
                metrics["diag"]["scalars"]["param-norm"] = tree_norm(params1)
        return state.replace(step=state.step + 1), metrics

    return train_step
