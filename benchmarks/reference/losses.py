"""Plain NumPy losses of the two algorithms the benchmark runs, computed on the
host in float64 from the reference forward's logits and values. Reverse-time
Python loops, as the upstream project writes them
(``agents/learner_module/compute_loss.py``); independent of ``tpu_rl/algos``
and ``tpu_rl/ops``.

All arrays are (B, S, 1) except ``logits`` (B, S, A). The last step of a
window only bootstraps: every mean runs over steps ``0 .. S-2``.
"""

from __future__ import annotations

import numpy as np


def _policy_terms(logits, act):
    logits = np.asarray(logits, np.float64)
    idx = np.asarray(act)[..., :1].astype(np.int64)
    log_prob = np.take_along_axis(logits, idx, axis=-1)
    entropy = -(np.exp(logits) * logits).sum(-1, keepdims=True)
    return log_prob, entropy


def smooth_l1(pred, target) -> float:
    d = np.abs(pred - target)
    return float(np.where(d < 1.0, 0.5 * d * d, d - 0.5).mean())


def ppo(logits, value, batch: dict, params: dict) -> dict:
    """Clipped surrogate with GAE(lambda) advantages and a smooth-L1 value
    loss against the one-step TD target; no masking inside the recursion."""
    g, lam = params.get("gamma", 0.99), params.get("lmbda", 0.95)
    eps = params.get("eps_clip", 0.1)
    v = np.asarray(value, np.float64)
    rew, fir = (np.asarray(batch[k], np.float64) for k in ("rew", "is_fir"))
    log_prob, entropy = _policy_terms(logits, batch["act"])
    td_target = rew[:, :-1] + g * (1.0 - fir[:, 1:]) * v[:, 1:]
    delta = td_target - v[:, :-1]
    adv = np.zeros_like(delta)
    run = np.zeros_like(delta[:, 0])
    for t in reversed(range(delta.shape[1])):
        run = delta[:, t] + g * lam * run
        adv[:, t] = run
    ratio = np.exp(log_prob[:, :-1] - np.asarray(batch["log_prob"], np.float64)[:, :-1])
    policy = -np.minimum(ratio * adv, np.clip(ratio, 1 - eps, 1 + eps) * adv).mean()
    return _total(policy, smooth_l1(v[:, :-1], td_target), entropy[:, :-1].mean(), params)


def impala(logits, value, batch: dict, params: dict) -> dict:
    """V-trace with the upstream clips: rho in [rho_min, rho_bar], c <= c_bar."""
    g = params.get("gamma", 0.99)
    rho_bar, rho_min = params.get("rho_bar", 0.8), params.get("rho_min", 0.1)
    c_bar = params.get("c_bar", 1.0)
    v = np.asarray(value, np.float64)
    rew, fir = (np.asarray(batch[k], np.float64) for k in ("rew", "is_fir"))
    log_prob, entropy = _policy_terms(logits, batch["act"])
    ratio = np.exp(log_prob[:, :-1] - np.asarray(batch["log_prob"], np.float64)[:, :-1])
    rho, c = np.clip(ratio, rho_min, rho_bar), np.minimum(ratio, c_bar)
    disc = g * (1.0 - fir[:, 1:])
    delta = rho * (rew[:, :-1] + disc * v[:, 1:] - v[:, :-1])
    vs = v.copy()  # vs[S-1] = V[S-1]
    run = np.zeros_like(delta[:, 0])
    for t in reversed(range(delta.shape[1])):
        run = delta[:, t] + c[:, t] * disc[:, t] * run
        vs[:, t] = v[:, t] + run
    adv = rho * (rew[:, :-1] + disc * vs[:, 1:] - v[:, :-1])
    policy = -(log_prob[:, :-1] * adv).mean()
    return _total(policy, smooth_l1(v[:, :-1], vs[:, :-1]), entropy[:, :-1].mean(), params)


def _total(policy: float, value: float, entropy: float, params: dict) -> dict:
    loss = (
        params.get("policy_loss_coef", 1.0) * policy
        + params.get("value_loss_coef", 0.5) * value
        - params.get("entropy_coef", 0.00005) * entropy
    )
    return {"loss": float(loss), "policy-loss": float(policy),
            "value-loss": float(value), "policy-entropy": float(entropy)}


LOSSES = {"PPO": ppo, "IMPALA": impala}
