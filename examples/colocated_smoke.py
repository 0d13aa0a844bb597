"""Colocated-mode smoke: the fused on-device loop learns — the `make ci`
gate for ISSUE 7 (Anakin-mode colocated envs).

On the CPU backend, a short colocated PPO run on jittable CartPole (the
``train_inline`` recipe: lr 3e-4, entropy 1e-3, reward_scale 0.1) must
lift the completed-episode mean return well above the random-policy
baseline (~22) within a small update budget. This exercises the whole
fused path end to end: act -> on-device env step -> window assembly ->
train_step under one jit, auto-reset, carry zeroing, on-device episode
stats.

Usage:
    JAX_PLATFORMS=cpu PYTHONPATH=. python examples/colocated_smoke.py
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RETURN_THRESHOLD = 60.0  # best-window mean; random ~22, seed-0 run peaks >130


def check_learning(updates: int, threshold: float, failures: list[str]) -> None:
    from tpu_rl.config import Config
    from tpu_rl.runtime.colocated import ColocatedLoop

    cfg = Config(
        env="CartPole-v1", env_mode="colocated", algo="PPO",
        batch_size=32, buffer_size=32, seq_len=5,
        lr=3e-4, entropy_coef=0.001, reward_scale=0.1,
        time_horizon=500, loss_log_interval=200,
    )
    t0 = time.time()
    loop = ColocatedLoop(cfg, seed=0, max_updates=updates)
    out = loop.run(log=False)
    print(
        f"[colocated-smoke] learning: {out['updates']} updates, "
        f"{out['episodes']} episodes, best-window mean return "
        f"{out['mean_return_best_window']:.1f} "
        f"(threshold {threshold}), {time.time() - t0:.1f}s",
        flush=True,
    )
    if out["mean_return_best_window"] < threshold:
        failures.append(
            f"no learning: best-window mean return "
            f"{out['mean_return_best_window']:.1f} < {threshold}"
        )
    if out["episodes"] < 100:
        failures.append(f"too few episodes completed: {out['episodes']}")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--updates", type=int, default=1800,
                   help="learning-check update budget (default 1800)")
    p.add_argument("--threshold", type=float, default=RETURN_THRESHOLD,
                   help="best-window mean-return bar (default 60)")
    args = p.parse_args()

    failures: list[str] = []
    check_learning(args.updates, args.threshold, failures)

    if failures:
        for f in failures:
            print(f"[colocated-smoke] FAIL: {f}", flush=True)
        return 1
    print("[colocated-smoke] OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
