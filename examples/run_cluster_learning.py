"""Demonstrate that the REAL distributed deployment learns — not just that it
completes updates.

Spawns the full local cluster (learner + storage + manager + vectorized
workers as separate processes over ZMQ + shm, the reference's
``main.py:301-414`` topology) on IMPALA/CartPole-v1 for a bounded number of
updates, then reads the learner's tensorboard event file and reports the
``50-game-mean-stat-of-epi-rew`` fleet-reward curve (the reference's own
env-performance scalar, ``agents/manager.py:62-79`` ->
``agents/learner.py:136-148``).

Run:
  JAX_PLATFORMS=cpu PYTHONPATH=/root/repo python examples/run_cluster_learning.py \
      [--updates 3000] [--out CLUSTER_LEARNING.md]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--updates", type=int, default=3000)
    p.add_argument("--algo", default="IMPALA")
    p.add_argument("--env", default="CartPole-v1")
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--num-envs", type=int, default=8)
    p.add_argument("--out", default=None, help="markdown run-record path")
    p.add_argument("--run-dir", default="runs/cluster_learning")
    p.add_argument("--base-port", type=int, default=30100)
    # Standard V-trace truncation is rho_bar=1 (no floor); the defaults keep
    # the reference's [0.1, 0.8] clip (compute_loss.py:29-43) for parity.
    p.add_argument("--rho-bar", type=float, default=0.8)
    p.add_argument("--rho-min", type=float, default=0.1)
    # Hyperparameters default to the inline-solved IMPALA recipe
    # (examples/run_baselines.py): hot exploration phase then a
    # near-deterministic tail. The round-3 run held entropy_coef=0.01
    # forever, which pins policy entropy ~0.58 — a CartPole policy that
    # flips actions ~28% of the time cannot balance 500 steps, so the fleet
    # mean was capped near 50 independent of any lag effect.
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--entropy-coef", type=float, default=1e-3)
    p.add_argument("--anneal-coef", type=float, default=5e-5)
    p.add_argument("--anneal-lr", type=float, default=1e-4)
    p.add_argument("--anneal-frac", type=float, default=0.4)
    p.add_argument(
        "--anneal-at", type=int, default=None,
        help="absolute switch update (overrides --anneal-frac); with "
        "--resume-from past this index the cold phase resumes immediately",
    )
    p.add_argument("--no-anneal", action="store_true")
    p.add_argument("--worker-step-sleep", type=float, default=0.02)
    p.add_argument(
        "--learner-chain", type=int, default=1,
        help="updates per dispatched learner program (Config.learner_chain); "
        "the learner accumulates K consumed batches per dispatch",
    )
    p.add_argument(
        "--k-epoch", type=int, default=1,
        help="optimizer epochs per batch (Config.K_epoch); V-MPO's inline "
        "recipe needs 4 — its KL Lagrange constraint is inactive at 1 "
        "(behavior == target at the only epoch, examples/run_baselines.py)",
    )
    p.add_argument(
        "--keep-window-carry", action="store_true",
        help="train from the actor-stored recurrent carries "
        "(Config.zero_window_carry=False, reference parity) instead of the "
        "R2D2-style zero-init that the IMPALA lag diagnosis made default "
        "here",
    )
    p.add_argument(
        "--value-clip", type=float, nargs=2, default=None,
        metavar=("LO", "HI"),
        help="bounded-return V-trace value clamp (Config.value_target_clip); "
        "CartPole at reward_scale 0.1 / gamma 0.99: 0 10",
    )
    p.add_argument("--target", type=float, default=475.0,
                   help="stop early when the fleet 50-game mean reaches this")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--max-hours", type=float, default=2.0,
        help="hard wallclock cap on the whole run",
    )
    p.add_argument(
        "--resume-from", default=None,
        help="models dir of a previous run: the learner restores the newest "
        "checkpoint (params + optimizer + update counter) and the workers "
        "warm-start from it — the SURVEY §5.4 resume path, exercised on the "
        "real topology. With an absolute anneal switch ('at') already "
        "passed, the resumed learner re-enters the cold phase immediately.",
    )
    args = p.parse_args()

    from tpu_rl.config import Config, MachinesConfig, WorkerMachine
    from tpu_rl.runtime.runner import local_cluster

    # Fresh timestamped subdir per invocation: stale event files from a
    # previous run would otherwise merge into the reward curve.
    run_dir = os.path.abspath(
        os.path.join(args.run_dir, time.strftime("%Y%m%d-%H%M%S"))
    )
    os.makedirs(run_dir, exist_ok=True)
    cfg = Config.from_dict(
        dict(
            env=args.env,
            algo=args.algo,
            batch_size=32,
            seq_len=5,
            hidden_size=64,
            lr=args.lr,
            entropy_coef=args.entropy_coef,
            entropy_anneal=(
                None
                if args.no_anneal
                else {
                    "coef": args.anneal_coef,
                    "lr": args.anneal_lr,
                    **(
                        {"at": args.anneal_at}
                        if args.anneal_at is not None
                        else {"frac": args.anneal_frac}
                    ),
                }
            ),
            stop_at_reward=args.target,
            value_target_clip=(
                tuple(args.value_clip) if args.value_clip else None
            ),
            # Decisive for async learning (measured): without zero-init the
            # stale actor-stored carries drive bootstrapped value
            # hallucination (mean V > discounted cap) -> persistent negative
            # advantages -> entropy ratchets to exactly 0 regardless of the
            # entropy bonus (collapse observed at coef 0.001, 0.01 AND 0.05).
            zero_window_carry=not args.keep_window_carry,
            rho_bar=args.rho_bar,
            rho_min=args.rho_min,
            # Throttle the fleet to just above the learner's consumption
            # rate (~500 transitions/s at 3 updates/s): on a single shared
            # core, unthrottled workers flood the relay queues and data ages
            # in flight — measured V-trace ratios fell to ~0.5 (heavy lag),
            # where the rho-clipped corrections are too weak to keep the
            # value function honest (mean V drifted past the discounted
            # cap). Near-empty queues keep the behavior policy fresh.
            worker_step_sleep=args.worker_step_sleep,
            worker_num_envs=args.num_envs,
            learner_chain=args.learner_chain,
            K_epoch=args.k_epoch,
            learner_device="cpu",  # deterministic on shared hosts; the
            # real-TPU topology is chip_smoke.py's to prove
            rollout_lag_sec=5.0,
            time_horizon=500,
            result_dir=run_dir,
            model_dir=(
                os.path.abspath(args.resume_from)
                if args.resume_from
                else os.path.join(run_dir, "models")
            ),
            model_save_interval=500,
            loss_log_interval=100,
        )
    )
    machines = MachinesConfig(
        learner_ip="127.0.0.1",
        learner_port=args.base_port,
        workers=[
            WorkerMachine(
                num_p=args.workers, manager_ip="127.0.0.1", ip="127.0.0.1",
                port=args.base_port + 2,
            )
        ],
    )
    t0 = time.time()
    deadline = t0 + args.max_hours * 3600.0  # hard cap: never spin forever
    sup = local_cluster(cfg, machines, max_updates=args.updates, seed=args.seed)
    try:
        learner = next(c for c in sup.children if c.name == "learner")
        while learner.proc.is_alive() and time.time() < deadline:
            sup.check()  # restart-on-silence supervision for the other roles
            time.sleep(2.0)
        rc = learner.proc.exitcode if not learner.proc.is_alive() else None
    finally:
        sup.stop()
    wallclock = time.time() - t0

    # ---- read the fleet-reward curve back from tensorboard events
    from tensorboard.backend.event_processing.event_accumulator import (
        EventAccumulator,
    )

    curve = []
    for ev_file in sorted(glob.glob(os.path.join(run_dir, "events.*"))):
        acc = EventAccumulator(ev_file)
        acc.Reload()
        if "50-game-mean-stat-of-epi-rew" in acc.Tags().get("scalars", []):
            curve += [
                (s.step, s.value)
                for s in acc.Scalars("50-game-mean-stat-of-epi-rew")
            ]
    curve.sort()
    fleet_max = max((v for _, v in curve), default=None)
    result = dict(
        algo=cfg.algo,
        env=cfg.env,
        updates=args.updates,
        learner_exit=rc,
        wallclock_s=round(wallclock, 1),
        workers=args.workers,
        num_envs_per_worker=args.num_envs,
        learner_chain=args.learner_chain,
        k_epoch=args.k_epoch,
        zero_window_carry=not args.keep_window_carry,
        seed=args.seed,
        target=args.target,
        solved=(fleet_max is not None and fleet_max >= args.target),
        fleet_reward_first=curve[0][1] if curve else None,
        fleet_reward_last=curve[-1][1] if curve else None,
        fleet_reward_max=fleet_max,
        n_stat_points=len(curve),
    )
    print(json.dumps(result), flush=True)
    if args.out:
        lines = [
            "# Cluster learning run record",
            "",
            "Full multi-process deployment (learner + storage + manager + "
            f"{args.workers} workers x {args.num_envs} envs over ZMQ + shm) — "
            "the reference `main.py:301-414` topology — learning "
            f"{cfg.env} with {cfg.algo}.",
            "",
            "```bash",
            "JAX_PLATFORMS=cpu PYTHONPATH=/root/repo python "
            f"examples/run_cluster_learning.py --updates {args.updates}",
            "```",
            "",
            f"- learner exit code: **{rc}** after {args.updates} updates "
            f"in {round(wallclock, 1)} s",
            "- fleet 50-game mean episode reward "
            "(`50-game-mean-stat-of-epi-rew`, worker -> manager window -> "
            "storage stat mailbox -> learner tensorboard):",
            "",
            "| game count | mean reward |",
            "|---|---|",
        ]
        step = max(1, len(curve) // 12)
        for s, v in curve[::step]:
            lines.append(f"| {s} | {v:.1f} |")
        if curve and curve[-1] not in curve[::step]:
            lines.append(f"| {curve[-1][0]} | {curve[-1][1]:.1f} |")
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")
        print(f"wrote {args.out}", flush=True)


if __name__ == "__main__":
    main()
