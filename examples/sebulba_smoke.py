"""Pod-scale colocated smoke — the `make ci` gate for ISSUE 18.

Three checks, all on the CPU backend with subprocess "virtual hosts"
(``XLA_FLAGS=--xla_force_host_platform_device_count`` per child, gloo
collectives via ``jax.distributed``):

1. POD LEARNING + DURABILITY: a 2-host pod-Anakin CartPole run (the
   ``colocated_smoke`` recipe sharded over the global data axis) must
   survive a SIGKILL of the non-chief host after the first committed
   checkpoint — the relaunched pod resumes from the newest committed
   index at a bumped run epoch — and still train to best-window mean
   return >= 60 within the update budget.
2. CHECKPOINT READABLE: after the run, the final committed checkpoint
   restores through the standard reader (``restore_actor_params``) and
   its marker records the bumped epoch.
3. SEBULBA SPLIT: the split actor/learner loop (2+2 devices, bounded
   queue) must complete with the overlap signature — compute attributed
   on BOTH lane ledgers in the same window, queue-wait > 0 somewhere,
   and the queue high-watermark bounded by the configured depth.

Usage:
    JAX_PLATFORMS=cpu PYTHONPATH=. python examples/sebulba_smoke.py
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RETURN_THRESHOLD = 60.0  # same bar as colocated_smoke (random policy ~22)
SAVE_INTERVAL = 100
PORT = 29980


# --------------------------------------------------------------- child bodies
def pod_child(pid: int, nprocs: int, workdir: str, updates: int) -> None:
    """One virtual pod host running the fused pod-Anakin loop."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax

    from tpu_rl.config import Config
    from tpu_rl.runtime.colocated import ColocatedLoop

    mh = {
        "coordinator": f"127.0.0.1:{PORT}",
        "num_processes": nprocs,
        "process_id": pid,
    }
    cfg = Config(
        env="CartPole-v1", env_mode="colocated", algo="PPO",
        batch_size=32, buffer_size=32, seq_len=5,
        lr=3e-4, entropy_coef=0.001, reward_scale=0.1,
        time_horizon=500, loss_log_interval=200,
        mesh_data=nprocs, multihost=mh,
        model_dir=os.path.join(workdir, "ckpt"),
        model_save_interval=SAVE_INTERVAL,
    )
    loop = ColocatedLoop(cfg, seed=0, max_updates=updates)
    out = loop.run()
    if jax.process_index() == 0:
        print("SMOKE_RESULT " + json.dumps({
            "updates": out["updates"],
            "episodes": out["episodes"],
            "best_window": out["mean_return_best_window"],
            "start_it": loop._start_it,
            "epoch": loop.run_epoch,
        }), flush=True)


def sebulba_child(workdir: str, updates: int) -> None:
    """Single-process sebulba split: 2 actor + 2 learner devices."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    from tpu_rl.config import Config
    from tpu_rl.runtime.sebulba import SebulbaLoop

    cfg = Config(
        env="CartPole-v1", env_mode="colocated", algo="PPO",
        batch_size=32, buffer_size=32, seq_len=5,
        lr=3e-4, entropy_coef=0.001, reward_scale=0.1,
        time_horizon=500, loss_log_interval=20,
        sebulba_split=2, sebulba_queue=2,
        result_dir=os.path.join(workdir, "sebulba"),
    )
    loop = SebulbaLoop(cfg, seed=0, max_updates=updates)
    out = loop.run(log=False)
    roles = {led.role: led.snapshot() for led in loop._ledgers()}
    print("SEBULBA_RESULT " + json.dumps({
        "updates": out["updates"],
        "episodes": out["episodes"],
        "queue_peak": out["queue_peak_depth"],
        "queue_depth": cfg.sebulba_queue,
        "actor_compute_s": roles["sebulba-actor"]["buckets"]["compute"],
        "learner_compute_s": roles["sebulba-learner"]["buckets"]["compute"],
        "actor_compute_ratio": roles["sebulba-actor"]["ratios"]["compute"],
        "learner_compute_ratio":
            roles["sebulba-learner"]["ratios"]["compute"],
        "queue_wait_s": (
            roles["sebulba-actor"]["buckets"]["queue-wait"]
            + roles["sebulba-learner"]["buckets"]["queue-wait"]
        ),
    }), flush=True)


# ------------------------------------------------------------- orchestration
def _spawn_pod(pid: int, nprocs: int, workdir: str, updates: int):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--pod-child", str(pid),
         "--nprocs", str(nprocs), "--workdir", workdir,
         "--updates", str(updates)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
    )


def _result_line(out: str, tag: str) -> dict:
    line = next(ln for ln in out.splitlines() if ln.startswith(tag))
    return json.loads(line[len(tag):])


def check_pod(updates: int, threshold: float, failures: list[str],
              workdir: str) -> None:
    ckpt_dir = os.path.join(workdir, "ckpt")
    t0 = time.time()

    # Phase A: launch the pod, then SIGKILL the non-chief host right after
    # the first two-phase commit lands.
    procs = [_spawn_pod(pid, 2, workdir, updates) for pid in range(2)]
    deadline = time.time() + 300
    while time.time() < deadline:
        if glob.glob(os.path.join(ckpt_dir, "*", "COMMITTED")):
            break
        if any(p.poll() is not None for p in procs):
            break
        time.sleep(0.25)
    if not glob.glob(os.path.join(ckpt_dir, "*", "COMMITTED")):
        for p in procs:
            p.kill()
        outs = [p.communicate()[0] for p in procs]
        failures.append(
            "no committed checkpoint before kill:\n"
            + "\n".join(o[-1500:] for o in outs)
        )
        return
    procs[1].send_signal(signal.SIGKILL)
    try:
        procs[0].wait(timeout=120)
    except subprocess.TimeoutExpired:
        procs[0].kill()
    for p in procs:
        p.communicate()
    print(
        f"[sebulba-smoke] pod host 1 SIGKILLed after first commit "
        f"({time.time() - t0:.1f}s); relaunching pod", flush=True,
    )

    # Phase B: the pod rejoins and finishes the budget.
    procs = [_spawn_pod(pid, 2, workdir, updates) for pid in range(2)]
    outs = []
    for pid, p in enumerate(procs):
        out, _ = p.communicate(timeout=900)
        outs.append(out)
        if p.returncode != 0:
            failures.append(f"rejoined host {pid} rc={p.returncode}\n"
                            f"{out[-1500:]}")
    if failures:
        return
    res = _result_line(outs[0], "SMOKE_RESULT ")
    print(
        f"[sebulba-smoke] pod: {res['updates']} updates, "
        f"{res['episodes']} episodes, best-window mean return "
        f"{res['best_window']:.1f} (threshold {threshold}), resumed from "
        f"idx {res['start_it']} at run epoch {res['epoch']}, "
        f"{time.time() - t0:.1f}s total", flush=True,
    )
    if res["best_window"] < threshold:
        failures.append(
            f"pod did not learn: best-window {res['best_window']:.1f} "
            f"< {threshold}"
        )
    if res["start_it"] < SAVE_INTERVAL:
        failures.append(f"rejoin did not resume: start_it={res['start_it']}")
    if res["epoch"] != 1:
        failures.append(f"run epoch not bumped on rejoin: {res['epoch']}")
    if res["updates"] != updates:
        failures.append(
            f"update index not monotonic to budget: {res['updates']}"
        )

    # Final committed checkpoint must be readable through the standard
    # reader, and its marker must carry the bumped epoch.
    from tpu_rl.checkpoint import (
        latest_committed,
        read_meta,
        restore_actor_params,
    )

    newest = latest_committed(ckpt_dir, "PPO")
    if newest is None or newest[0] != updates:
        failures.append(f"final commit missing or wrong idx: {newest}")
        return
    if read_meta(newest[1]).get("epoch") != 1:
        failures.append(f"final marker epoch: {read_meta(newest[1])}")
    params = restore_actor_params(ckpt_dir, "PPO")
    if params is None or "actor" not in params:
        failures.append("committed checkpoint unreadable via "
                        "restore_actor_params")


def check_sebulba(updates: int, failures: list[str], workdir: str) -> None:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    t0 = time.time()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--sebulba-child",
         "--workdir", workdir, "--updates", str(updates)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
    )
    out, _ = proc.communicate(timeout=600)
    if proc.returncode != 0:
        failures.append(f"sebulba child rc={proc.returncode}\n{out[-1500:]}")
        return
    res = _result_line(out, "SEBULBA_RESULT ")
    print(
        f"[sebulba-smoke] split: {res['updates']} updates, "
        f"{res['episodes']} episodes, queue peak "
        f"{res['queue_peak']}/{res['queue_depth']}, actor compute "
        f"{res['actor_compute_ratio']:.0%} / learner compute "
        f"{res['learner_compute_ratio']:.0%}, queue-wait "
        f"{res['queue_wait_s']:.2f}s, {time.time() - t0:.1f}s", flush=True,
    )
    if res["updates"] != updates:
        failures.append(f"sebulba stopped early: {res['updates']}")
    # The overlap acceptance signal: both lanes burned compute in the SAME
    # ledger window (one window spans the whole run here).
    if not (res["actor_compute_s"] > 0 and res["learner_compute_s"] > 0):
        failures.append(f"no actor/learner overlap: {res}")
    if res["queue_wait_s"] <= 0:
        failures.append("no backpressure attributed to queue-wait")
    if not 1 <= res["queue_peak"] <= res["queue_depth"]:
        failures.append(f"queue depth not bounded: {res}")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--pod-child", type=int, default=None, metavar="PID")
    p.add_argument("--sebulba-child", action="store_true")
    p.add_argument("--nprocs", type=int, default=2)
    p.add_argument("--workdir", default=None)
    p.add_argument("--updates", type=int, default=None)
    p.add_argument("--threshold", type=float, default=RETURN_THRESHOLD)
    args = p.parse_args()

    if args.pod_child is not None:
        pod_child(args.pod_child, args.nprocs, args.workdir,
                  args.updates or 1800)
        return 0
    if args.sebulba_child:
        sebulba_child(args.workdir, args.updates or 120)
        return 0

    failures: list[str] = []
    with tempfile.TemporaryDirectory(prefix="sebulba_smoke_") as workdir:
        check_pod(args.updates or 1800, args.threshold, failures, workdir)
        check_sebulba(120, failures, workdir)

    if failures:
        for f in failures:
            print(f"[sebulba-smoke] FAIL: {f}", flush=True)
        return 1
    print("[sebulba-smoke] OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
