"""Distributed-tracing smoke: boot the smallest real cluster with rollout
lineage sampling on (``trace_sample_n``), let the storage edge auto-merge the
per-role trace dumps at shutdown, then re-merge and validate the fleet trace:
all four roles on one clock-corrected timeline, and at least one sampled
rollout chained worker -> manager -> storage -> learner by Chrome flow
events. Exits nonzero on any failure — this is the ``make trace-smoke`` CI
gate.

Run:
  JAX_PLATFORMS=cpu PYTHONPATH=/root/repo python examples/trace_smoke.py \
      [--updates 6] [--base-port 30500] [--telemetry-port 30560]

Open the resulting ``fleet_trace.json`` in https://ui.perfetto.dev to see the
lineage arrows.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REQUIRED_ROLES = {"worker", "manager", "storage", "learner"}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--updates", type=int, default=6)
    p.add_argument("--base-port", type=int, default=30500)
    p.add_argument("--telemetry-port", type=int, default=30560)
    p.add_argument("--timeout", type=float, default=240.0)
    p.add_argument("--sample-n", type=int, default=2)
    args = p.parse_args()

    from tpu_rl.config import MachinesConfig, WorkerMachine
    from tpu_rl.obs import merge_result_dir
    from tpu_rl.obs.merge import MERGED_NAME
    from tpu_rl.runtime.runner import local_cluster
    from tests.conftest import small_config  # the CI-sized Config recipe

    run_dir = tempfile.mkdtemp(prefix="trace_smoke_")
    cfg = small_config(
        env="CartPole-v1",
        algo="PPO",
        worker_step_sleep=0.0,
        learner_device="cpu",
        rollout_lag_sec=30.0,
        time_horizon=100,
        loss_log_interval=2,
        result_dir=run_dir,
        telemetry_port=args.telemetry_port,
        telemetry_interval_s=0.5,
        telemetry_stale_s=120.0,
        trace_sample_n=args.sample_n,
    )
    machines = MachinesConfig(
        learner_ip="127.0.0.1",
        learner_port=args.base_port,
        workers=[WorkerMachine(
            num_p=2, manager_ip="127.0.0.1", ip="127.0.0.1",
            port=args.base_port + 5,
        )],
    )
    print(f"[trace-smoke] cluster up; run_dir={run_dir}", flush=True)
    sup = local_cluster(cfg, machines, max_updates=args.updates)
    failures: list[str] = []
    try:
        learner = next(c for c in sup.children if c.name == "learner")
        deadline = time.time() + args.timeout
        while time.time() < deadline and learner.proc.is_alive():
            time.sleep(1.0)
        if learner.proc.is_alive() or learner.proc.exitcode != 0:
            failures.append(
                f"learner did not complete cleanly "
                f"(alive={learner.proc.is_alive()}, "
                f"exitcode={learner.proc.exitcode})"
            )
    finally:
        sup.stop()

    merged_path = os.path.join(run_dir, MERGED_NAME)
    if not os.path.exists(merged_path):
        failures.append("storage edge did not auto-merge fleet_trace.json")
    # Re-merge now that every role has joined and flushed its final dump —
    # the authoritative artifact the assertions below run against.
    summary = merge_result_dir(run_dir)
    print(
        f"[trace-smoke] merged {summary['n_files']} dump(s): "
        f"{summary['n_events']} events, {summary['flows']} flow(s), "
        f"roles={summary['roles']}", flush=True,
    )
    try:
        fleet = json.loads(open(merged_path).read())  # valid JSON on disk
    except (OSError, ValueError) as e:
        failures.append(f"fleet trace invalid: {type(e).__name__}: {e}")
        fleet = {"traceEvents": [], "meta": {"roles": [], "clock": {}}}

    missing = REQUIRED_ROLES - set(fleet["meta"]["roles"])
    if missing:
        failures.append(f"fleet trace missing roles: {sorted(missing)}")
    chains: dict[str, list[str]] = {}
    for ev in fleet["traceEvents"]:
        if ev.get("cat") == "lineage":
            chains.setdefault(ev["id"], []).append(ev["args"]["hop"])
    linked = [
        tid for tid, hops in chains.items()
        if {"worker-tick", "storage-ingest", "dispatch"} <= set(hops)
        and ("relay-in" in hops or "relay-out" in hops)
    ]
    print(
        f"[trace-smoke] {len(chains)} lineage chain(s), "
        f"{len(linked)} fully linked worker->manager->storage->learner",
        flush=True,
    )
    if not linked:
        failures.append(
            f"no fully-linked rollout chain; partial chains: "
            f"{dict(list(chains.items())[:5])}"
        )
    if not any(k.startswith("worker") for k in fleet["meta"]["clock"]):
        failures.append(
            f"clock sync never estimated a worker offset: "
            f"{fleet['meta']['clock']}"
        )

    if failures:
        for f in failures:
            print(f"[trace-smoke] FAIL: {f}", file=sys.stderr, flush=True)
        return 1
    print(f"[trace-smoke] OK — open {merged_path} in ui.perfetto.dev",
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
