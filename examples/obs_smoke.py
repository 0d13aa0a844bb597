"""Observability smoke: boot the smallest real cluster with the telemetry
plane on, scrape ``/metrics`` and ``/healthz`` mid-run, and validate the
rolling ``telemetry.json`` + Chrome trace artifacts. Exits nonzero on any
failure — this is the ``make obs-smoke`` CI gate.

Run:
  JAX_PLATFORMS=cpu PYTHONPATH=/root/repo python examples/obs_smoke.py \
      [--updates 6] [--base-port 30400] [--telemetry-port 30460]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REQUIRED_ROLES = ("worker", "manager", "storage", "learner")
_STALENESS_COUNT = re.compile(
    r"^policy_staleness_updates_count\{[^}]*\} (\d+)$", re.M
)


def _get(url: str, timeout: float = 3.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except (urllib.error.URLError, ConnectionError, OSError):
        return None, ""


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--updates", type=int, default=6)
    p.add_argument("--base-port", type=int, default=30400)
    p.add_argument("--telemetry-port", type=int, default=30460)
    p.add_argument("--timeout", type=float, default=240.0)
    args = p.parse_args()

    from tpu_rl.config import MachinesConfig, WorkerMachine
    from tpu_rl.runtime.runner import local_cluster
    from tests.conftest import small_config  # the CI-sized Config recipe

    run_dir = tempfile.mkdtemp(prefix="obs_smoke_")
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
    )
    machines = MachinesConfig(
        learner_ip="127.0.0.1",
        learner_port=args.base_port,
        workers=[WorkerMachine(
            num_p=2, manager_ip="127.0.0.1", ip="127.0.0.1",
            port=args.base_port + 5,
        )],
    )
    print(f"[obs-smoke] cluster up; run_dir={run_dir}", flush=True)
    sup = local_cluster(cfg, machines, max_updates=args.updates)
    metrics_url = f"http://127.0.0.1:{args.telemetry_port}/metrics"
    failures: list[str] = []
    try:
        learner = next(c for c in sup.children if c.name == "learner")
        deadline = time.time() + args.timeout
        text = ""
        while time.time() < deadline:
            _, text = _get(metrics_url)
            counts = [int(m) for m in _STALENESS_COUNT.findall(text)]
            if (
                all(f'role="{r}"' in text for r in REQUIRED_ROLES)
                and any(c > 0 for c in counts)
            ):
                break
            time.sleep(0.5)
        else:
            failures.append(
                "per-role /metrics samples (incl. nonzero staleness) never "
                f"converged; last scrape was {len(text)} bytes"
            )
        missing = [r for r in REQUIRED_ROLES if f'role="{r}"' not in text]
        if missing:
            failures.append(f"/metrics missing roles: {missing}")
        else:
            print(
                f"[obs-smoke] /metrics: {len(text.splitlines())} lines, "
                f"all of {REQUIRED_ROLES} present", flush=True,
            )

        status, body = _get(f"http://127.0.0.1:{args.telemetry_port}/healthz")
        if status not in (200, 503):
            failures.append(f"/healthz unreachable (status={status})")
        else:
            doc = json.loads(body)
            print(
                f"[obs-smoke] /healthz {status}: "
                f"{sorted(doc['roles'])}", flush=True,
            )

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

    tele_path = os.path.join(run_dir, "telemetry.json")
    try:
        tele = json.loads(open(tele_path).read())
        roles = {s["role"] for s in tele["sources"]}
        print(f"[obs-smoke] telemetry.json roles: {sorted(roles)}", flush=True)
        if not {"worker", "storage", "learner"} <= roles:
            failures.append(f"telemetry.json missing roles: {roles}")
    except (OSError, ValueError, KeyError) as e:
        failures.append(f"telemetry.json invalid: {type(e).__name__}: {e}")
    trace_path = os.path.join(run_dir, "trace.json")
    try:
        trace = json.loads(open(trace_path).read())
        spans = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
        print(f"[obs-smoke] trace.json spans: {sorted(spans)}", flush=True)
        if "dispatch" not in spans:
            failures.append(f"trace.json has no dispatch span: {spans}")
    except (OSError, ValueError, KeyError) as e:
        failures.append(f"trace.json invalid: {type(e).__name__}: {e}")

    if failures:
        for f in failures:
            print(f"[obs-smoke] FAIL: {f}", file=sys.stderr, flush=True)
        return 1
    print("[obs-smoke] OK", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
