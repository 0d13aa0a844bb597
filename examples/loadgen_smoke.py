"""Load-plane smoke: a real two-replica inference fleet under a synthetic
10k-client open-loop sweep, with a SIGKILL of one replica mid-sweep — the
CPU-scale proof of ISSUE 12's acceptance bar:

- two ``replica_main`` processes (continuous batching, ver-keyed swaps fed
  by a live model PUB publishing rising versions) serve the checked
  ``inference_base_port`` range;
- ``run_loadgen`` sweeps three offered-load plateaus from 2 driver
  processes standing in for >= 10k synthetic clients, grading each stage
  through a fresh SLO engine and writing ``<result-dir>/loadgen.json``;
- one replica is SIGKILL'd mid-sweep: hedged retries absorb the loss,
  overall success must stay >= 99.9%, and the per-stage version floor must
  never decrease (the fleet's monotonic-weights guarantee under churn);
- the sub-saturation first stage must grade GREEN on
  ``p99:inference-rtt``;
- the replicas serve through a BUCKET LADDER (``inference_buckets=8``) with
  telemetry on, and every stage is graded against the replicas' live stat
  snapshots on ``counter:inference-xla-recompiles==0`` — the PR 11
  recompile ratchet as an SLO: all bucket programs compile before the
  socket binds, so a sweep across flush sizes must never hit XLA again.
  Each stage's verdict must be a hard GREEN (``ok is True``), never
  no-data.

Exits nonzero on any failure — this is the ``make loadgen-smoke`` CI gate.

Run:
  JAX_PLATFORMS=cpu PYTHONPATH=/root/repo python examples/loadgen_smoke.py \
      [--clients 12000] [--base-port 31400] [--kill-at 8]
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SLO_SPEC = (
    "p99:inference-rtt<250ms@window=60s,"
    "counter:inference-xla-recompiles==0"
)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--clients", type=int, default=12_000)
    p.add_argument("--base-port", type=int, default=31400)
    p.add_argument("--rates", default="100,250,600",
                   help="aggregate offered rps per stage")
    p.add_argument("--duration", type=float, default=6.0)
    p.add_argument("--kill-at", type=float, default=8.0,
                   help="seconds into the sweep the replica-1 SIGKILL fires")
    p.add_argument("--result-dir", default=None)
    args = p.parse_args()

    # This parent and its two replica processes all run jax, and one process
    # owns a chip: the smoke is a CPU one unless the caller says otherwise.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from tpu_rl.config import Config
    from tpu_rl.fleet import replica_main
    from tpu_rl.loadgen import probe_ready, run_loadgen
    from tpu_rl.models.families import build_family
    from tpu_rl.runtime.protocol import Protocol
    from tpu_rl.runtime.transport import MODEL_HWM, Pub, Sub

    model_port = args.base_port + 10
    stat_port = args.base_port + 11
    result_dir = args.result_dir or tempfile.mkdtemp(prefix="loadgen-smoke-")
    cfg = Config.from_dict(dict(
        algo="IMPALA", obs_shape=(4,), action_space=2, hidden_size=32,
        worker_num_envs=1, act_mode="remote",
        inference_replicas=2, inference_base_port=args.base_port,
        inference_batch=16, inference_flush_us=500,
        inference_timeout_ms=1500, inference_hedge_ms=150,
        inference_retries=1,
        # Bucket-ladder sweep (ladder [8, 16]) with telemetry on
        # (result_dir flips telemetry_enabled): the recompile-ratchet SLO
        # below grades the replicas' own counters live.
        inference_buckets=8, result_dir=result_dir,
        telemetry_interval_s=1.0,
    ))
    ports = [args.base_port, args.base_port + 1]
    endpoints = [("127.0.0.1", prt) for prt in ports]
    out_path = os.path.join(result_dir, "loadgen.json")
    rates = [float(r) for r in args.rates.split(",")]

    # The stand-in learner: a live model PUB bumping the policy version
    # every second, so the sweep exercises the replicas' ver-keyed swaps
    # and the drivers' floor ratchet with real rollout churn.
    family = build_family(cfg)
    params = family.init_params(jax.random.key(0), seq_len=cfg.seq_len)
    actor_host = jax.device_get(params["actor"])
    pub = Pub("*", model_port, bind=True, hwm=MODEL_HWM)
    stop_pub = threading.Event()

    def _publish() -> None:
        ver = 0
        while not stop_pub.is_set():
            ver += 1
            pub.send(Protocol.Model, {"actor": actor_host, "ver": ver})
            stop_pub.wait(2.0)

    ctx = mp.get_context("spawn")
    replicas = [
        ctx.Process(
            target=replica_main,
            args=(cfg, i, ports[i], "127.0.0.1", model_port,
                  stat_port, None, None),
            kwargs={"seed": 0},
            daemon=True,
        )
        for i in range(2)
    ]

    # Server-side telemetry tap: the replicas' stat PUBs connect out to
    # learner_ip:stat_port, so the smoke binds the SUB end and keeps each
    # replica's LATEST snapshot — the extra grading input for the
    # recompile-ratchet SLO (a killed replica's last snapshot keeps
    # counting: its pre-kill recompiles stay in the fleet sum).
    stat_sub = Sub("*", stat_port, bind=True)
    latest: dict[int, dict] = {}
    stop_stats = threading.Event()

    def _collect_stats() -> None:
        while not stop_stats.is_set():
            for proto, snap in stat_sub.drain(max_msgs=256):
                if proto == Protocol.Telemetry and isinstance(snap, dict):
                    latest[int(snap.get("rid", -1))] = snap
            stop_stats.wait(0.1)

    killer = None
    try:
        for proc in replicas:
            proc.start()
        print(f"[loadgen] fleet booting on {ports} ...", flush=True)
        if not probe_ready(endpoints, cfg, timeout_s=180.0):
            print("[loadgen] FAIL: fleet never became ready", flush=True)
            return 1
        threading.Thread(target=_publish, daemon=True).start()
        threading.Thread(target=_collect_stats, daemon=True).start()
        # First replica snapshots must land before grading starts, so the
        # recompile rule can never grade no-data on stage 0.
        t_wait = time.monotonic() + 30.0
        while len(latest) < 2 and time.monotonic() < t_wait:
            time.sleep(0.2)
        if len(latest) < 2:
            print("[loadgen] FAIL: replica telemetry never arrived",
                  flush=True)
            return 1

        # The chaos leg: replica 1 dies -9 mid-sweep (stage 2 at the
        # defaults). No respawn — the surviving replica must carry the
        # offered load through hedged failover.
        killer = threading.Timer(args.kill_at, replicas[1].kill)
        killer.daemon = True
        killer.start()

        print(
            f"[loadgen] sweep: {args.clients} clients, rates {rates} rps, "
            f"kill replica-1 at t+{args.kill_at}s", flush=True,
        )
        doc = run_loadgen(
            cfg, endpoints, n_clients=args.clients, rates=rates,
            duration_s=args.duration, out_path=out_path, n_procs=2,
            rows=1, slo_spec=SLO_SPEC,
            extra_snapshots=lambda: list(latest.values()),
        )
    finally:
        if killer is not None:
            killer.cancel()
        stop_pub.set()
        stop_stats.set()
        pub.close()
        stat_sub.close()
        for proc in replicas:
            if proc.is_alive():
                proc.kill()
            proc.join(timeout=10)

    for stage in doc["stages"]:
        print(json.dumps(stage), flush=True)

    failures = []
    if not os.path.exists(out_path):
        failures.append(f"{out_path} was never written")
    if len(doc["stages"]) != len(rates):
        failures.append(
            f"expected {len(rates)} stages, got {len(doc['stages'])}"
        )
    success = doc["overall"]["success_rate"]
    if success < 0.999:
        failures.append(
            f"overall success {success} < 0.999 — the kill was not absorbed"
        )
    floors = [s["version_floor"] for s in doc["stages"]]
    if any(b < a for a, b in zip(floors, floors[1:])):
        failures.append(f"version floor regressed across stages: {floors}")
    if floors and floors[-1] < 1:
        failures.append(
            f"floor never rose ({floors}) — the model broadcast never landed"
        )
    first_slo = doc["stages"][0].get("slo") if doc["stages"] else None
    if not (first_slo and first_slo["ok"]):
        failures.append(
            f"sub-saturation stage SLO not green: {first_slo}"
        )
    # Recompile ratchet across the bucket-ladder sweep: EVERY stage's
    # counter:inference-xla-recompiles==0 rule must grade a hard GREEN.
    # ok=None (no-data) is a failure too — it would mean the replicas'
    # snapshots never reached the grading set and the ratchet was not
    # actually checked.
    for i, stage in enumerate(doc["stages"]):
        rules = (stage.get("slo") or {}).get("rules", [])
        rule = next(
            (r for r in rules if r["metric"] == "inference-xla-recompiles"),
            None,
        )
        if rule is None or rule["ok"] is not True:
            failures.append(
                f"stage {i}: recompile ratchet not green: {rule}"
            )
    absorbed = sum(
        s["hedges"] + s["failovers"] for s in doc["stages"][1:]
    )
    if absorbed == 0:
        failures.append(
            "no hedges/failovers after the kill — the chaos leg never bit"
        )

    if failures:
        for f in failures:
            print(f"[loadgen] FAIL: {f}", flush=True)
        return 1
    print(
        f"[loadgen] OK: {doc['overall']['ok']}/{doc['overall']['sent']} "
        f"ok ({success:.4%}), floors {floors}, "
        f"{absorbed} hedged/failed-over after the kill, "
        f"curve at {out_path}", flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
