"""Telemetry plane over the real runtime: the Model-version echo through a
live worker -> storage hop, and the cluster e2e acceptance test — scrape
/metrics mid-run and find Prometheus-parseable samples from every role,
including a nonzero policy-staleness observation.

Port range: this module owns 289xx (test_runtime owns 29xxx,
test_inference_service 30xxx).
"""

import json
import os
import re
import threading
import time
import urllib.error
import urllib.request

import pytest

from tests.conftest import small_config
from tests.test_runtime import _cluster_cfg, _machines
from tpu_rl.obs import STALENESS_HIST, TelemetryAggregator
from tpu_rl.runtime.protocol import Protocol


# ----------------------------------------------------- worker -> storage echo
@pytest.mark.timeout(240)
def test_model_version_echo_worker_to_storage():
    """Tag a live Model broadcast with ver=7; a real Worker must echo it into
    every subsequent RolloutBatch, and feeding those frames through the real
    storage ingest must land a policy-staleness observation."""
    import jax

    from tpu_rl.data.assembler import RolloutAssembler
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.models.families import build_family
    from tpu_rl.runtime.storage import LearnerStorage
    from tpu_rl.runtime.transport import MODEL_HWM, Pub, Sub
    from tpu_rl.runtime.worker import Worker

    base = 28900
    cfg = small_config(
        env="CartPole-v1", algo="PPO", worker_num_envs=2,
        worker_step_sleep=0.0, time_horizon=8,
        # enables the worker's registry/emitter (no sockets open worker-side)
        telemetry_port=18126, telemetry_interval_s=0.2,
    )
    relay_sub = Sub("127.0.0.1", base, bind=True)  # plays the manager
    model_pub = Pub("127.0.0.1", base + 1, bind=True, hwm=MODEL_HWM)
    stop = threading.Event()
    w = Worker(
        cfg, worker_id=0, manager_ip="127.0.0.1", manager_port=base,
        learner_ip="127.0.0.1", model_port=base + 1, stop_event=stop,
    )
    wt = threading.Thread(target=w.run, daemon=True)
    wt.start()

    family = build_family(cfg)
    host_actor = jax.device_get(
        family.init_params(jax.random.key(0), seq_len=cfg.seq_len)["actor"]
    )
    pub_stop = threading.Event()

    t_tx = 1_700_000_000_123_456_789  # a learner's send stamp, recognisable

    def keep_publishing():  # re-send: ZMQ slow-joiner drops early frames
        while not pub_stop.is_set():
            model_pub.send(
                Protocol.Model,
                {"actor": host_actor, "ver": 7, "epoch": 0, "t_tx": t_tx},
            )
            time.sleep(0.05)

    pt = threading.Thread(target=keep_publishing, daemon=True)
    pt.start()

    echoed, telemetry = [], []
    try:
        deadline = time.time() + 180
        while time.time() < deadline and not (
            len(echoed) >= 5 and any("t0" in t["clk"] for t in telemetry)
        ):
            got = relay_sub.recv(timeout_ms=500)
            if got is None:
                continue
            proto, payload = got
            if proto == Protocol.RolloutBatch and payload.get("ver") == 7:
                echoed.append(payload)
            elif proto == Protocol.Telemetry:
                telemetry.append(payload)
    finally:
        pub_stop.set()
        stop.set()
        pt.join(timeout=10)
        wt.join(timeout=30)
        relay_sub.close()
        model_pub.close()
    assert len(echoed) >= 5, "worker never echoed the broadcast version"
    assert all(p["wid"] == 0 for p in echoed)

    # Storage edge: the echoed frames must produce staleness observations.
    st = LearnerStorage(cfg, handles=None, learner_port=0)
    st.aggregator = TelemetryAggregator()  # plane on, no HTTP side effects
    assembler = RolloutAssembler(
        BatchLayout.from_config(cfg), lag_sec=cfg.rollout_lag_sec
    )
    for payload in echoed:
        st._ingest(Protocol.RolloutBatch, payload, assembler)
    agg = st.aggregator
    assert agg.max_version == 7  # echo alone ratchets the bound
    h = agg.registry.histogram(STALENESS_HIST, labels={"wid": "0"})
    assert h.count == len(echoed) and h.sum == 0.0  # acting at max version

    # Satellite: the worker's CLOCK-driven snapshots rode the same channel.
    assert telemetry, "worker emitted no Telemetry frames"
    assert telemetry[0]["role"] == "worker" and telemetry[0]["wid"] == 0
    # the clock-sync echo is still paired: the broadcast's own t_tx (it rides
    # the Model frame's description) with the worker's receive and send stamps
    clk = next(t["clk"] for t in telemetry if "t0" in t["clk"])
    assert clk["t0"] == t_tx and 0 < clk["t1"] <= clk["t2"]
    st._ingest(Protocol.Telemetry, telemetry[0], assembler)
    assert any(s.get("role") == "worker" for s, _ in agg.all_snapshots())


# ------------------------------------------------------------- cluster e2e
def _scrape(url: str, timeout: float = 3.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except (urllib.error.URLError, ConnectionError, OSError):
        return None, ""


_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9].*$|^#.*$"
)


# slow: boots a full supervised cluster (~85s on this one-core box) and
# the same surface is gated in CI by `make obs-smoke`/`make trace-smoke`;
# the tier-1 budget goes to the unit-level obs tests.
@pytest.mark.slow
@pytest.mark.timeout(300)
def test_cluster_telemetry_scrape_end_to_end(tmp_path):
    """Acceptance: boot the full local cluster with the plane on, scrape
    /metrics mid-run, and find Prometheus-parseable samples from worker,
    manager, storage AND learner — including a nonzero
    policy-staleness-updates observation — then validate /healthz,
    result_dir/telemetry.json and the learner's Chrome trace. With
    trace_sample_n on (ISSUE 5 acceptance), the run must also leave a merged
    fleet_trace.json whose sampled rollout chains link worker, manager,
    storage and learner spans with Chrome flow events."""
    from tpu_rl.runtime.runner import local_cluster

    base, tport = 28920, 28960
    cfg = _cluster_cfg(
        tmp_path,
        telemetry_port=tport,
        telemetry_interval_s=0.5,
        telemetry_stale_s=120.0,  # slow CI must not flap /healthz
        result_dir=str(tmp_path / "run"),
        loss_log_interval=2,
        trace_sample_n=2,  # every 2nd worker tick carries a trace trailer
    )
    assert cfg.telemetry_enabled
    sup = local_cluster(cfg, _machines(base), max_updates=6)
    metrics_url = f"http://127.0.0.1:{tport}/metrics"
    staleness_count = re.compile(
        r"^policy_staleness_updates_count\{[^}]*\} (\d+)$", re.M
    )
    try:
        learner = next(c for c in sup.children if c.name == "learner")
        text, ok = "", False
        deadline = time.time() + 240
        while time.time() < deadline:
            _, text = _scrape(metrics_url)
            counts = [int(m) for m in staleness_count.findall(text)]
            if (
                all(f'role="{r}"' in text
                    for r in ("worker", "manager", "storage", "learner"))
                and any(c > 0 for c in counts)
            ):
                ok = True
                break
            time.sleep(0.5)
        assert ok, f"per-role samples never converged; last scrape:\n{text}"
        # every exposition line is Prometheus-parseable
        for line in text.splitlines():
            assert _SAMPLE_RE.match(line), f"unparseable line: {line!r}"

        status, body = _scrape(f"http://127.0.0.1:{tport}/healthz")
        assert status in (200, 503)
        doc = json.loads(body)
        assert {"worker", "manager", "storage", "learner"} <= set(doc["roles"])
        for role in doc["roles"].values():
            assert role["sources"] >= 1

        # /tracez: the storage edge's live span ring + clock estimates.
        status, body = _scrape(f"http://127.0.0.1:{tport}/tracez")
        assert status == 200
        tz = json.loads(body)
        assert tz["role"] == "storage" and tz["trace"] is not None

        while time.time() < deadline and learner.proc.is_alive():
            time.sleep(1.0)
        assert not learner.proc.is_alive() and learner.proc.exitcode == 0
    finally:
        sup.stop()

    # Post-run artifacts: the rolling JSON snapshot and the Chrome trace.
    tele = json.loads((tmp_path / "run" / "telemetry.json").read_text())
    roles = {src["role"] for src in tele["sources"]}
    assert {"worker", "storage", "learner"} <= roles
    trace = json.loads((tmp_path / "run" / "trace.json").read_text())
    names = {ev["name"] for ev in trace["traceEvents"] if ev["ph"] == "X"}
    assert {"feed-wait", "dispatch"} <= names
    assert os.path.getsize(tmp_path / "run" / "telemetry.json") > 0

    # ISSUE 5 acceptance: the storage edge auto-merged the fleet trace at
    # shutdown; re-merge now that EVERY role has joined (late final dumps)
    # and require at least one complete clock-corrected lineage chain.
    from tpu_rl.obs import merge_result_dir
    from tpu_rl.obs.merge import MERGED_NAME

    run = tmp_path / "run"
    assert (run / MERGED_NAME).exists(), "storage did not auto-merge"
    summary = merge_result_dir(str(run))
    assert {"worker", "manager", "storage", "learner"} <= set(summary["roles"])
    assert summary["flows"] >= 1
    fleet = json.loads((run / MERGED_NAME).read_text())  # valid JSON on disk
    chains: dict = {}
    for ev in fleet["traceEvents"]:
        if ev.get("cat") == "lineage":
            chains.setdefault(ev["id"], []).append(ev["args"]["hop"])
    assert any(
        {"worker-tick", "storage-ingest", "dispatch"} <= set(hops)
        and ("relay-in" in hops or "relay-out" in hops)
        for hops in chains.values()
    ), f"no fully-linked rollout chain: {chains}"
    # clock sync saw the worker (full NTP loop rides Model + Telemetry)
    assert any(k.startswith("worker") for k in fleet["meta"]["clock"])
