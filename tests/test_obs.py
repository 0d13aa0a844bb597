"""Telemetry-plane unit tests (tpu_rl.obs): registry snapshot/merge/diff
round-trips, Prometheus exposition golden output, aggregator staleness math,
Chrome trace-event schema, the HTTP exporter, and the zero-overhead guarantee
of the disabled path. The live worker->storage version echo and the cluster
/metrics scrape live in test_obs_runtime.py / test_runtime.py.
"""

import json
import tracemalloc
import urllib.request

import pytest

from tests.conftest import small_config
from tpu_rl.obs import (
    HIST_BUCKETS,
    JsonExporter,
    MetricsRegistry,
    PeriodicSnapshot,
    TelemetryAggregator,
    TelemetryHTTPServer,
    TraceRecorder,
    diff_snapshots,
    maybe_aggregator,
    merge_snapshots,
    render_healthz,
    render_prometheus,
)
from tpu_rl.runtime.protocol import Protocol, decode, encode


# ---------------------------------------------------------------- registry
def test_registry_snapshot_wire_round_trip():
    """A snapshot IS a Telemetry payload: it must survive the closed-schema
    wire codec bit-exactly (no adapter layer between registry and wire)."""
    reg = MetricsRegistry(role="worker", labels={"wid": "3"}, host="h", pid=42)
    reg.counter("worker-env-steps").inc(17)
    reg.gauge("worker-policy-version").set(5)
    reg.histogram("tick-time", labels={"phase": "act"}).observe(0.002)
    snap = reg.snapshot()
    proto, back = decode(encode(Protocol.Telemetry, snap))
    assert proto == Protocol.Telemetry
    assert back == snap
    # constant registry labels merged into each series
    assert back["counters"][0] == ["worker-env-steps", {"wid": "3"}, 17.0]
    assert back["hists"][0][1] == {"wid": "3", "phase": "act"}


def test_registry_merge_and_diff():
    a = MetricsRegistry(role="w", pid=1, host="h")
    b = MetricsRegistry(role="w", pid=1, host="h")
    for reg, k in ((a, 3), (b, 5)):
        reg.counter("c").inc(k)
        reg.histogram("h").observe(float(k))
        reg.gauge("g").set(float(k))
    sa, sb = a.snapshot(), b.snapshot()
    merged = merge_snapshots(sa, sb)
    assert dict((n, v) for n, _l, v in merged["counters"]) == {"c": 8.0}
    (_, _, counts, total, count) = merged["hists"][0]
    assert (total, count) == (8.0, 2)
    assert sum(counts) == 2
    # gauges: newest ts wins (sb snapshotted second)
    assert merged["gauges"][0][2] == 5.0
    # diff is the additive inverse over counters/hist slots
    d = diff_snapshots(merged, sa)
    assert d["counters"][0][2] == 5.0
    assert d["hists"][0][4] == 1
    # floored at zero: a restarted source never yields negative rates
    d2 = diff_snapshots(sa, merged)
    assert d2["counters"][0][2] == 0.0


def test_histogram_bucket_layout():
    reg = MetricsRegistry(role="r", pid=0, host="h")
    h = reg.histogram("lat")
    h.observe(2.0 ** -14)  # == first bound -> first slot (le is inclusive)
    h.observe(1e9)  # past the last bound -> overflow slot
    assert len(h.counts) == len(HIST_BUCKETS) + 1
    assert h.counts[0] == 1 and h.counts[-1] == 1


def test_periodic_snapshot_wall_clock_gating():
    """The emitter fires on the CLOCK, not on activity — the satellite that
    makes idle/stuck workers visible to /healthz."""
    sent = []
    t = [0.0]
    reg = MetricsRegistry(role="w", pid=0, host="h")
    em = PeriodicSnapshot(reg, sent.append, interval_s=5.0, clock=lambda: t[0])
    assert em.maybe_emit()  # first call ships immediately
    assert not em.maybe_emit()  # same instant: gated
    t[0] = 4.9
    assert not em.maybe_emit()
    t[0] = 5.0
    assert em.maybe_emit()
    assert len(sent) == 2 and sent[0]["role"] == "w"


# --------------------------------------------------------------- aggregator
def test_aggregator_staleness_math():
    t = [0.0]
    agg = TelemetryAggregator(
        registry=MetricsRegistry(role="storage", pid=0, host="h"),
        stale_after_s=10.0,
        clock=lambda: t[0],
    )
    # The learner's gauge is the authoritative max version.
    learner = MetricsRegistry(role="learner", pid=1, host="h")
    learner.gauge("learner-update-index").set(10)
    assert agg.ingest(learner.snapshot())
    assert agg.max_version == 10
    agg.observe_staleness(wid=0, version=7)  # 3 updates stale
    agg.observe_staleness(wid=0, version=10)  # fresh
    agg.observe_staleness(wid=1, version=12)  # echo ratchets the bound
    assert agg.max_version == 12
    agg.observe_staleness(wid=1, version=-1)  # unversioned: ignored
    h0 = agg.registry.histogram("policy-staleness-updates", labels={"wid": "0"})
    h1 = agg.registry.histogram("policy-staleness-updates", labels={"wid": "1"})
    assert h0.count == 2 and h0.sum == 3.0
    assert h1.count == 1 and h1.sum == 0.0


def test_aggregator_rejects_foreign_payloads():
    agg = TelemetryAggregator()
    assert not agg.ingest({"mean": 1.0})  # a Stat dict is not a snapshot
    assert not agg.ingest("junk")
    assert agg.n_rejected == 2 and not agg.sources


def test_aggregator_role_health_staleness():
    t = [0.0]
    agg = TelemetryAggregator(stale_after_s=10.0, clock=lambda: t[0])
    w = MetricsRegistry(role="worker", pid=7, host="h")
    agg.ingest(w.snapshot())
    assert agg.role_health()["worker"]["alive"]
    assert agg.healthy()
    t[0] = 11.0  # worker silent past the window
    health = agg.role_health()
    assert not health["worker"]["alive"]
    assert health["storage"]["alive"]  # own role: always answering
    assert not agg.healthy()
    status, body = render_healthz(agg)
    assert status == 503 and body["status"] == "stale"
    agg.ingest(w.snapshot())  # fresh frame revives the role
    assert render_healthz(agg)[0] == 200


# ------------------------------------------------------------- prometheus
def test_prometheus_exposition_golden():
    """Pin the exact exposition text (format 0.0.4) for a small fixed
    aggregator state — sorting, TYPE lines, label escaping, cumulative
    buckets, +Inf, _sum/_count."""
    agg = TelemetryAggregator(
        registry=MetricsRegistry(role="storage", pid=1, host="host0"),
        clock=lambda: 0.0,
    )
    reg = agg.registry
    reg.counter("storage-windows").inc(4)
    reg.gauge("storage-game-count").set(2)
    h = reg.histogram("policy-staleness-updates", labels={"wid": "0"})
    h.observe(0.0)  # first slot (bisect_left: 0.0 < 2^-14)
    h.observe(3.0)  # between 2^1 and 2^2
    text = render_prometheus(agg)
    lines = text.splitlines()
    assert lines[0] == "# TYPE storage_windows counter"
    assert lines[1] == (
        'storage_windows{host="host0",pid="1",role="storage"} 4'
    )
    assert lines[2] == "# TYPE storage_game_count gauge"
    assert lines[3] == (
        'storage_game_count{host="host0",pid="1",role="storage"} 2'
    )
    assert lines[4] == "# TYPE policy_staleness_updates histogram"
    # cumulative le buckets over the shared layout
    b = [ln for ln in lines if ln.startswith("policy_staleness_updates_bucket")]
    assert len(b) == len(HIST_BUCKETS) + 1  # bounds + +Inf
    assert b[0] == (
        'policy_staleness_updates_bucket{host="host0",le="6.103515625e-05",'
        'pid="1",role="storage",wid="0"} 1'
    )
    assert b[-1] == (
        'policy_staleness_updates_bucket{host="host0",le="+Inf",pid="1",'
        'role="storage",wid="0"} 2'
    )
    assert lines[-3] == (
        'policy_staleness_updates_sum{host="host0",pid="1",role="storage",'
        'wid="0"} 3'
    )
    assert lines[-2] == (
        'policy_staleness_updates_count{host="host0",pid="1",role="storage",'
        'wid="0"} 2'
    )
    # Pre-interpolated tail quantile: rank 1.98 of 2 falls in the (2, 4]
    # bucket at frac 0.98 -> 2 * 2**0.98 (geometric interpolation).
    assert lines[-1] == (
        'policy_staleness_updates_p99{host="host0",pid="1",role="storage",'
        'wid="0"} 3.944930817973437'
    )
    # every sample line parses as name{labels} value
    for ln in lines:
        if ln.startswith("#"):
            continue
        name_part, _, val = ln.rpartition(" ")
        float(val)
        assert name_part[0].isalpha()


def test_prometheus_cumulative_bucket_monotonicity():
    agg = TelemetryAggregator()
    h = agg.registry.histogram("x")
    for v in (0.001, 0.5, 2.0, 1e7):
        h.observe(v)
    text = render_prometheus(agg)
    counts = [
        int(ln.rpartition(" ")[2])
        for ln in text.splitlines()
        if ln.startswith("x_bucket")
    ]
    assert counts == sorted(counts) and counts[-1] == 4


# ------------------------------------------------------------- http server
@pytest.mark.timeout(30)
def test_http_exporter_metrics_and_healthz():
    agg = TelemetryAggregator()
    agg.registry.counter("storage-windows").inc(2)
    srv = TelemetryHTTPServer(agg, port=0)  # ephemeral port
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(f"{base}/metrics", timeout=5) as r:
            assert r.status == 200
            assert "0.0.4" in r.headers["Content-Type"]
            body = r.read().decode()
        assert "storage_windows" in body
        with urllib.request.urlopen(f"{base}/healthz", timeout=5) as r:
            assert r.status == 200
            doc = json.loads(r.read())
        assert doc["status"] == "ok"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/nope", timeout=5)
        assert ei.value.code == 404
    finally:
        srv.close()


# ------------------------------------------------------------------ trace
def test_trace_chrome_schema(tmp_path):
    tr = TraceRecorder(capacity=8, pid=123)
    with tr.span("assemble", tid="feeder"):
        pass
    with tr.span("train-step"):
        pass
    doc = tr.to_chrome()
    assert set(doc) == {"traceEvents", "displayTimeUnit", "meta"}
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert [e["name"] for e in events] == ["assemble", "train-step"]
    for e in events:
        assert e["pid"] == 123
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["dur"] >= 0.0
    # two lanes, named via thread_name metadata
    assert {m["args"]["name"] for m in metas} == {"feeder", "main"}
    assert len({e["tid"] for e in events}) == 2
    # ring: capacity bounds the buffer, recording never fails
    for i in range(50):
        tr.add(f"s{i}", 0.0, 0.001)
    assert len(tr) == 8 and tr.n_recorded == 52
    path = tmp_path / "trace.json"
    tr.dump(str(path))
    loaded = json.loads(path.read_text())  # valid JSON on disk
    assert loaded["displayTimeUnit"] == "ms"


def test_trace_meta_anchors_and_role_identity(tmp_path):
    """ISSUE 5 satellite: every dump carries the merge anchor (wall_anchor_ns
    paired with the perf_counter epoch) plus role/pid/host identity and a
    process_name metadata event — without these a ring can't be placed on
    the fleet timeline."""
    tr = TraceRecorder(capacity=8, pid=77, role="storage", host="box9")
    tr.add("storage-ingest", 0.0, 0.001, args={"trace_id": 5})
    doc = tr.to_chrome(extra_meta={"clock": {"worker/h/1": {"offset_ns": 3}}})
    meta = doc["meta"]
    assert meta["role"] == "storage" and meta["pid"] == 77
    assert meta["host"] == "box9"
    assert isinstance(meta["wall_anchor_ns"], int)
    assert meta["clock"] == {"worker/h/1": {"offset_ns": 3}}  # extra merged
    pnames = [
        e for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "process_name"
    ]
    assert [p["args"]["name"] for p in pnames] == ["storage box9/77"]
    # span args (the lineage tag) survive the export
    (ev,) = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert ev["args"] == {"trace_id": 5}
    path = tmp_path / "t.json"
    tr.dump(str(path), extra_meta={"clock": {}})
    assert json.loads(path.read_text())["meta"]["clock"] == {}


# ------------------------------------------------------------ json exporter
def test_json_exporter_rolling_snapshot(tmp_path):
    t = [0.0]
    agg = TelemetryAggregator(clock=lambda: t[0])
    agg.registry.counter("storage-windows").inc()
    path = tmp_path / "telemetry.json"
    exp = JsonExporter(agg, str(path), interval_s=2.0)
    assert exp.maybe_export(now=0.0)
    assert not exp.maybe_export(now=1.0)  # gated
    doc = json.loads(path.read_text())
    assert set(doc) == {"ts", "healthz", "sources"}
    assert doc["healthz"]["status"] == "ok"
    assert doc["sources"][0]["role"] == "storage"
    assert exp.maybe_export(now=2.5) and exp.n_written == 2


# ----------------------------------------------------- disabled = zero cost
def test_disabled_telemetry_allocates_nothing():
    """Acceptance pin: with telemetry_port=0 and result_dir=None the plane
    is never constructed — storage opens no server, and its per-frame tick
    path allocates nothing (the hot-loop guard is one `is None` check)."""
    from tpu_rl.runtime.storage import LearnerStorage

    cfg = small_config(telemetry_port=0, result_dir=None)
    assert not cfg.telemetry_enabled
    assert maybe_aggregator(cfg) is None
    st = LearnerStorage(cfg, handles=None, learner_port=0)
    st._setup_telemetry()
    assert st.aggregator is None and st._http is None
    assert st._json_exp is None and st._tb_exp is None

    # The disabled ingest path for a Telemetry frame and a versioned
    # RolloutBatch must be allocation-free (measured, not assumed).
    telemetry_payload = {"role": "worker", "pid": 1, "host": "h"}
    for _ in range(64):  # warm any lazy interpreter state
        st._ingest(Protocol.Telemetry, telemetry_payload, assembler=None)
    tracemalloc.start()
    snap1 = tracemalloc.take_snapshot()
    for _ in range(256):
        st._ingest(Protocol.Telemetry, telemetry_payload, assembler=None)
    snap2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    here = [
        s
        for s in snap2.compare_to(snap1, "lineno")
        if s.traceback[0].filename.endswith("storage.py") and s.size_diff > 0
    ]
    assert not here, [str(s) for s in here]


def test_enabled_telemetry_gate():
    assert small_config(telemetry_port=18123).telemetry_enabled
    assert small_config(result_dir="/tmp/x").telemetry_enabled
    agg = maybe_aggregator(small_config(telemetry_port=18123))
    assert isinstance(agg, TelemetryAggregator)


def test_sampling_off_trace_path_allocates_nothing():
    """ISSUE 5 acceptance pin: with trace_sample_n=0 the storage ingest path
    for UNSAMPLED RolloutBatch frames (trailer=None) allocates nothing in
    storage.py even when a tracer exists — the guard is one `is None` pair.
    The assembler's own data-plane writes are its job, not tracing cost."""
    import numpy as np

    from tpu_rl.data.assembler import RolloutAssembler
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.runtime.storage import LearnerStorage
    from tpu_rl.types import BATCH_FIELDS

    cfg = small_config(telemetry_port=0, result_dir=None, relay_mode="raw")
    st = LearnerStorage(cfg, handles=None, learner_port=0)
    st._tracer = TraceRecorder(capacity=64, pid=1, role="storage", host="h")
    layout = BatchLayout.from_config(cfg)
    asm = RolloutAssembler(layout, lag_sec=1e9)
    payload = {
        f: np.zeros((2, layout.width(f)), dtype=np.float32)
        for f in BATCH_FIELDS
    }
    payload["id"] = ["e0", "e1"]
    payload["done"] = np.zeros(2, dtype=np.uint8)
    for _ in range(64):
        st._ingest(Protocol.RolloutBatch, payload, asm)
    tracemalloc.start()
    snap1 = tracemalloc.take_snapshot()
    for _ in range(256):
        st._ingest(Protocol.RolloutBatch, payload, asm)
    snap2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    here = [
        s
        for s in snap2.compare_to(snap1, "lineno")
        if s.traceback[0].filename.endswith("storage.py") and s.size_diff > 0
    ]
    assert here == [], [str(s) for s in here]
    assert st._tracer.n_recorded == 0  # nothing sampled -> nothing recorded


def test_sampling_off_manager_relay_allocates_nothing():
    """Same pin at the relay: ingesting untraced (2-part) frames with a
    tracer present costs one length check, zero allocations in manager.py.
    The queue is prefilled past capacity so deque block growth and the
    beyond-small-int drop counter are steady-state before measuring."""
    from tpu_rl.runtime.manager import Manager

    cfg = small_config(relay_mode="raw")
    m = Manager(cfg, 0, "127.0.0.1", 0)
    m._tracer = TraceRecorder(capacity=64, pid=1, role="manager", host="h")

    class _NullPub:
        def send_raw(self, parts):
            pass

    pub = _NullPub()
    parts = encode(Protocol.RolloutBatch, {"x": 1})
    # Warm past the deque's maxlen AND past CPython's small-int cache (256)
    # so n_dropped's live int object is steady-state; the warm runs INSIDE
    # the tracing window so that int's allocation site is tracked in BOTH
    # snapshots (counter churn nets to zero, not to one untracked->tracked).
    tracemalloc.start()
    for _ in range(m.queue.maxlen + 300):
        m._ingest(Protocol.RolloutBatch, parts, pub)
    assert m.n_dropped > 256
    snap1 = tracemalloc.take_snapshot()
    for _ in range(256):
        m._ingest(Protocol.RolloutBatch, parts, pub)
    snap2 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    here = [
        s
        for s in snap2.compare_to(snap1, "lineno")
        if s.traceback[0].filename.endswith("manager.py") and s.size_diff > 0
    ]
    assert here == [], [str(s) for s in here]
    assert m._tracer.n_recorded == 0


# ------------------------------------------------------------ tracez server
@pytest.mark.timeout(30)
def test_http_exporter_tracez_endpoint():
    agg = TelemetryAggregator()
    tr = TraceRecorder(capacity=8, pid=9, role="storage")
    tr.add("storage-ingest", 0.0, 0.002, args={"trace_id": 11})
    srv = TelemetryHTTPServer(
        agg, port=0, tracez=lambda: {"role": "storage", "trace": tr.to_chrome()}
    )
    try:
        url = f"http://127.0.0.1:{srv.port}/tracez"
        with urllib.request.urlopen(url, timeout=5) as r:
            assert r.status == 200
            doc = json.loads(r.read())
        assert doc["role"] == "storage"
        names = [
            e["name"] for e in doc["trace"]["traceEvents"] if e["ph"] == "X"
        ]
        assert names == ["storage-ingest"]
    finally:
        srv.close()


@pytest.mark.timeout(30)
def test_http_exporter_close_releases_port_and_is_idempotent():
    """ISSUE 5 satellite (graceful shutdown regression): close() must join
    the serving thread and release the socket so the SAME port can be
    re-bound immediately — the restart-a-role-in-place case — and calling
    close() twice must be a no-op, not an error."""
    agg = TelemetryAggregator()
    srv1 = TelemetryHTTPServer(agg, port=0)
    port = srv1.port
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=5
    ) as r:
        assert r.status == 200
    srv1.close()
    srv1.close()  # idempotent
    srv2 = TelemetryHTTPServer(agg, port=port)  # same port, fresh server
    try:
        assert srv2.port == port
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5
        ) as r:
            assert r.status == 200
    finally:
        srv2.close()
        srv2.close()


# ---------------------------------------------------------- flight recorder
def test_flightrec_dump_content_and_fingerprint(tmp_path):
    from tpu_rl.obs import flightrec

    cfg = small_config()
    tr = TraceRecorder(capacity=8, pid=5, role="worker")
    tr.add("worker-tick", 0.0, 0.001, args={"trace_id": 3})
    fr = flightrec.FlightRecorder(
        "worker", str(tmp_path), tracer=tr, cfg=cfg,
        extra=lambda: {"queue_depth": 4},
    )
    path = fr.dump("unit-test")
    assert path is not None and path.endswith(
        f"flightrec-worker-{__import__('os').getpid()}.json"
    )
    doc = json.loads(open(path).read())
    assert doc["role"] == "worker" and doc["reason"] == "unit-test"
    assert doc["last_error"] is None
    assert doc["extra"] == {"queue_depth": 4}
    # fingerprint: stable per config, distinct across configs
    assert doc["config_fingerprint"] == flightrec.config_fingerprint(cfg)
    assert flightrec.config_fingerprint(
        small_config(batch_size=cfg.batch_size * 2)
    ) != doc["config_fingerprint"]
    names = [
        e["name"] for e in doc["trace"]["traceEvents"] if e["ph"] == "X"
    ]
    assert names == ["worker-tick"]
    # without a sink, dump is a clean no-op
    assert flightrec.FlightRecorder("w", None).dump() is None
    # extra() raising must not kill the dump
    boom = flightrec.FlightRecorder(
        "w", str(tmp_path), extra=lambda: 1 / 0
    )
    doc2 = boom.snapshot()
    assert "error" in doc2["extra"]


def test_flightrec_crash_hook_via_role_entry(tmp_path):
    """utils.errlog.role_entry: a role that installed a recorder and dies
    leaves flightrec-<role>-<pid>.json carrying the fatal traceback."""
    import os

    from tpu_rl.obs import flightrec
    from tpu_rl.utils.errlog import role_entry

    def target():
        flightrec.install("worker", str(tmp_path))
        raise RuntimeError("synthetic crash")

    with pytest.raises(RuntimeError, match="synthetic crash"):
        role_entry(target, "worker", str(tmp_path / "logs"))
    path = tmp_path / f"flightrec-worker-{os.getpid()}.json"
    doc = json.loads(path.read_text())
    assert doc["reason"] == "fatal-exception"
    assert "RuntimeError: synthetic crash" in doc["last_error"]
    assert "Traceback" in doc["last_error"]


def test_flightrec_sigusr1_dump(tmp_path):
    """kill -USR1 <pid> on a live process dumps without stopping it. The
    pytest process IS the main thread, so the real handler path runs; the
    previous handler is restored afterwards."""
    import os
    import signal
    import threading

    from tpu_rl.obs import flightrec

    if threading.current_thread() is not threading.main_thread():
        pytest.skip("signal install requires the main thread")
    prev = signal.getsignal(signal.SIGUSR1)
    try:
        fr = flightrec.install("storage", str(tmp_path))
        assert flightrec.current() is fr
        os.kill(os.getpid(), signal.SIGUSR1)
        path = tmp_path / f"flightrec-storage-{os.getpid()}.json"
        doc = json.loads(path.read_text())
        assert doc["reason"] == "SIGUSR1" and fr.n_dumps == 1
    finally:
        signal.signal(signal.SIGUSR1, prev)


# ------------------------------------------------------------------- merge
def _trace_doc(role, pid, anchor_ns, spans, clock=None, host="h"):
    """Hand-built TraceRecorder dump: spans = [(name, ts_us, dur_us, args)]."""
    meta = {"role": role, "pid": pid, "host": host, "wall_anchor_ns": anchor_ns}
    if clock is not None:
        meta["clock"] = clock
    return {
        "traceEvents": [
            {"name": n, "ph": "X", "ts": ts, "dur": dur, "pid": pid,
             "tid": 0, **({"args": args} if args else {})}
            for n, ts, dur, args in spans
        ],
        "displayTimeUnit": "ms",
        "meta": meta,
    }


def test_merge_clock_corrects_and_links_flows(tmp_path):
    """Two processes whose wall clocks disagree by 5 s, plus a learner: the
    merged timeline must place their spans in TRUE order (clock-corrected),
    chain the sampled rollout's hops with flow events, and close the chain
    onto the first dispatch after window-close, flagged synthesized."""
    from tpu_rl.obs.merge import merge_traces

    R = 1_000_000_000_000  # reference epoch, ns
    tid42 = 42
    worker = _trace_doc(
        "worker", 1, R + 5_000_000_000,  # local clock 5 s AHEAD of reference
        [("worker-tick", 0.0, 100.0, {"trace_id": tid42, "seq": 7})],
    )
    storage = _trace_doc(
        "storage", 2, R + 1_000_000,  # colocated with reference, 1 ms later
        [
            ("storage-ingest", 500.0, 20.0, {"trace_id": tid42}),
            ("window-close", 600.0, 1.0, {"trace_id": tid42}),
        ],
        clock={"worker/h/1": {
            "offset_ns": 5_000_000_000, "uncertainty_ns": 1000,
            "n_samples": 4, "kind": "rtt", "age_s": 0.0,
        }},
    )
    learner = _trace_doc(
        "learner", 3, R + 2_000_000,
        [("dispatch", 0.0, 50.0, None)],
    )
    merged = merge_traces([worker, storage, learner])
    assert merged["meta"]["roles"] == ["learner", "storage", "worker"]
    assert merged["meta"]["flows"] == 1
    xs = {e["name"]: e for e in merged["traceEvents"] if e["ph"] == "X"}
    # Uncorrected, the worker's tick would sit 5 s in the future; corrected,
    # it is the EARLIEST event (the normalized axis origin).
    assert xs["worker-tick"]["ts"] == pytest.approx(0.0)
    assert xs["storage-ingest"]["ts"] == pytest.approx(1500.0)
    assert xs["window-close"]["ts"] == pytest.approx(1600.0)
    assert xs["dispatch"]["ts"] == pytest.approx(2000.0)
    # docs get distinct pid lanes even if raw pids collided
    assert len({e["pid"] for e in merged["traceEvents"] if e["ph"] == "X"}) == 3
    flows = [e for e in merged["traceEvents"] if e.get("cat") == "lineage"]
    assert [f["ph"] for f in flows] == ["s", "t", "t", "f"]
    assert all(f["id"] == f"0x{tid42:x}" for f in flows)
    assert [f["args"]["hop"] for f in flows] == [
        "worker-tick", "storage-ingest", "window-close", "dispatch"
    ]
    # only the synthesized learner hop is flagged; the finish binds encl.
    assert [f["args"]["synthesized"] for f in flows] == [
        False, False, False, True
    ]
    assert flows[-1]["bp"] == "e"
    # the start anchors at its slice END (frame leaves the hop)
    assert flows[0]["ts"] == pytest.approx(100.0)
    json.dumps(merged)  # whole doc is valid trace-event JSON


def test_merge_skips_unanchored_and_single_hop_chains():
    from tpu_rl.obs.merge import merge_traces

    no_anchor = {
        "traceEvents": [{"name": "x", "ph": "X", "ts": 0, "dur": 1, "pid": 0,
                         "tid": 0}],
        "meta": {"role": "worker"},  # pre-anchor dump: nothing to place
    }
    lone = _trace_doc(
        "worker", 1, 10**12,
        [("worker-tick", 0.0, 1.0, {"trace_id": 9})],
    )
    merged = merge_traces([no_anchor, lone])
    assert merged["meta"]["roles"] == ["worker"]
    assert merged["meta"]["flows"] == 0  # one hop is not a chain
    assert not [e for e in merged["traceEvents"] if e.get("cat") == "lineage"]
    assert merge_traces([])["traceEvents"] == []


def test_merge_result_dir_and_cli(tmp_path):
    from tpu_rl.obs import merge_result_dir
    from tpu_rl.obs.merge import MERGED_NAME, main

    R = 10**12
    docs = {
        "trace-worker-1.json": _trace_doc(
            "worker", 1, R, [("worker-tick", 0.0, 5.0, {"trace_id": 1})]
        ),
        "trace-storage-2.json": _trace_doc(
            "storage", 2, R,
            [("storage-ingest", 50.0, 5.0, {"trace_id": 1})],
        ),
        "trace.json": _trace_doc(  # the learner's dump name
            "learner", 3, R, [("dispatch", 100.0, 5.0, None)]
        ),
    }
    for name, doc in docs.items():
        (tmp_path / name).write_text(json.dumps(doc))
    (tmp_path / "telemetry.json").write_text("{}")  # ignored: not a trace
    summary = merge_result_dir(str(tmp_path))
    assert summary["n_files"] == 3 and summary["flows"] == 1
    assert set(summary["roles"]) == {"worker", "storage", "learner"}
    out = json.loads((tmp_path / MERGED_NAME).read_text())
    assert out["meta"]["flows"] == 1
    # CLI: re-merge in place (the merged file is excluded from its own
    # inputs), usage errors exit 2, empty dirs exit 1
    assert main([str(tmp_path)]) == 0
    assert main([]) == 2
    assert main([str(tmp_path / "nope")]) == 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert main([str(empty)]) == 1
