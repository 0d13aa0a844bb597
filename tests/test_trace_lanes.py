"""The chip owner's host lanes (ISSUE 23): one span primitive on the
profiler's clock, a learner main lane that is covered exhaustively, a
profiler window that opens once, and the gap attribution that reads it all
(``benchmarks/hostplane.py``)."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from benchmarks import hostplane, trace
from benchmarks.hostplane import Host, Span
from benchmarks.trace import DeviceTrace, Event
from tests.conftest import small_config
from tpu_rl.obs.perf import ProfilerCapture
from tpu_rl.obs.trace import TraceRecorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------- (a) capture round trip
@pytest.mark.timeout(120)
def test_spans_of_two_threads_round_trip_through_a_capture(tmp_path):
    """Every span is in the capture under its lane, nested and ordered as it
    ran, with its arguments — and the ring holds the same instants: placed
    by its ``capture`` entry it agrees with the capture to microseconds."""
    rec = TraceRecorder(capacity=256, pid=1, role="learner", annotate=True)
    prof = ProfilerCapture(str(tmp_path / "prof"), tracer=rec)

    def feeder():
        for _ in range(3):
            with rec.span("assemble", tid="feeder"):
                time.sleep(0.002)
                with rec.span("h2d-put", tid="feeder"):
                    time.sleep(0.001)

    try:
        assert prof.start() is not None
        th = threading.Thread(target=feeder)
        th.start()
        for i in range(4):
            with rec.span("feed-wait"):
                time.sleep(0.001)
            with rec.span("dispatch", args={"update": i}):
                time.sleep(0.001)
        th.join()
        path = prof.stop()
    finally:
        prof.close()
    host = hostplane.load(path)
    assert set(host.lanes) == {"main", "feeder"}
    main = host.lane("main")
    assert [s.name for s in main] == ["feed-wait", "dispatch"] * 4
    assert [s.args.get("update") for s in main if s.name == "dispatch"] == [0, 1, 2, 3]
    assert all(a.end <= b.start for a, b in zip(main, main[1:]))
    fed = host.lane("feeder")
    assert [s.name for s in fed] == ["assemble", "h2d-put"] * 3
    for outer, inner in zip(fed[::2], fed[1::2]):
        assert outer.start <= inner.start and inner.end <= outer.end  # nested
    assert [s.name for s in hostplane._top_level(fed)] == ["assemble"] * 3

    ring = hostplane.from_ring(rec.to_chrome())
    assert set(ring.lanes) == {"main", "feeder"}
    for lane in ("main", "feeder"):
        a, b = host.lane(lane), ring.lane(lane)
        assert [s.name for s in a] == [s.name for s in b]
        for x, y in zip(a, b):
            # the ring stamps inside the annotation, a few microseconds apart
            assert abs(x.start - y.start) < 50e3 and abs(x.end - y.end) < 50e3
    assert [s.args for s in ring.lane("main")] == [s.args for s in main]


def test_a_ring_without_a_capture_or_past_it_reads_as_nothing():
    rec = TraceRecorder(capacity=4, pid=1, annotate=True)
    with rec.span("dispatch"):
        pass
    assert hostplane.from_ring(rec.to_chrome()) is None  # no capture entry
    rec.add("capture", rec.now() - 1.0, 0.5, tid="profiler")
    for _ in range(4):  # the ring lets go of the capture and all it covered
        with rec.span("dispatch"):
            pass
    assert hostplane.from_ring(rec.to_chrome()) is None
    assert hostplane.parse(b"") is None


# --------------------------------------------- the primitive's bookkeeping
def test_one_span_call_feeds_ring_timer_and_ledger():
    from tpu_rl.obs.goodput import COMPUTE, IDLE, QUEUE_WAIT, GoodputLedger
    from tpu_rl.utils.timer import ExecutionTimer

    rec = TraceRecorder(capacity=8)
    rec.timer, rec.ledger = ExecutionTimer(), GoodputLedger("learner")
    with rec.span("dispatch", timer="learner-step-time", bucket=COMPUTE) as sp:
        time.sleep(0.002)
    assert sp.secs >= 0.002
    assert rec.timer.mean_elapsed("learner-step-time") == sp.secs
    assert rec.ledger.snapshot()["buckets"]["compute"] == sp.secs
    # what the block learns decides the accounting, the site is found again
    # by (lane, name), and a span that is not kept is no ring entry
    with rec.span("feed-wait", timer="learner-queue-wait-time", bucket=QUEUE_WAIT) as sp:
        sp.timed, sp.bucket, sp.keep = False, IDLE, False
    assert rec.timer.mean_elapsed("learner-queue-wait-time") is None
    buckets = rec.ledger.snapshot()["buckets"]
    assert buckets["queue-wait"] == 0.0 and buckets["idle"] == sp.secs
    with rec.span("feed-wait"):
        pass
    assert rec.timer.mean_elapsed("learner-queue-wait-time") is not None
    assert [e["name"] for e in rec.to_chrome()["traceEvents"] if e["ph"] == "X"] == [
        "dispatch", "feed-wait",
    ]
    # without a ring a span still times and accounts
    bare = TraceRecorder(capacity=0)
    with bare.span("fetch", tid="feeder") as sp:
        pass
    assert len(bare) == 0 and sp.secs >= 0.0


def test_export_thread_keeps_the_file_current_without_the_main_lane(tmp_path):
    rec = TraceRecorder(capacity=16, pid=9, role="learner")
    path = tmp_path / "trace.json"
    rec.start_export(str(path), period_s=0.02)
    try:
        for i in range(40):  # more than the ring holds
            with rec.span(f"s{i % 3}", tid="main" if i % 2 else "feeder"):
                pass
        deadline = time.monotonic() + 10
        while not path.exists() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert json.loads(path.read_text())["meta"]["role"] == "learner"
        with rec.span("last"):  # the pass's own span goes out with the next
            pass
    finally:
        rec.close_export()
    doc = json.loads(path.read_text())
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    lanes = {
        e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert len(spans) <= 16 and set(lanes.values()) == {"main", "feeder", "exporter"}
    # the file ends where the ring does (but for the passes' own spans: the
    # last pass wrote the file before its span closed)
    assert any(e["name"] == "trace-export" for e in spans)
    tail = lambda evs: [  # noqa: E731
        (e["name"], e["ts"]) for e in evs if e["ph"] == "X" and e["name"] != "trace-export"
    ][-4:]
    assert tail(spans) == tail(rec.to_chrome()["traceEvents"])
    assert doc["meta"]["wall_anchor_ns"] == rec.wall_anchor_ns


# ------------------------------- (e) roles without a chip stay off jax
def test_the_recorder_of_a_role_without_a_chip_never_imports_jax():
    """Worker, manager and storage build ``TraceRecorder()`` and call
    ``add`` / ``span`` / ``dump``: that path must work where jax cannot even
    be imported."""
    code = (
        "import importlib.util, sys\n"
        "sys.modules['jax'] = None  # any 'import jax' now raises\n"
        f"spec = importlib.util.spec_from_file_location('t', {ROOT + '/tpu_rl/obs/trace.py'!r})\n"
        "t = importlib.util.module_from_spec(spec); spec.loader.exec_module(t)\n"
        "r = t.TraceRecorder(capacity=4, role='worker')\n"
        "r.add('worker-tick', r.now(), 0.001)\n"
        "with r.span('storage-ingest', tid='main'): pass\n"
        "assert len(r.to_chrome()['traceEvents']) == 4\n"
        "try:\n"
        "    t.TraceRecorder(annotate=True)\n"
        "except ImportError:\n"
        "    print('ok')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=60
    )
    assert out.stdout.strip() == "ok", out.stderr


# ----------------------------------- (d) attribution on a hand-built trace
def _hand_trace():
    """Three updates of 100 us; ops leave three gaps in the window [100, 300]:
    [140, 200] under log-sync, [240, 270] under a feed wait while the feeder
    assembles, [280, 300] under nothing."""
    step = lambda t: Event("jit_step", t, 40)  # noqa: E731
    dev = DeviceTrace(
        "/device:TPU:0",
        modules=[step(0), step(100), step(200), step(300)],
        ops=[Event("fusion.1", 100, 40), Event("fusion.2", 200, 40),
             Event("fusion.3", 270, 10)],
    )
    us = lambda name, a, b, **kw: Span(name, a, b - a, kw)  # noqa: E731
    host = Host({
        "main": [
            us("dispatch", -10, -5, update=1), us("dispatch", 90, 95, update=2),
            us("log-sync", 96, 195), us("dispatch", 196, 198, update=3),
            us("feed-wait", 199, 268), us("dispatch", 268, 270, update=4),
        ],
        "feeder": [us("store-empty", 190, 245), us("assemble", 245, 266),
                   us("h2d-put", 255, 266), us("queue-put", 266, 267)],
    })
    return trace.Trace([dev]), host


def test_gaps_take_the_name_of_the_span_that_covers_them():
    tr, host = _hand_trace()
    assert tr.devices[0].window == (100, 300)
    assert host.clock_ok(tr)
    assert host.idle_gaps(tr) == [
        ["log-sync", 60e-9], ["feed-wait>assemble", 30e-9], ["unattributed", 20e-9],
    ]
    # the bench's own gaps, in the same order
    assert [s for _, s in host.idle_gaps(tr)] == [s for _, s in tr.breakdown()["idle_gaps"]]
    # all of [140, 200] but the 2 ns between spans, [240, 270], none of [280, 300]
    assert host.attributed_share(tr) == pytest.approx((58 + 30) / 110)
    # per update (2 in the window): main outside waits and syncs, and waits
    assert host.per_update_ms(tr, "main", but=hostplane.FEED_WAITS + hostplane.DEVICE_SYNCS) \
        == pytest.approx((2 + 2) / 2 / 1e6)
    assert host.lane_ns(tr, "main", names=hostplane.FEED_WAITS) == 69
    assert host.per_update_ms(tr, "feeder", names=("assemble",)) == pytest.approx(21 / 2 / 1e6)


@pytest.mark.parametrize("skew_ns", [+120, -60])
def test_a_skewed_clock_fails_the_check_and_names_nothing(skew_ns):
    """Host spans 120 ns late: a dispatch begins after its execution started.
    60 ns early: a log-sync ends before the execution it waited for."""
    tr, host = _hand_trace()
    for spans in host.lanes.values():
        for s in spans:
            s.start += skew_ns
    assert not host.clock_ok(tr)
    assert {name for name, _ in host.idle_gaps(tr)} == {"unattributed"}
    assert host.attributed_share(tr) == 0.0


# --------------------------------- (b) (c) the learner's main lane, on CPU
class _NullPub:
    def send(self, _proto, _payload) -> None:
        pass


def _run_learner(tmp_path, port, n_updates, *, produce=None, prepare=None, **kw):
    """One real ``LearnerService`` over the shm store, fed the same seeded
    window for ever. ``produce(store, window, stop)`` replaces the producer
    thread's body; ``prepare(svc)`` sees the service before it runs."""
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.data.shm_ring import OnPolicyStore, alloc_handles
    from tpu_rl.runtime.learner_service import LearnerService
    from tpu_rl.types import BATCH_FIELDS

    B = 16
    cfg = small_config(**{
        **dict(
            env="CartPole-v1", algo="PPO", batch_size=B, seq_len=16, hidden_size=64,
            learner_device="cpu", result_dir=str(tmp_path / "run"),
            model_dir=str(tmp_path / "models"), model_save_interval=8,
            loss_log_interval=4, telemetry_interval_s=0.05,
        ),
        **kw,
    })
    layout = BatchLayout.from_config(cfg)
    handles = alloc_handles(layout, capacity=B)
    store = OnPolicyStore(handles, layout)
    rng = np.random.default_rng(7)
    window = {}
    for f in BATCH_FIELDS:
        shape = (layout.seq_len, layout.width(f))
        if f == "act":
            window[f] = rng.integers(0, 2, size=shape).astype(np.float32)
        elif f == "is_fir":
            window[f] = np.zeros(shape, np.float32)
            window[f][0] = 1.0
        elif f == "log_prob":
            window[f] = np.full(shape, -0.7, np.float32)
        else:
            window[f] = rng.standard_normal(shape).astype(np.float32) * 0.1
    stop = threading.Event()

    def feed():
        if produce is not None:
            return produce(store, window, stop)
        while not stop.is_set():
            if not store.put(window):
                time.sleep(0.0005)

    th = threading.Thread(target=feed, daemon=True)
    th.start()
    svc = LearnerService(
        cfg, handles, model_port=port, stop_event=stop, max_updates=n_updates,
        seed=0, stat_port=port + 1,
    )
    if prepare is not None:
        prepare(svc)
    try:
        svc.run()
    finally:
        stop.set()
        th.join(timeout=30)
    return svc, cfg


def _lanes_of(run_dir) -> dict:
    """``trace.json``'s spans by lane name."""
    doc = json.loads((run_dir / "trace.json").read_text())
    lanes = {
        e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    by_lane: dict = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            by_lane.setdefault(lanes[e["tid"]], []).append(e)
    return by_lane


def _learn_lines(run_dir) -> list:
    """``learn.jsonl``'s records, in the order they were appended."""
    try:
        with open(run_dir / "learn.jsonl") as f:
            return [json.loads(line) for line in f]
    except FileNotFoundError:
        return []


def _counters(svc) -> dict:
    """The learner's counters as its next telemetry snapshot would hold them."""
    from tpu_rl.obs import MetricsRegistry

    reg = MetricsRegistry(role="learner")
    svc._emit_telemetry(reg, _NullPub(), svc.timer, 0)
    return {name: value for name, _labels, value in reg.snapshot()["counters"]}


class _Feed:
    """The learner's feed, with a word to say where the loop asks it how
    many batches are ready (under ``log-sync``: a crossing's question).
    ``patient``: a ``get`` never comes back empty, so what a crossing set
    aside is closed behind the next dispatch and nowhere else."""

    def __init__(self, inner, svc, at_crossing, patient):
        self._inner, self._svc, self._at_crossing = inner, svc, at_crossing
        self._patient = patient
        self.crossings = 0
        self.hold = False  # the next get() finds nothing

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def qsize(self):
        if self._svc._tracer.open_span("main")[0] != "log-sync":
            return self._inner.qsize()  # the queue-depth gauge's reading
        self.crossings += 1
        return self._at_crossing(self, self._inner.qsize())

    def get(self, timeout):
        if self.hold:
            self.hold = False
            return None
        item = self._inner.get(timeout)
        while item is None and self._patient and not self._svc._stopped():
            item = self._inner.get(timeout)
        return item


def _with_feed(at_crossing=lambda _feed, _n: 1, patient=False):
    """A ``prepare`` for :func:`_run_learner` that wraps the service's feed
    in a :class:`_Feed`; by default every crossing hears "one batch ready"."""

    def prepare(svc):
        make = svc._make_feed
        svc._make_feed = lambda *a: _Feed(make(*a), svc, at_crossing, patient)

    return prepare


@pytest.mark.timeout(300)
def test_learner_main_lane_is_exhaustive_and_the_ledger_still_sums(tmp_path):
    svc, cfg = _run_learner(tmp_path, 29731, n_updates=40)
    by_lane = _lanes_of(tmp_path / "run")
    assert {"main", "feeder", "publisher", "ckpt-writer", "exporter"} <= set(by_lane)
    assert {e["name"] for e in by_lane["publisher"]} == {"publish-d2h", "publish-send"}
    assert {e["name"] for e in by_lane["ckpt-writer"]} == {"ckpt-d2h", "ckpt-write"}
    assert {"fetch", "assemble", "h2d-put", "queue-put"} <= {e["name"] for e in by_lane["feeder"]}
    main = sorted(by_lane["main"], key=lambda e: e["ts"])
    names = {e["name"] for e in main}
    assert {
        "feed-wait", "rng-split", "program-record", "dispatch", "diag-fold",
        "account", "publish", "telemetry-emit", "log-sync", "log-write",
        "diag-drain", "ckpt-save", "heartbeat",
    } <= names
    dispatches = [e for e in main if e["name"] == "dispatch"]
    assert [e["args"]["update"] for e in dispatches] == list(range(1, 41))
    # none nests in another, and from the first dispatch to the last they
    # cover the wall time (the first dispatches compile: left out)
    lo, hi = dispatches[5]["ts"], dispatches[-1]["ts"]
    inside = [e for e in main if lo <= e["ts"] < hi]
    assert all(a["ts"] + a["dur"] <= b["ts"] + 1e-3 for a, b in zip(inside, inside[1:]))
    covered = sum(e["dur"] for e in inside)
    assert covered / (hi - lo) >= 0.98, covered / (hi - lo)
    # the same spans fed the timers the benchmark reads, and the ledger
    assert svc.timer.mean_elapsed("learner-step-time") > 0
    assert svc.timer.mean_elapsed("learner-queue-wait-time") >= 0
    assert svc.timer.mean_elapsed("learner-batching-time") > 0
    snap = svc.ledger.snapshot()
    assert snap["overcommit_ratio"] <= 0.01
    assert sum(snap["buckets"].values()) == pytest.approx(snap["elapsed_s"], rel=0.01)
    assert snap["buckets"]["compute"] > 0 and snap["buckets"]["wire"] > 0
    assert snap["buckets"]["ckpt"] > 0
    # the learner's registry says how the broadcast's latest-wins slot was
    # used: one snapshot per update and the first broadcast, sent or superseded
    counters = _counters(svc)
    assert counters["learner-publish-snapshots"] == 41
    assert 1 <= counters["learner-publish-sent"] <= 41
    # every send's span says what went to the socket (the Model layout: a head
    # and a part a leaf, never compressed), and the counter is their sum
    sends = [e["args"] for e in by_lane["publisher"] if e["name"] == "publish-send"]
    assert len(sends) == counters["learner-publish-sent"]
    assert {a["codec"] for a in sends} == {"PARTS"} and len({a["parts"] for a in sends}) == 1
    assert sends[0]["parts"] > 3 and sends[0]["bytes"] > 0
    assert counters["learner-publish-bytes"] == sum(a["bytes"] for a in sends)
    # ten logged updates, each with its books closed once: behind the next
    # dispatch, or in line at the crossing (five saves due, the last a stop too)
    assert counters["learner-log-behind-dispatch"] == svc.n_log_behind_dispatch
    assert counters["learner-log-inline"] == sum(svc.n_log_inline.values()) >= 5
    assert counters["learner-log-behind-dispatch"] + counters["learner-log-inline"] == 10


BEFORE_A_DISPATCH = {"feed-wait", "rng-split", "program-record"}


@pytest.mark.timeout(300)
def test_after_a_log_sync_only_the_next_dispatch_stands_before_the_chip(tmp_path):
    """ISSUE 44: a log-sync empties the pipeline, so between its end and the
    next dispatch the main lane does what that dispatch needs and nothing
    else; the logged update's books (``log-write``, ``diag-drain``) are
    closed once the chip has its next update — unless a save is due, the
    loop is stopping or no batch is ready, and then in today's order."""
    svc, cfg = _run_learner(
        tmp_path, 29771, n_updates=26, loss_log_interval=2, model_save_interval=8
    )
    main = sorted(_lanes_of(tmp_path / "run")["main"], key=lambda e: e["ts"])
    dispatches = [e for e in main if e["name"] == "dispatch"]
    assert [e["args"]["update"] for e in dispatches] == list(range(1, 27))
    assert all(a["ts"] + a["dur"] <= b["ts"] + 1e-3 for a, b in zip(main, main[1:]))
    syncs = [e for e in main if e["name"] == "log-sync"]
    assert [e["args"]["update"] for e in syncs] == list(range(2, 27, 2))
    behind = 0
    for sync in syncs:
        i = sync["args"]["update"]
        after = [e for e in main if e["ts"] >= sync["ts"] + sync["dur"] - 1e-3 and e is not sync]
        nxt = next((e for e in after if e["name"] == "dispatch"), None)
        between = [e["name"] for e in after if nxt is None or e["ts"] < nxt["ts"]]
        write = next(e for e in after if e["name"] == "log-write")
        drain = next(e for e in after if e["name"] == "diag-drain")
        if i % 8 == 0 or i == 26:  # a save due, the budget spent: today's order
            assert between[:2] == ["log-write", "diag-drain"], (i, between)
        elif between[0] == "log-write":  # the feed had no batch ready
            assert between[1] == "diag-drain", (i, between)
        else:
            behind += 1
            assert set(between) <= BEFORE_A_DISPATCH, (i, between)
            # the last launch before the sync was its own update's, the next
            # is the one the books hide behind
            assert nxt["args"]["update"] == i + 1
            assert nxt["ts"] <= write["ts"] <= drain["ts"], (i, between)
            later = next(
                (e for e in dispatches if e["args"]["update"] == i + 2), None
            )
            assert later is None or drain["ts"] + drain["dur"] <= later["ts"] + 1e-3
    assert behind == svc.n_log_behind_dispatch >= 1
    assert svc.n_log_inline["save"] == 3 and svc.n_log_inline["stop"] == 1
    assert behind + sum(svc.n_log_inline.values()) == len(syncs)
    # a line with index i follows update i's read-back, in order, each once
    lines = _learn_lines(tmp_path / "run")
    assert [r["idx"] for r in lines] == list(range(2, 27, 2))
    assert all(r["n_updates"] == 2.0 for r in lines)


@pytest.mark.timeout(300)
@pytest.mark.parametrize("chain", [1, 2])
def test_the_feeder_places_into_a_slot_it_holds_and_releases_after_the_put(
    tmp_path, capsys, monkeypatch, chain
):
    """ISSUE 45: the feeder lane's order is ``queue-slot`` (only while the
    queue is full), ``fetch``, ``assemble`` with ``h2d-put`` inside it,
    ``queue-put`` — so placed and not yet taken never exceeds
    ``learner_prefetch``, the batch in placement included. Unchained and
    on-policy the store's hand-over is a lease, released under ``queue-put``
    and nowhere else while the feeder runs; chained it is a copy. The two
    counters and ``learner.log``'s closing line say which."""
    from tpu_rl.data.shm_ring import OnPolicyStore

    depth, n_updates = 2, 24
    seen = {"released_under": [], "most": 0, "feed": None}

    def produce(store, window, stop):
        while not stop.is_set():
            if store.put_many([window] * 4) < 4:
                time.sleep(0.0005)

    def prepare(svc):
        release = OnPolicyStore.release

        def release_seen(store):
            if store._lease is not None:
                under = svc._tracer.open_span("feeder")
                seen["released_under"].append(under and under[0])
            release(store)

        monkeypatch.setattr(OnPolicyStore, "release", release_seen)
        make, place = svc._make_feed, svc._assemble_device

        def placing(raws):
            # the queue holds the placed and untaken; this one is on its way
            # (the feeder starts with the pipeline: its first may come first)
            queued = 0 if seen["feed"] is None else seen["feed"].qsize()
            seen["most"] = max(seen["most"], queued + 1)
            return place(raws)

        def feed_seen(*a):
            svc._assemble_device = placing
            seen["feed"] = make(*a)
            return seen["feed"]

        svc._make_feed = feed_seen

    svc, cfg = _run_learner(
        tmp_path, 29811 + 4 * chain, n_updates=n_updates, produce=produce,
        prepare=prepare, learner_chain=chain, learner_prefetch=depth,
    )
    # the first dispatch compiles: the feed gets ahead and has to wait
    assert seen["most"] == depth
    feeder = sorted(_lanes_of(tmp_path / "run")["feeder"], key=lambda e: e["ts"])
    order = [e["name"] for e in feeder if e["name"] not in ("store-empty", "h2d-put")]
    assert "queue-slot" in order
    cycle, i = ["fetch"] * chain + ["assemble", "queue-put"], 0
    while i < len(order):
        i += order[i] == "queue-slot"  # at most one, in front of the fetch
        if len(order) - i < len(cycle):
            break  # the dispatch the stop cut short
        assert order[i : i + len(cycle)] == cycle, (i, order[i : i + 6])
        i += len(cycle)
    for put in (e for e in feeder if e["name"] == "h2d-put"):
        assert any(
            a["name"] == "assemble" and a["ts"] <= put["ts"]
            and put["ts"] + put["dur"] <= a["ts"] + a["dur"] + 1e-3
            for a in feeder
        )
    counters = _counters(svc)
    raws = svc.n_feed["leased" if chain == 1 else "copied"]
    assert raws >= n_updates
    if chain == 1:
        assert counters["learner-feed-leased"] == raws and counters["learner-feed-copied"] == 0
        # every lease but the one the feeder held on its way out
        assert seen["released_under"].count("queue-put") >= raws - 1
        assert set(seen["released_under"]) <= {"queue-put", None}
    else:
        assert counters["learner-feed-copied"] == raws and counters["learner-feed-leased"] == 0
        # consume() is lease, copy, release: all of it inside the fetch
        assert set(seen["released_under"]) == {"fetch"}
    said = f"{raws} batches leased, 0 copied" if chain == 1 else f"0 batches leased, {raws} copied"
    closing = [ln for ln in capsys.readouterr().out.splitlines() if "logged updates" in ln]
    assert len(closing) == 1 and closing[0].endswith(f"the feed took {said}")


@pytest.mark.timeout(300)
@pytest.mark.parametrize("run_ahead", [1, 3])
def test_the_loop_asks_for_a_batch_only_with_room_on_the_chip(
    tmp_path, monkeypatch, run_ahead
):
    """ISSUE 45: the loop goes ``RUN_AHEAD`` updates ahead of the chip and
    no further — with as many in flight it waits for the oldest (``chip-wait``,
    a span of its own in front of ``feed-wait``) before it takes the batch
    that update would hold placed. Never between a log-sync and the next
    dispatch: a sync leaves nothing in flight."""
    from tpu_rl.runtime import learner_service

    monkeypatch.setattr(learner_service, "RUN_AHEAD", run_ahead)
    svc, cfg = _run_learner(
        tmp_path, 29831 + 4 * run_ahead, n_updates=24, loss_log_interval=6,
        model_save_interval=100,
    )
    main = sorted(_lanes_of(tmp_path / "run")["main"], key=lambda e: e["ts"])
    assert all(a["ts"] + a["dur"] <= b["ts"] + 1e-3 for a, b in zip(main, main[1:]))
    names = [e["name"] for e in main]
    assert names.count("dispatch") == 24
    waits = [i for i, n in enumerate(names) if n == "chip-wait"]
    if run_ahead == 1:
        assert waits  # a step of this size outlasts the loop's own statements
    for i in waits:
        assert names[i + 1] == "feed-wait", names[i : i + 3]
        # what stands in front of it is an update in flight, not a sync
        before = [n for n in names[:i] if n in ("dispatch", "log-sync")]
        assert before[-run_ahead:] == ["dispatch"] * run_ahead, (i, before[-4:])


@pytest.mark.timeout(300)
def test_the_profiler_window_opens_one_capture(tmp_path):
    svc, cfg = _run_learner(
        tmp_path, 29741, n_updates=3 + 4 + 10, loss_log_interval=1,
        profile_dir=str(tmp_path / "prof"), profile_start=3, profile_steps=4,
    )
    assert svc._prof_capture.n_captures == 1
    assert len(os.listdir(tmp_path / "prof")) == 1
    host = hostplane.load(str(tmp_path / "prof"))
    dispatched = [s.args["update"] for s in host.lane("main") if s.name == "dispatch"]
    assert dispatched == [4, 5, 6, 7]  # opened after update 3, closed after 7
    ring = hostplane.from_ring(svc._tracer.to_chrome())
    assert [s.args["update"] for s in ring.lane("main") if s.name == "dispatch"] == dispatched
    window = [s for s in ring.lane("main") if s.name == "profiler-window"]
    assert len(window) == 2  # the start's span and the stop's, and no third
    # ``hostplane.clock_ok`` on the ring, against a device as the syncs saw
    # it (every update logs: an execution begins when it was launched and the
    # chip was free, and ends when the log-sync that waited for it returned).
    # Each log-sync waits for the newest launched update, so the check holds
    # as it is written; had a sync waited for update i with i + 1 already
    # launched (a lagged read-back), it would have ended before i + 1 did.
    assert ring.clock_ok(trace.Trace([_device_as_the_syncs_saw_it(ring, lag=0)]))
    assert not ring.clock_ok(trace.Trace([_device_as_the_syncs_saw_it(ring, lag=1)]))


def _device_as_the_syncs_saw_it(host, lag):
    """One execution a ``dispatch`` of the lane: from its launch (or the end
    of the execution before it) to the end of the ``log-sync`` of update
    ``its own + lag``; where the lane holds no such sync, a microsecond."""
    syncs = {s.args["update"]: s for s in host.lane("main") if s.name == "log-sync"}
    runs, edge = [], 0.0
    for d in (s for s in host.lane("main") if s.name == "dispatch"):
        start = max(d.end, edge)
        sync = syncs.get(d.args["update"] + lag)
        edge = max(sync.end, start) if sync is not None else start + 1e3
        runs.append(Event("jit_step", start, edge - start))
    assert len(syncs) >= 3 and len(runs) >= 4
    return DeviceTrace("/device:TPU:0", modules=runs)
