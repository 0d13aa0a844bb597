"""Device memory gets owners (ISSUE 49): the learner's memory book
(``utils/platform.MemoryBook``) on scripted books and on real CPU runs of a
``LearnerService``, the recorder's counter sample, the one reader of
``device.memory_stats()``, and the record of it all in
``backend-learner.json`` under ``memory``."""

import json
import threading
from types import SimpleNamespace

import pytest

from benchmarks import harness
from tests.test_trace_lanes import _run_learner
from tpu_rl.obs import flightrec, merge_traces, perf
from tpu_rl.obs.trace import TraceRecorder
from tpu_rl.utils import platform

GB = 10**9


class Books:
    """A device whose ``memory_stats()`` answers a given sequence."""

    def __init__(self, rows, limit=16 * GB):
        self.rows = list(rows)
        self.limit = limit
        self.n = 0

    def memory_stats(self):
        in_use, peak, reserved = self.rows[min(self.n, len(self.rows) - 1)]
        self.n += 1
        return {
            "bytes_in_use": in_use, "peak_bytes_in_use": peak,
            "peak_bytes_reserved": reserved, "bytes_limit": self.limit,
        }


# site, lane, (bytes_in_use, peak_bytes_in_use, peak_bytes_reserved)
SCRIPT = [
    ("run", "startup", (1, 12, 3)),  # the set-up's peaks: 12 live, 3 reserved
    ("place", "startup", (6, 12, 3)),
    ("feed-start", "startup", (6, 12, 3)),
    ("publish", "main", (7, 12, 3)),  # before the first sync: no window yet
    ("log-sync", "main", (7, 12, 5)),  # the first update reserved 5
    ("publish", "main", (7, 12, 5)),  # nothing new: not kept
    ("ckpt-save", "main", (13, 13, 5)),  # a new maximum, and the peak rose
    ("ckpt-d2h", "ckpt-writer", (7, 13, 5)),
    ("log-sync", "main", (8, 13, 5)),  # not kept
    ("log-sync", "main", (8, 13, 5)),  # the last
]


def scripted(script=SCRIPT, tracer=None, **kw):
    dev = Books([tuple(v * GB for v in row) for _, _, row in script], **kw)
    book = platform.MemoryBook([dev], tracer)
    book.declare("train-state", SimpleNamespace(nbytes=6 * GB))
    book.declare("ckpt-snapshot", SimpleNamespace(nbytes=6 * GB))
    for i, (site, lane, _) in enumerate(script):
        if site == "place":
            book.hold("train-state")
        if site == "ckpt-save":
            book.hold("ckpt-snapshot")
        if site == "ckpt-d2h":
            book.drop("ckpt-snapshot")
        book.stamp(site, i if lane != "startup" else None, tid=lane)
    return book, book.record()


def test_kept_stamps_are_what_the_sequence_says():
    _book, rec = scripted()
    kept = [(s[0], s[2]) for s in rec["stamps"]]
    assert kept == [
        ("run", None), ("place", None), ("feed-start", None),  # every start-up stamp
        ("publish", 3),  # the loop's first, and a new maximum
        ("log-sync", 4),  # the first of its site; the reserved book rose
        ("ckpt-save", 6),  # a new maximum of bytes_in_use, a risen peak
        ("ckpt-d2h", 7),  # the first of its site
        ("log-sync", 9),  # the last
    ]
    assert rec["stamps_taken"] == len(SCRIPT) and rec["stamps_dropped"] == 0
    assert rec["stamps"][0][3:] == [1 * GB, 12 * GB, 3 * GB]
    assert rec["bytes_limit"] == 16 * GB and rec["devices"] == 1
    # the owners alive at every kept stamp
    assert rec["owners"]["train-state"]["alive"] == [0, 1, 1, 1, 1, 1, 1, 1]
    assert rec["owners"]["ckpt-snapshot"]["alive"] == [0, 0, 0, 0, 0, 1, 0, 0]
    assert rec["owners"]["ckpt-snapshot"]["alive_max"] == 1
    assert rec["owners"]["batch"] == {
        "bytes_each": None, "alive_max": 0, "bound": None, "alive": [0] * 8
    }


def test_window_is_the_fullest_stamp_since_the_first_sync():
    _book, rec = scripted()
    w = rec["window"]
    assert w["stamp"][:4] == ["ckpt-save", w["stamp"][1], 6, 13 * GB]
    assert w["alive"]["ckpt-snapshot"] == 1 and w["alive"]["train-state"] == 1
    assert w["first_sync_unix_s"] == rec["stamps"][4][1]
    # the live book's peak rose inside the window: the runtime's own is exact
    assert w["in_use_peak_rose"] is True and w["live_peak_bytes"] == 13 * GB
    assert w["reserved_peak_rose"] is False


def test_scratch_is_the_reserved_book_at_the_first_sync():
    _book, rec = scripted()
    assert rec["window"]["scratch_bytes"] == 5 * GB
    assert rec["window"]["raised_by_learner"] is True  # 3 at run, 5 after an update
    quiet = [(s, lane, (a, b, 3)) for s, lane, (a, b, _) in SCRIPT]
    _book, rec = scripted(quiet)
    assert rec["window"]["scratch_bytes"] == 3 * GB
    assert rec["window"]["raised_by_learner"] is False  # a set-up program's


def test_without_a_risen_peak_the_window_is_a_lower_bound():
    script = [(s, lane, (min(a, 11), 12, r)) for s, lane, (a, _, r) in SCRIPT]
    _book, rec = scripted(script)
    w = rec["window"]
    assert w["in_use_peak_rose"] is False
    assert w["stamp"][0] == "ckpt-save" and w["live_peak_bytes"] == 11 * GB  # not 12


def test_the_window_ends_at_close():
    """What the shutdown makes after the loop — a last save's snapshot — is
    stamped and kept, and is neither the window's top nor its peak."""
    script = SCRIPT[:6] + [
        ("close", "main", (7, 12, 5)),
        ("ckpt-d2h", "ckpt-writer", (14, 14, 6)),  # the shutdown's save
    ]
    _book, rec = scripted(script)
    assert [s[0] for s in rec["stamps"]][-2:] == ["close", "ckpt-d2h"]
    assert rec["stamps"][-1][3:] == [14 * GB, 14 * GB, 6 * GB]
    w = rec["window"]
    assert w["stamp"][0] == "log-sync" and w["stamp"][3] == 7 * GB
    assert w["in_use_peak_rose"] is False and w["reserved_peak_rose"] is False
    assert w["live_peak_bytes"] == 7 * GB and w["scratch_bytes"] == 5 * GB


def test_no_window_before_the_first_sync():
    book, rec = scripted(SCRIPT[:4])
    assert rec["window"] is None
    assert [s[0] for s in rec["stamps"]] == ["run", "place", "feed-start", "publish"]
    assert book.last_books == (7 * GB, 12 * GB, 3 * GB, 16 * GB)


def test_a_backend_without_books_stamps_null_and_still_counts():
    class Cpu:
        def memory_stats(self):
            return None

    rec_ring = TraceRecorder(capacity=64)
    book = platform.MemoryBook([Cpu()], rec_ring)
    book.declare("batch", SimpleNamespace(nbytes=100), bound=5)
    book.stamp("run", tid="startup")
    book.count("batch", 3)
    book.stamp("log-sync", 1)
    book.count("batch", 2)
    book.stamp("publish", 2)
    rec = book.record()
    assert [s[3:] for s in rec["stamps"]] == [[None, None, None]] * 3  # never RSS
    assert rec["bytes_limit"] is None and book.last_books is None
    assert rec["owners"]["batch"] == {
        "bytes_each": 100, "alive_max": 3, "bound": 5, "alive": [0, 3, 2]
    }
    w = rec["window"]
    assert w["stamp"][0] == "log-sync" and w["alive"]["batch"] == 3
    assert w["scratch_bytes"] is None and w["raised_by_learner"] is None
    assert len(rec_ring) == 0  # nothing to draw


def test_the_loops_kept_stamps_are_bounded(monkeypatch):
    monkeypatch.setattr(platform, "MAX_LOOP_STAMPS", 4)
    script = [("run", "startup", (1, 1, 1)), ("log-sync", "main", (2, 2, 1))]
    script += [("publish", "main", (3 + i, 3 + i, 1)) for i in range(10)]  # each a new maximum
    _book, rec = scripted(script)
    assert [s[0] for s in rec["stamps"]] == ["run", "log-sync"] + ["publish"] * 4
    assert rec["stamps"][-1][3] == 12 * GB  # the last is there all the same
    assert rec["stamps_dropped"] == 7
    assert rec["window"]["stamp"][3] == 12 * GB  # the window never loses its top


def test_the_stamp_is_the_fullest_devices():
    devs = [Books([(1, 2, 9)]), Books([(5, 6, 7)]), Books([(3, 8, 1)])]
    book = platform.MemoryBook(devs)
    book.stamp("run", tid="startup")
    assert book.record()["stamps"][0][3:] == [5, 6, 7]
    assert perf.device_memory_books(devs) == [
        (1, 2, 9, 16 * GB), (5, 6, 7, 16 * GB), (3, 8, 1, 16 * GB)
    ]


def test_one_reader_of_the_runtimes_books():
    import jax

    assert perf.device_memory_books(jax.devices()[:2]) == [None, None]  # the CPU keeps none

    class OneBook:
        def memory_stats(self):
            return {"bytes_in_use": 5}

    assert perf.device_memory_books([OneBook()]) == [(5, 5, 0, None)]
    # the gauges' pair from a row somebody has just read: no second read
    assert perf.device_memory_bytes(books=(3, 4, 5, 16)) == (3.0, 9.0)
    rss, peak = perf.device_memory_bytes(jax.devices()[0])  # the gauges' fall-back
    assert rss > 0 and peak >= rss


def test_stamps_from_several_threads_keep_one_book():
    dev = Books([(i, i, 1) for i in range(1, 4000)])
    book = platform.MemoryBook([dev])
    book.stamp("log-sync", 0)

    def lane(name):
        for i in range(300):
            book.hold("publish-snapshot")
            book.stamp(name, i, tid=name)
            book.drop("publish-snapshot")

    threads = [threading.Thread(target=lane, args=(n,)) for n in ("a", "b", "c")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rec = book.record()
    assert rec["stamps_taken"] == 901
    assert rec["owners"]["publish-snapshot"]["alive_max"] <= 3
    assert rec["owners"]["publish-snapshot"]["alive"][-1] <= 3
    assert len(rec["stamps"]) <= platform.MAX_LOOP_STAMPS + 1


# ------------------------------------------------------------ counter sample
def test_a_counter_sample_is_a_ring_entry_on_the_rings_clock(tmp_path):
    rec = TraceRecorder(capacity=16, pid=7, role="learner", annotate=True)
    with rec.span("dispatch", args={"update": 1}):
        pass
    at = rec.now()
    rec.sample("device-mem", 123, tid="publisher", at=at)
    rec.sample("device-mem", 456)
    doc = rec.to_chrome()
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert [e["args"] for e in counters] == [{"device-mem": 123}, {"device-mem": 456}]
    assert all(e["name"] == "device-mem" and e["pid"] == 7 and "dur" not in e for e in counters)
    # on the ring's clock: unix microseconds less the anchor's, like a span's
    assert counters[0]["ts"] == pytest.approx((rec.unix_s(at) - rec.wall_anchor_ns / 1e9) * 1e6)
    (span,) = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert span["ts"] <= counters[0]["ts"] <= counters[1]["ts"]
    lanes = {e["args"]["name"]: e["tid"] for e in doc["traceEvents"] if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert counters[0]["tid"] == lanes["publisher"] and counters[1]["tid"] == lanes["main"]
    # a span list is spans only; the count is every entry's
    entries, wrapped = rec.entries()
    assert [e[1] for e in entries] == ["dispatch"] and not wrapped and len(rec) == 3
    # the flight recorder's dump holds it
    fr = flightrec.FlightRecorder("learner", str(tmp_path), tracer=rec)
    with open(fr.dump("test")) as f:
        dumped = json.load(f)["trace"]["traceEvents"]
    assert [e["args"] for e in dumped if e["ph"] == "C"] == [{"device-mem": 123}, {"device-mem": 456}]


def test_the_exporter_and_the_merge_carry_counter_samples(tmp_path):
    rec = TraceRecorder(capacity=16, role="learner", annotate=True)
    path = str(tmp_path / "trace.json")
    rec.start_export(path, period_s=60.0)
    with rec.span("dispatch"):
        rec.sample("device-mem", 7)
    rec.close_export()
    with open(path) as f:
        doc = json.load(f)
    (c,) = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    (x,) = [e for e in doc["traceEvents"] if e["ph"] == "X" and e["name"] == "dispatch"]
    assert c["args"] == {"device-mem": 7} and x["ts"] <= c["ts"] <= x["ts"] + x["dur"]
    merged = merge_traces([doc])
    (mc,) = [e for e in merged["traceEvents"] if e["ph"] == "C"]
    (mx,) = [e for e in merged["traceEvents"] if e["ph"] == "X" and e["name"] == "dispatch"]
    assert mx["ts"] <= mc["ts"] <= mx["ts"] + mx["dur"]  # moved with the spans


# ------------------------------------------------------- a learner on the CPU
def _learner(tmp_path_factory, name, **kw):
    tmp = tmp_path_factory.mktemp(name)
    writes = []  # what the record held each time it was written
    with pytest.MonkeyPatch.context() as mp:
        write = platform.BackendRecord._write

        def noting(self):
            mem = self.info.get("memory")
            writes.append((sorted(self.info), None if mem is None else len(mem["stamps"])))
            write(self)

        mp.setattr(platform.BackendRecord, "_write", noting)
        for k, v in kw.pop("patch", {}).items():
            mp.setattr(perf, k, v)
        svc, cfg = _run_learner(
            tmp, harness.free_port_block(2), n_updates=20, loss_log_interval=4,
            ckpt_async=True, **kw,
        )
    with open(tmp / "run" / "backend-learner.json") as f:
        doc = json.load(f)
    with open(tmp / "run" / "trace.json") as f:
        trace = json.load(f)
    return svc, cfg, doc, trace, writes


@pytest.fixture(scope="module")
def cpu_run(tmp_path_factory):
    """Twenty updates of a small PPO learner on the CPU backend, which keeps
    no books: a sync every 4, an async save every 8."""
    return _learner(tmp_path_factory, "membook")


@pytest.fixture(scope="module")
def booked_run(tmp_path_factory):
    """The same with the runtime's books scripted: every read finds a KB
    more live than the last."""
    lock, n = threading.Lock(), [0]

    def books(devices):
        with lock:
            n[0] += 1
            return [(n[0] * 1000, n[0] * 1000 + 7, 4000, 10**9) for _ in devices]

    return _learner(tmp_path_factory, "membook-booked", patch={"device_memory_books": books})


@pytest.mark.timeout(300)
def test_the_learners_owners_are_sized_from_the_leaves(cpu_run):
    import jax

    from tpu_rl.algos.registry import get_algo
    from tpu_rl.runtime.learner_service import RUN_AHEAD

    _svc, cfg, doc, _trace, _writes = cpu_run
    owners = doc["memory"]["owners"]
    _family, state, _step = get_algo(cfg.algo).build(cfg, jax.random.key(0))
    assert owners["train-state"]["bytes_each"] == sum(
        x.nbytes for x in jax.tree.leaves(state)
    )
    assert owners["train-state"]["alive_max"] == 1
    batch = owners["batch"]
    assert batch["bound"] == cfg.learner_prefetch + RUN_AHEAD
    assert 1 <= batch["alive_max"] <= batch["bound"]
    # one placed batch: every field of the layout, float32, batch x seq rows
    from tpu_rl.data.layout import BatchLayout

    assert batch["bytes_each"] == BatchLayout.from_config(cfg).traj_floats * 4 * cfg.batch_size
    assert 1 <= owners["publish-snapshot"]["alive_max"] <= 2
    assert owners["publish-snapshot"]["bytes_each"] < owners["train-state"]["bytes_each"]
    assert owners["inference-params"] == {
        "bytes_each": None, "alive_max": 0, "bound": None,
        "alive": [0] * len(doc["memory"]["stamps"]),
    }
    assert owners["diag"]["bytes_each"] > 0 and owners["diag"]["alive_max"] <= 2


def test_a_saves_snapshot_lives_from_ckpt_save_to_the_writers_d2h(cpu_run):
    _svc, _cfg, doc, _trace, _writes = cpu_run
    mem = doc["memory"]
    snap = mem["owners"]["ckpt-snapshot"]
    assert snap["bytes_each"] == mem["owners"]["train-state"]["bytes_each"]
    at = {s[0]: i for i, s in reversed(list(enumerate(mem["stamps"])))}  # first of each site
    assert snap["alive"][at["ckpt-save"]] >= 1  # made under the span, stamped at its exit
    assert snap["alive"][at["ckpt-d2h"]] == snap["alive"][at["ckpt-save"]] - 1  # let go
    assert snap["alive"][-1] == 0 and snap["alive_max"] <= 2  # the shutdown's save too
    assert mem["stamps"][at["ckpt-save"]][2] == 8 == mem["stamps"][at["ckpt-d2h"]][2]


def test_the_startup_stamps_in_order_and_null_columns_on_the_cpu(cpu_run):
    _svc, _cfg, doc, trace, _writes = cpu_run
    mem = doc["memory"]
    sites = [s[0] for s in mem["stamps"]]
    head = ["run", "train-state", "restore", "place", "inference-start", "publish"]
    # the loop's end, then the shutdown's save on the writer's lane
    assert sites[:6] == head and sites[-2:] == ["close", "ckpt-d2h"]
    assert {"publish-d2h", "feed-start", "log-sync", "ckpt-save", "ckpt-d2h"} <= set(sites)
    assert all(s[3:] == [None, None, None] for s in mem["stamps"])  # null, never RSS
    assert mem["bytes_limit"] is None and mem["devices"] == 1
    times = [s[1] for s in mem["stamps"] if s[0] in head + ["feed-start", "close"]]
    assert times == sorted(times)
    start = doc["startup"]
    assert start["run_entry_unix_s"] <= mem["stamps"][0][1] <= start["loop_entry_unix_s"]
    assert mem["window"]["stamp"][0] == "log-sync" and mem["window"]["stamp"][2] == 4
    assert mem["window"]["alive"]["train-state"] == 1
    assert not [e for e in trace["traceEvents"] if e["ph"] == "C"]  # no books, no track


def test_memory_is_written_after_the_first_sync_and_again_at_close(cpu_run):
    _svc, _cfg, doc, _trace, writes = cpu_run
    with_memory = [(keys, n) for keys, n in writes if n is not None]
    assert len(with_memory) == 2
    (first_keys, first_n), (last_keys, last_n) = with_memory
    assert "startup" in first_keys and "compiles" not in first_keys
    # run .. the first log-sync, and the newest stamp where the books were
    # closed behind the next dispatch
    assert "compiles" in last_keys and last_n > first_n >= 9
    assert all("memory" not in keys for keys, n in writes if n is None)
    assert last_n == len(doc["memory"]["stamps"])
    # the compile counts are the parent's — the dispatch's and, with
    # telemetry on, PerfTracker's cost analysis: the book compiles nothing
    row = doc["compiles"]["programs"]["train_step"]
    assert row["count"] == 2
    # and the update program's own sizes, from the executable the first
    # dispatch left on the lowering ``add_program`` made: the donated state
    # is aliased whole, the arguments hold it, a batch and the key
    sizes = row["memory"]
    assert set(sizes) == {"temp_bytes", "argument_bytes", "output_bytes", "alias_bytes"}
    owners = doc["memory"]["owners"]
    assert sizes["alias_bytes"] == owners["train-state"]["bytes_each"]
    batch = owners["batch"]["bytes_each"]  # less the fields the program never reads
    assert sizes["alias_bytes"] + batch // 2 < sizes["argument_bytes"] <= sizes["alias_bytes"] + 2 * batch
    assert sizes["temp_bytes"] > 0


@pytest.mark.timeout(300)
def test_scripted_books_reach_the_record_the_track_and_the_gauges(booked_run):
    from tpu_rl.obs import MetricsRegistry

    svc, _cfg, doc, trace, _writes = booked_run
    mem = doc["memory"]
    assert mem["bytes_limit"] == 10**9 and mem["stamps_taken"] >= len(mem["stamps"])
    live = [s[3] for s in mem["stamps"]]
    assert live == sorted(live) and all(s[4] == s[3] + 7 and s[5] == 4000 for s in mem["stamps"])
    w = mem["window"]
    assert w["in_use_peak_rose"] is True and w["scratch_bytes"] == 4000
    (close,) = [s for s in mem["stamps"] if s[0] == "close"]
    assert w["raised_by_learner"] is False and w["live_peak_bytes"] == close[4]
    assert w["stamp"] == close  # ever fuller: the window's fullest is its last
    assert live[-1] > close[3]  # the shutdown's save came after it
    # every loop stamp set a new maximum: all kept up to the bound
    assert mem["stamps_taken"] == len(mem["stamps"]) <= platform.MAX_LOOP_STAMPS
    # the device-mem track: one counter event a stamp, on the lanes' clock
    track = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    assert [e["name"] for e in track] == ["device-mem"] * mem["stamps_taken"]
    assert sorted(e["args"]["device-mem"] for e in track) == live
    anchor = trace["meta"]["wall_anchor_ns"] / 1e9
    by_live = {s[3]: s[1] for s in mem["stamps"]}
    for e in track:
        assert anchor + e["ts"] / 1e6 == pytest.approx(by_live[e["args"]["device-mem"]], abs=1e-4)
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X" and e["name"] == "log-sync"]
    syncs = [s for s in mem["stamps"] if s[0] == "log-sync"]
    assert len(spans) == len(syncs) == 5
    for sp, st in zip(spans, syncs):  # stamped at the site's exit, inside its span
        assert sp["ts"] <= (st[1] - anchor) * 1e6 <= sp["ts"] + sp["dur"] + 1
    # the two gauges are the book's last stamp, not a read of their own
    class NoPub:
        def send(self, *_a):
            pass

    reg = MetricsRegistry(role="learner")
    svc._emit_telemetry(reg, NoPub(), svc.timer, 20)
    gauges = {name: value for name, _labels, value in reg.snapshot()["gauges"]}
    last = mem["stamps"][-1]
    assert gauges["learner-device-mem-bytes"] == last[3]
    assert gauges["learner-device-mem-peak-bytes"] == last[4] + last[5]
    assert svc._book.record()["stamps_taken"] == mem["stamps_taken"]  # no read since
