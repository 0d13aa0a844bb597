"""The seven ``setup.*`` readers (``benchmarks/startup.py``,
``benchmarks/metrics/setup.*.py``) on hand-made records, and on two records a
TPU v5e left (``data/backend_learner_{cold,warm}.json``: the learner's
``backend-learner.json`` of a cold and of a warm run of
``tf-longctx.learner``, with the three numbers that place it on the
benchmark's axis and the values that run's result line printed)."""

import copy
import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import harness, startup

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = [
    "setup.before_program_s", "setup.build_s", "setup.trace_lower_s", "setup.compile_s",
    "setup.cache_hit_share", "setup.warmup_s", "setup.named_share",
]
T0 = 1_790_000_000.0  # the program's unix clock; the benchmark's axis is T0 less


def reader(name):
    return harness.load_module(os.path.join(harness.HERE, "metrics", f"{name}.py"))


def as_run(doc, t_start, window_mono, window_ts):
    """What a reader takes of a ``harness.Run``."""
    return SimpleNamespace(
        paths=doc,
        notes={"window": {"setup_phases_s": {"imports": 3.5, "parity": 29.0}}},
        spec=SimpleNamespace(t_start=t_start),
        window=SimpleNamespace(start=SimpleNamespace(mono=window_mono, row={"ts": window_ts})),
    )


def value(name, run):
    got = reader(name).read(run)
    return got[0] if isinstance(got, tuple) else got


def xla(kind, fun, start, secs, cache=None, thread="MainThread"):
    return ["xla", kind, T0 + start, secs, {"fun": fun, "cache": cache, "thread": thread}]


@pytest.fixture
def record():
    """A start-up of 20 s entered 30 s into the process, a first sync at
    27 s of the program, the window 2 s after it."""
    sites = [
        ("init-multihost", 0.0, 0.0), ("imports", 0.0, 0.5), ("mesh", 0.5, 0.0),
        ("backend-open", 0.5, 0.5), ("family", 1.0, 1.0), ("train-state", 2.0, 10.0),
        ("step-build", 12.0, 0.0), ("restore", 12.0, 3.0), ("place", 15.0, 1.0),
        ("wire", 16.0, 2.0), ("inference-start", 18.0, 0.0), ("feed-start", 19.0, 1.0),
    ]
    spans = [["startup", n, T0 + a, d, None] for n, a, d in sites]
    spans += [
        ["main", "publish", T0 + 18.0, 0.5, None],  # 18.5-19.0: no span
        ["main", "program-record", T0 + 20.0, 3.0, {"update": 1}],
        ["main", "dispatch", T0 + 23.0, 2.0, {"update": 1}],
        ["main", "log-sync", T0 + 25.0, 2.0, None],
        # the update program: its trace holds a callee's; lowered twice
        xla("trace", "train_step", 20.0, 2.0),
        xla("trace", "row_add", 20.5, 0.5),
        xla("lower", "train_step", 22.0, 1.0),
        xla("backend", "train_step", 23.0, 1.5, "hit"),
        # the feeder compiled while the main thread did
        xla("backend", "device_put", 23.5, 0.5, "miss", thread="feeder"),
        xla("trace", "device_put", 23.2, 0.3, thread="feeder"),
    ]
    return {
        "platform": "tpu",
        "startup": {
            "run_entry_unix_s": T0, "loop_entry_unix_s": T0 + 20.0,
            "first_sync_end_unix_s": T0 + 27.0, "ring_wrapped": False, "spans": spans,
        },
        "compile_s": 4.0, "cache_hits": 2, "cache_misses": 2,
        "compiles": {
            "listener_calls": 900, "events_dropped": 0,
            "programs": {
                "train_step": {"count": 1, "trace_s": 2.0, "lower_s": 1.0, "backend_s": 1.5,
                               "hits": 1, "misses": 0},
                "row_add": {"count": 0, "trace_s": 0.5, "lower_s": 0.0, "backend_s": 0.0,
                            "hits": 0, "misses": 0},
                "device_put": {"count": 1, "trace_s": 0.3, "lower_s": 0.0, "backend_s": 0.5,
                               "hits": 0, "misses": 1},
                "snapshot": {"count": 2, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 2.0,
                             "hits": 1, "misses": 1},
            },
            "events": [
                ["trace", "train_step", T0 + 20.0, 2.0, None, "MainThread"],  # in the ring too
                ["backend", "snapshot", T0 + 27.5, 1.0, "hit", "MainThread"],  # before the window
                ["backend", "snapshot", T0 + 40.0, 1.0, "miss", "MainThread"],  # after its start
            ],
        },
    }


@pytest.fixture
def run(record):
    # the benchmark's axis: process start at 100.0, run() entered at 130.0
    return as_run(record, t_start=100.0, window_mono=159.0, window_ts=T0 + 29.0)


def test_the_seven_on_a_hand_made_record(run):
    got, extra = reader("setup.before_program_s").read(run)
    assert got == pytest.approx(30.0) and extra["phases_end_s"]["parity"] == 29.0
    got, extra = reader("setup.build_s").read(run)
    assert got == pytest.approx(15.0)  # family + train-state + step-build + restore + place
    assert extra["startup_lane_s"] == pytest.approx(20.0)
    assert extra["sites"]["train-state"] == 10.0 and len(extra["sites"]) == 12
    assert value("setup.warmup_s", run) == pytest.approx(9.0)
    # before + the lane + warm-up is the run's setup_s: window start less process start
    assert 30.0 + extra["startup_lane_s"] + 9.0 == pytest.approx(159.0 - 100.0)
    # 27 s to the first sync's end, 18.5-19.0 under no span
    assert value("setup.named_share", run) == pytest.approx(100 * 26.5 / 27.0)
    _, extra = reader("setup.named_share").read(run)
    assert extra == {"ring_wrapped": False, "ring_entries": 22, "xla_entries": 6}


def test_a_callees_trace_is_inside_its_callers_a_union_not_a_sum(run):
    # main thread: trace 20-22 (the callee's 20.5-21 inside) + lower 22-23;
    # feeder: trace 23.2-23.5 — 3.3 s, where the spans sum to 3.8
    got, extra = reader("setup.trace_lower_s").read(run)
    assert got == pytest.approx(3.3)
    assert extra["top"][0] == ["train_step", 3.0]
    # two threads in the backend at once count twice: thread-seconds
    got, extra = reader("setup.compile_s").read(run)
    assert got == pytest.approx(1.5 + 0.5 + 1.0)  # + the snapshot before the window
    assert extra["top"][:2] == [["snapshot", 2.0], ["train_step", 1.5]]
    assert extra["listener_calls"] == 900 and extra["events_dropped"] == 0


def test_verdicts_after_the_windows_start_are_left_out(run):
    got, extra = reader("setup.cache_hit_share").read(run)
    # the late snapshot's miss is the window's; by seconds: train_step 1.5 +
    # the early snapshot 1.0 hit, the feeder's device_put 0.5 missed
    assert extra == {"hits": 2, "misses": 1, "hit_s": 2.5, "miss_s": 0.5}
    assert got == pytest.approx(100 * 2 / 3)


def test_a_phase_that_runs_into_the_window_is_cut_at_its_start(record):
    record["compiles"]["events"].append(
        ["backend", "late", T0 + 28.5, 4.0, "miss", "MainThread"]
    )
    run = as_run(record, 100.0, 159.0, T0 + 29.0)
    assert value("setup.compile_s", run) == pytest.approx(3.0 + 0.5)


def test_no_verdicts_no_share(record):
    for row in record["compiles"]["programs"].values():
        row["hits"] = row["misses"] = 0
    for ev in record["compiles"]["events"]:
        ev[4] = None
    assert reader("setup.cache_hit_share").read(as_run(record, 100.0, 159.0, T0 + 29.0)) is None


@pytest.mark.parametrize("gone", ["startup", "compiles", "both"])
@pytest.mark.parametrize("name", NAMES)
def test_a_record_from_before_the_lanes_reads_as_nothing(record, name, gone):
    """The parent's ``backend-learner.json`` (and the colocated loop's, which
    has ``compiles`` and no ``startup``): every reader returns None."""
    old = copy.deepcopy(record)
    for key in ("startup", "compiles"):
        if gone in (key, "both"):
            del old[key]
    assert reader(name).read(as_run(old, 100.0, 159.0, T0 + 29.0)) is None


def test_union_s():
    assert startup.union_s([]) == 0.0
    assert startup.union_s([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == pytest.approx(4.0)


@pytest.mark.parametrize("state", ["cold", "warm"])
def test_recorded(state):
    """The readers give on the record what the chip run's line printed, and
    the two states are told apart without the size of ``setup_s``."""
    with open(os.path.join(HERE, "data", f"backend_learner_{state}.json")) as f:
        fixture = json.load(f)
    run = as_run(fixture["record"], **fixture["axis"])
    for name in NAMES:
        assert value(name, run) == pytest.approx(fixture["line"][name], rel=1e-6), name
    s = startup.of_run(run)
    assert not s.ring_wrapped
    assert [x[1] for x in s.lane(startup.STARTUP)] == [
        "init-multihost", "imports", "mesh", "backend-open", "family", "train-state",
        "step-build", "restore", "place", "wire", "inference-start", "feed-start",
    ]
    assert value("setup.named_share", run) >= 95.0
    # told apart without the size of setup_s: by count, and by backend
    # seconds (cold 21.37 s missed; warm all 1.76 s hit). Smallthinker's cold
    # run reads 40% by count (its four `while` programs had been compiled by
    # the benchmark's checks in the same process) and 0.2% by seconds.
    share, extra = reader("setup.cache_hit_share").read(run)
    by_seconds = 100 * extra["hit_s"] / (extra["hit_s"] + extra["miss_s"])
    if state == "cold":
        assert share < 10.0 and by_seconds < 10.0
    else:
        assert share > 90.0 and by_seconds > 90.0
    # before the program + the lane + warm-up is the run's setup_s: the
    # window opened at learn.jsonl's second line, half a second after its first
    setup_s = fixture["axis"]["window_mono"] - fixture["axis"]["t_start"]
    _, extra = reader("setup.build_s").read(run)
    parts = value("setup.before_program_s", run) + extra["startup_lane_s"]
    assert parts + value("setup.warmup_s", run) == pytest.approx(setup_s, abs=1e-6)
    assert 0.0 < setup_s - fixture["line"]["first_line_s"] < 1.0
