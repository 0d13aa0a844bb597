"""Set-up traced from inside (ISSUE 34): the learner's lane ``startup``,
compilations as spans of the lane ``xla`` with a program name and a cache
verdict, and the record of both that outlives the ring
(``backend-learner.json``: ``startup``, ``compiles``)."""

import json
import re

import pytest

from benchmarks import harness
from tests.test_trace_lanes import _run_learner
from tpu_rl.config import Config
from tpu_rl.obs.trace import TraceRecorder
from tpu_rl.utils import platform

SITES = [
    "init-multihost", "imports", "mesh", "backend-open", "family", "train-state",
    "step-build", "restore", "place", "wire", "inference-start", "feed-start",
]


@pytest.fixture(scope="module")
def learner_run(tmp_path_factory):
    """One CPU learner run of ten updates whose train step is rebuilt after
    update 5 (the entropy anneal's switch): a compilation after start-up."""
    import contextlib
    import io

    tmp = tmp_path_factory.mktemp("startup")
    log = io.StringIO()
    # No floor under a phase's length: how long a compilation takes here (or
    # whether a persistent cache another test switched on answers it in a
    # millisecond) must not decide what these tests see.
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stdout(log):
        mp.setattr(platform, "XLA_SPAN_MIN_S", 0.0)
        svc, cfg = _run_learner(
            tmp, harness.free_port_block(2), n_updates=10, loss_log_interval=2,
            entropy_anneal={"at": 5, "coef": 0.001},
        )
    with open(tmp / "run" / "backend-learner.json") as f:
        return svc, json.load(f), log.getvalue()


@pytest.mark.timeout(300)
def test_every_site_of_the_startup_lane_once_in_order(learner_run):
    _svc, doc, _log = learner_run
    start = doc["startup"]
    assert start["ring_wrapped"] is False
    lane = [s for s in start["spans"] if s[0] == "startup"]
    assert [s[1] for s in lane] == SITES  # each once, in the order they ran
    for (_, _, a0, a_s, _), (_, _, b0, _, _) in zip(lane, lane[1:]):
        assert a0 + a_s <= b0  # none overlaps the next
    assert start["run_entry_unix_s"] <= lane[0][2]
    assert lane[-1][2] + lane[-1][3] <= start["loop_entry_unix_s"]
    assert start["loop_entry_unix_s"] < start["first_sync_end_unix_s"]
    # between the lane's spans lies the first broadcast, on the main lane
    first_publish = next(s for s in start["spans"] if s[:2] == ["main", "publish"])
    assert lane[-2][2] + lane[-2][3] <= first_publish[2] <= lane[-1][2]
    # the record was written when the first log-sync ended: it holds that
    # sync and the dispatches before it, and nothing of the later updates
    syncs = [s for s in start["spans"] if s[:2] == ["main", "log-sync"]]
    assert len(syncs) == 1
    assert syncs[0][2] + syncs[0][3] == pytest.approx(start["first_sync_end_unix_s"], abs=1e-6)
    assert [s[4]["update"] for s in start["spans"] if s[1] == "dispatch"] == [1, 2]


def test_compilations_are_xla_spans_and_an_aggregate(learner_run):
    _svc, doc, _log = learner_run
    xla = [s for s in doc["startup"]["spans"] if s[0] == "xla"]
    assert xla and {s[1] for s in xla} <= {"trace", "lower", "backend"}
    assert all(s[4]["fun"] and s[4]["thread"] for s in xla)
    # the update program compiled on the main lane, by name: under a
    # dispatch or, with telemetry on, under PerfTracker's cost analysis
    step = [s for s in xla if s[1] == "backend" and s[4]["fun"] == "train_step"]
    hosts = [
        s for s in doc["startup"]["spans"]
        if s[0] == "main" and s[1] in ("program-record", "dispatch")
    ]
    assert step and all(
        any(h[2] <= s[2] and s[2] + s[3] <= h[2] + h[3] + 1e-3 for h in hosts) for s in step
    )
    compiles = doc["compiles"]
    rows = compiles["programs"]
    assert rows["train_step"]["count"] >= 2  # the first build and the anneal's
    assert compiles["listener_calls"] >= sum(r["count"] for r in rows.values())
    assert doc["compile_s"] == round(sum(r["backend_s"] for r in rows.values()), 3)
    assert doc["cache_hits"] == sum(r["hits"] for r in rows.values())
    assert doc["cache_misses"] == sum(r["misses"] for r in rows.values())
    assert len(compiles["events"]) <= platform.MAX_COMPILE_EVENTS


def test_a_recompilation_after_the_first_sync_is_one_log_line(learner_run):
    _svc, _doc, log = learner_run
    lines = re.findall(r"^\[learner\] compiled (\S+) in (\S+) s \(cache (\w+)\) (.*)$", log, re.M)
    named = [l for l in lines if l[0] == "train_step"]
    # rebuilt when update 5 was accounted: update 6 compiled it, once (with
    # telemetry on in PerfTracker's cost analysis, whose executable the
    # dispatch then finds)
    assert len(named) == 1, log
    assert named[0][3] in ("under main/program-record update 6", "under main/dispatch update 6")
    # nothing compiled before the first log-sync is announced
    first_sync = log.index("[learner] update 2 ")
    assert all(log.index(f"compiled {l[0]} in {l[1]}") > first_sync for l in lines)


def test_compile_clock_under_a_recorder(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(platform, "XLA_SPAN_MIN_S", 0.0)  # as in learner_run
    rec = TraceRecorder(capacity=256, annotate=True)
    clock = platform.CompileClock(rec, "test")
    try:
        @jax.jit
        def startup_probe(x):
            return jnp.tanh(x @ x.T).sum()

        def phases():
            return {k: v for k, v in clock.programs.get("startup_probe", {}).items()}

        startup_probe(jnp.ones((48, 48)))
        first = phases()
        assert first["count"] == 1
        assert first["trace_s"] > 0 and first["lower_s"] > 0 and first["backend_s"] > 0
        calls = clock.n_calls
        startup_probe(jnp.ones((48, 48)))  # the same shape: nothing compiles
        assert phases() == first and clock.n_calls == calls
        startup_probe(jnp.ones((96, 96)))  # a new shape does
        second = phases()
        assert second["count"] == 2 and second["backend_s"] > first["backend_s"]
        spans, wrapped = rec.entries()
        mine = [s for s in spans if s[0] == "xla" and s[4]["fun"] == "startup_probe"]
        assert not wrapped and [s[1] for s in mine].count("backend") == 2
        assert all(s[4]["cache"] in ("hit", "miss", None) for s in mine)
        stats = clock.stats()
        assert set(stats) == {"compile_s", "cache_hits", "cache_misses"}
        rows = clock.programs.values()
        assert stats["compile_s"] == round(sum(r["backend_s"] for r in rows), 3)
        assert stats["cache_hits"] == sum(r["hits"] for r in rows)
        assert stats["cache_misses"] == sum(r["misses"] for r in rows)
    finally:
        clock.close()


def test_a_cache_verdict_goes_to_the_backend_phase_that_closes_next():
    """Driven through the listeners' own entry points: the persistent cache
    is off on the CPU, so no real compilation here has a verdict."""
    rec = TraceRecorder(capacity=16, annotate=True)
    clock = platform.CompileClock(rec, "test")
    try:
        backend = "/jax/core/compile/backend_compile_duration"
        clock._event("/jax/compilation_cache/cache_hits")
        clock._phase(backend, 100.0, 100.5, fun_name="jit(step)")
        clock._event("/jax/compilation_cache/cache_misses")
        clock._phase("/jax/core/compile/jaxpr_trace_duration", 101.0, 101.2, fun_name="step")
        clock._phase(backend, 101.2, 103.2, fun_name="jit(step)")
        clock._phase(backend, 104.0, 104.001, fun_name="jit(tiny)")  # under the floor
        clock._phase("/some/other/event", 0.0, 9.0, fun_name="jit(step)")
        assert clock.programs["step"] == {
            "count": 2, "trace_s": pytest.approx(0.2), "lower_s": 0.0,
            "backend_s": pytest.approx(2.5), "hits": 1, "misses": 1,
        }
        assert clock.programs["tiny"]["count"] == 1
        assert [(e[0], e[1], e[4]) for e in clock.events] == [
            ("backend", "step", "hit"), ("trace", "step", None), ("backend", "step", "miss"),
        ]
        spans, _ = rec.entries()
        assert [(s[1], s[4]["fun"], s[4]["cache"]) for s in spans] == [
            ("backend", "step", "hit"), ("trace", "step", None), ("backend", "step", "miss"),
        ]
        assert clock.stats() == {"compile_s": 2.501, "cache_hits": 1, "cache_misses": 1}
        assert clock.record()["listener_calls"] == 4
    finally:
        clock.close()


def test_the_event_list_is_bounded_and_the_aggregate_is_not():
    clock = platform.CompileClock()
    try:
        for i in range(platform.MAX_COMPILE_EVENTS + 7):
            clock._phase(
                "/jax/core/compile/backend_compile_duration", float(i), i + 0.5,
                fun_name=f"jit(p{i})",
            )
        doc = clock.record()
        assert len(doc["events"]) == platform.MAX_COMPILE_EVENTS
        assert doc["events_dropped"] == 7
        assert len(doc["programs"]) == platform.MAX_COMPILE_EVENTS + 7
    finally:
        clock.close()


def test_without_a_ring_or_a_result_dir_nothing_is_written(tmp_path, capsys):
    rec = TraceRecorder(capacity=0, annotate=True)
    with rec.span("imports", tid="startup"):
        assert rec.open_span("startup") == ("imports", None)
    assert rec.open_span("startup") is None and rec.entries()[0] == []
    backend = platform.BackendRecord("learner", Config(learner_device="cpu"), tracer=rec)
    try:
        backend.record_startup(1.0, 2.0, 3.0)
        backend.record_startup(1.0, 2.0, 3.0)  # once
        assert "startup" not in backend.info
    finally:
        backend.close()
    backend.close()
    assert {"compile_s", "compiles"} <= set(backend.info)
    assert list(tmp_path.iterdir()) == []
    # with a result_dir but no recorder (the colocated loop, a fleet
    # replica): the compile log, and no start-up record
    other = platform.BackendRecord(
        "colocated", Config(learner_device="cpu", result_dir=str(tmp_path))
    )
    other.record_startup(1.0, 2.0, 3.0)
    other.close()
    with open(tmp_path / "backend-colocated.json") as f:
        doc = json.load(f)
    assert "startup" not in doc and set(doc["compiles"]) == {
        "listener_calls", "programs", "events", "events_dropped",
    }
