"""Live performance plane tests (tpu_rl.obs.perf + tpu_rl.obs.slo):
histogram quantile interpolation, live-vs-offline FLOPs/MFU agreement,
recompile counting across shape drift, SLO grammar + golden-fixture
determinism, the /slo and /prof HTTP endpoints, and the profiler crash
hook."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from tests.conftest import small_config
from tests.test_algos import make_batch
from tpu_rl.obs import (
    HIST_BUCKETS,
    MetricsRegistry,
    TelemetryAggregator,
    TelemetryHTTPServer,
    hist_quantile,
)
from tpu_rl.obs.perf import (
    PerfTracker,
    ProfilerCapture,
    device_memory_bytes,
    device_peak_flops,
    process_self_stats,
)
from tpu_rl.obs.slo import SloEngine, SloRule, parse_slo_spec


# ---------------------------------------------------------------- quantiles
def test_hist_quantile_empty_and_bounds():
    n_slots = len(HIST_BUCKETS) + 1
    assert hist_quantile([0] * n_slots, 0.99) is None
    # One observation in one bucket: every quantile stays inside its bounds.
    counts = [0] * n_slots
    counts[10] = 1
    hi = HIST_BUCKETS[10]
    lo = hi / 2.0
    for q in (0.0, 0.5, 0.99, 1.0):
        v = hist_quantile(counts, q)
        assert lo <= v <= hi, (q, v)


def test_hist_quantile_geometric_interpolation():
    """Rank fraction f inside an octave bucket (lo, 2*lo] interpolates as
    lo * 2**f — exact for log-uniform data, never outside the bucket."""
    n_slots = len(HIST_BUCKETS) + 1
    counts = [0] * n_slots
    counts[16] = 4  # bucket (2, 4]
    # rank = q * 4; frac = rank / 4 = q
    for q in (0.25, 0.5, 0.75, 1.0):
        assert hist_quantile(counts, q) == pytest.approx(2.0 * 2.0**q)


def test_hist_quantile_monotone_in_q_and_overflow():
    n_slots = len(HIST_BUCKETS) + 1
    counts = [1] * n_slots  # mass everywhere, incl. overflow slot
    qs = (0.1, 0.5, 0.9, 0.99, 0.999, 1.0)
    vals = [hist_quantile(counts, q) for q in qs]
    assert vals == sorted(vals)
    # Overflow slot interpolates within its synthetic (2^20, 2^21] octave.
    assert vals[-1] == pytest.approx(HIST_BUCKETS[-1] * 2.0)


def test_histogram_quantile_method_matches_module_fn():
    reg = MetricsRegistry(role="t", pid=0, host="h")
    h = reg.histogram("lat")
    for v in (0.001, 0.002, 0.004, 0.008, 1e9):
        h.observe(v)
    assert h.quantile(0.5) == hist_quantile(h.counts, 0.5)
    assert reg.histogram("empty").quantile(0.99) is None


# ------------------------------------------------------- flops / mfu / drift
def _small_step():
    import jax

    from tpu_rl.algos.registry import get_algo

    cfg = small_config(algo="PPO")
    fam, state, train_step = get_algo("PPO").build(cfg, jax.random.PRNGKey(0))
    step = jax.jit(train_step)
    batch = make_batch(cfg, fam)
    return step, state, batch


@pytest.mark.timeout(120)
def test_live_flops_and_mfu_agree_with_bench_methodology(monkeypatch):
    """The tracker's one-time AOT capture vs an inline
    lower/compile/cost_analysis on the SAME jitted step: FLOPs must agree
    exactly (same program), achieved FLOPs/s within 15% (independent timing
    windows over the same dispatches)."""
    import jax

    step, state, batch = _small_step()
    key = jax.random.PRNGKey(1)

    cost = step.lower(state, batch, key).compile().cost_analysis()
    flops_offline = float(cost["flops"])
    monkeypatch.setenv("TPU_RL_PEAK_FLOPS", "1e12")
    tracker = PerfTracker(n_devices=1)
    assert tracker.capture(step, state, batch, key)
    assert tracker.capture(step, state, batch, key) is False  # identity no-op
    assert tracker.flops_per_call == pytest.approx(flops_offline)
    assert flops_offline > 0

    # warmup (compile paid), then timed dispatches feeding both estimators
    s, metrics = step(state, batch, key)
    jax.block_until_ready(metrics)
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        t_it = time.perf_counter()
        s, metrics = step(s, batch, key)
        jax.block_until_ready(metrics)
        tracker.note(time.perf_counter() - t_it)
    dt = time.perf_counter() - t0

    achieved_offline = flops_offline * iters / dt
    achieved_live = tracker.achieved_flops_per_s()
    assert achieved_live is not None
    assert achieved_live == pytest.approx(achieved_offline, rel=0.15)
    # MFU path exercised via the env-var denominator (no TPU on CI).
    mfu = tracker.mfu()
    assert mfu is not None and mfu == pytest.approx(achieved_live / 1e12)


@pytest.mark.timeout(120)
def test_recompile_counter_exactly_one_after_shape_drift():
    """After warmup the counter reads 0; steady-state dispatches at the
    warmup shape keep it at 0; ONE drifted shape increments it exactly
    once — the sharp per-entry-point signal the plane is specified on."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return (x * 2.0).sum()

    tracker = PerfTracker(n_devices=1, peak_flops=None)
    x = jnp.ones((8, 4))
    tracker.capture(f, x)
    f(x).block_until_ready()  # warmup trace
    assert tracker.recompiles == 0
    for _ in range(5):  # steady state: zero increments
        f(x).block_until_ready()
    assert tracker.recompiles == 0
    f(jnp.ones((16, 4))).block_until_ready()  # shape drift: one retrace
    assert tracker.recompiles == 1
    f(jnp.ones((16, 4))).block_until_ready()  # drifted shape now cached
    assert tracker.recompiles == 1


def test_recompile_rebind_freezes_old_count():
    import jax
    import jax.numpy as jnp

    f1 = jax.jit(lambda x: x + 1)
    f2 = jax.jit(lambda x: x + 2)
    tracker = PerfTracker(n_devices=1)
    tracker.capture(f1, jnp.ones(3))
    f1(jnp.ones(3)).block_until_ready()
    f1(jnp.ones(5)).block_until_ready()  # drift on the first binding
    assert tracker.recompiles == 1
    tracker.capture(f2, jnp.ones(3))  # expected rebuild: freeze + rebase
    f2(jnp.ones(3)).block_until_ready()
    assert tracker.recompiles == 1  # old drift kept, new warmup not counted
    f2(jnp.ones(7)).block_until_ready()
    assert tracker.recompiles == 2


def test_device_peak_flops_cpu_env_and_tpu_table(monkeypatch):
    """TPU_RL_PEAK_FLOPS is the CPU-smoke denominator only; on a TPU the
    table decides and an unknown device_kind is an error, not None."""
    monkeypatch.setenv("TPU_RL_PEAK_FLOPS", "2.5e13")
    assert device_peak_flops() == 2.5e13

    class V5p:
        platform = "tpu"
        device_kind = "TPU v5p"

    assert device_peak_flops(V5p()) == 459e12  # env ignored off the CPU

    class Unknown:
        platform = "tpu"
        device_kind = "TPU v9 mystery"

    with pytest.raises(ValueError, match="TPU v9 mystery"):
        device_peak_flops(Unknown())
    monkeypatch.delenv("TPU_RL_PEAK_FLOPS")

    class Cpu:
        platform = "cpu"
        device_kind = "cpu"

    assert device_peak_flops(Cpu()) is None


def test_process_and_device_memory_stats():
    rss, n_fds = process_self_stats()
    assert rss > 0 and n_fds > 0  # /proc exists on the CI image
    in_use, peak = device_memory_bytes()
    assert in_use > 0 and peak >= in_use  # CPU backend: RSS fallback

    class Chip:
        """A TPU's two books: live buffers, and the programs' scratch."""

        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    gb = 1e9
    chip = Chip({"bytes_in_use": 0.3 * gb, "peak_bytes_in_use": 0.49 * gb,
                 "peak_bytes_reserved": 7.38 * gb, "bytes_reserved": 0.0})
    assert device_memory_bytes(chip) == (0.3 * gb, (0.49 + 7.38) * gb)
    # a runtime that keeps one book only
    assert device_memory_bytes(Chip({"bytes_in_use": 5.0, "peak_bytes_in_use": 9.0})) == (5.0, 9.0)
    assert device_memory_bytes(Chip({"bytes_in_use": 5.0})) == (5.0, 5.0)


# ---------------------------------------------------------------- slo parse
def test_slo_spec_parse_grammar():
    rules = parse_slo_spec(
        "p99:inference-rtt<5ms@window=30s,"
        "gauge:learner-mfu>0.002,"
        "rate:transport-rejected-frames<1/s,"
        "counter:storage-requeue-full<=10,"
        "p50:learner-step-time<200us"
    )
    assert [r.kind for r in rules] == ["p99", "gauge", "rate", "counter", "p50"]
    assert rules[0].threshold == pytest.approx(0.005)  # ms -> seconds
    assert rules[0].window_s == 30.0
    assert rules[1].window_s == 60.0  # default
    assert rules[3].op == "<="
    assert rules[4].threshold == pytest.approx(2e-4)  # us -> seconds
    assert parse_slo_spec("  ") == []


@pytest.mark.parametrize(
    "bad",
    [
        "p42:x<1",  # unknown kind
        "gauge:x~1",  # no comparison
        "gauge:<1",  # empty metric
        "gauge:x<fast",  # bad threshold
        "gauge:x<1@window=abc",  # bad qualifier
        "gauge:x<1@burn=0.5",  # unknown qualifier
    ],
)
def test_slo_spec_parse_errors(bad):
    with pytest.raises(ValueError) as ei:
        parse_slo_spec(bad)
    assert bad.split("@")[0].split(",")[0] in str(ei.value)


def test_config_validates_slo_spec():
    small_config(slo_spec="gauge:learner-mfu>0.002").validate()
    with pytest.raises(ValueError):
        small_config(slo_spec="p42:x<1").validate()


# ----------------------------------------------------------- slo evaluation
def _snap(counters=(), gauges=(), hists=()):
    return {
        "counters": [list(c) for c in counters],
        "gauges": [list(g) for g in gauges],
        "hists": [list(h) for h in hists],
    }


def _rtt_hist(ms_values):
    reg = MetricsRegistry(role="w", pid=0, host="h")
    h = reg.histogram("inference-rtt")
    for v in ms_values:
        h.observe(v / 1e3)
    return ["inference-rtt", {}, list(h.counts), sum(ms_values) / 1e3,
            len(ms_values)]


def test_slo_engine_golden_fixture_deterministic():
    """Same snapshots + same `now` values => identical verdicts, every
    field. The engine must be a pure function of (fixture, clock)."""
    fixture = [
        _snap(
            counters=[["transport-rejected-frames", {}, 10.0]],
            gauges=[["learner-mfu", {}, 0.01]],
            hists=[_rtt_hist([1.0] * 99 + [2.0])],
        )
    ]
    spec = (
        "p99:inference-rtt<5ms@window=30s,"
        "gauge:learner-mfu>0.002,"
        "rate:transport-rejected-frames<1/s"
    )

    def run():
        eng = SloEngine(spec)
        out = [eng.evaluate(fixture, now=t) for t in (0.0, 1.0, 2.0)]
        return out, eng.failed

    (a, fa), (b, fb) = run(), run()
    assert a == b and fa == fb
    final = a[-1]
    assert final["ok"] is True and final["failing"] == 0
    by_rule = {r["kind"]: r for r in final["rules"]}
    assert by_rule["p99"]["value"] < 0.005
    assert by_rule["gauge"]["value"] == 0.01
    # constant counter across evaluations -> zero rate
    assert by_rule["rate"]["value"] == pytest.approx(0.0)
    assert all(r["burn_rate"] == 0.0 for r in final["rules"])


def test_slo_engine_failure_burn_rate_and_rate_rule():
    spec = "gauge:learner-mfu>0.5,rate:transport-rejected-frames<1/s"
    eng = SloEngine(spec)
    # Counter grows 2/s; gauge is below its floor -> both rules hard-fail.
    for t in range(5):
        fix = [_snap(
            counters=[["transport-rejected-frames", {}, 2.0 * t]],
            gauges=[["learner-mfu", {}, 0.001]],
        )]
        verdict = eng.evaluate(fix, now=float(t))
    assert verdict["ok"] is False and verdict["failing"] == 2
    by_rule = {r["kind"]: r for r in verdict["rules"]}
    assert by_rule["rate"]["value"] == pytest.approx(2.0)
    assert by_rule["gauge"]["burn_rate"] == 1.0
    # first rate evaluation had no delta (ok=None, doesn't burn) -> 4/4 since
    assert by_rule["rate"]["samples"] == 4
    assert eng.failed


def test_slo_engine_no_data_neither_passes_nor_burns():
    eng = SloEngine("p99:never-recorded<1ms")
    verdict = eng.evaluate([_snap()], now=0.0)
    assert verdict["ok"] is True  # no hard failure...
    assert verdict["no_data"] == 1  # ...but silence is surfaced
    assert verdict["rules"][0]["ok"] is None
    assert not eng.failed


def test_slo_engine_merges_hists_and_worst_case_gauges():
    # Two sources: p99 must reflect the MERGED distribution; a `<` gauge
    # rule must compare against the WORST (max) source.
    fix = [
        _snap(hists=[_rtt_hist([1.0] * 50)],
              gauges=[["learner-queue-depth", {}, 1.0]]),
        _snap(hists=[_rtt_hist([40.0] * 50)],
              gauges=[["learner-queue-depth", {}, 9.0]]),
    ]
    eng = SloEngine("p99:inference-rtt<5ms,gauge:learner-queue-depth<5")
    verdict = eng.evaluate(fix, now=0.0)
    p99, depth = verdict["rules"]
    assert p99["ok"] is False and p99["value"] > 0.02  # tail source visible
    assert depth["ok"] is False and depth["value"] == 9.0


def test_slo_rule_check_ops():
    r = SloRule(raw="x", kind="gauge", metric="m", op=">=", threshold=2.0)
    assert r.check(2.0) and not r.check(1.9) and not r.upper_bound


# -------------------------------------------------------------- http plane
@pytest.mark.timeout(30)
def test_http_slo_endpoint_unwired_and_wired():
    agg = TelemetryAggregator()
    srv = TelemetryHTTPServer(agg, port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/slo", timeout=5
            )
        assert ei.value.code == 404
    finally:
        srv.close()

    verdicts = [{"ok": True, "failing": 0}, {"ok": False, "failing": 1}]
    srv = TelemetryHTTPServer(agg, port=0, slo=lambda: verdicts[0])
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(f"{base}/slo", timeout=5) as r:
            assert r.status == 200
            assert json.loads(r.read())["ok"] is True
        verdicts.pop(0)  # flip to a failing report
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/slo", timeout=5)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["failing"] == 1
    finally:
        srv.close()


@pytest.mark.timeout(30)
def test_http_prof_endpoint_validation_and_conflict(tmp_path):
    agg = TelemetryAggregator()
    srv = TelemetryHTTPServer(agg, port=0)  # prof not wired
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/prof?ms=10", timeout=5
            )
        assert ei.value.code == 404
    finally:
        srv.close()

    calls = []

    def fake_prof(ms):
        calls.append(ms)
        if len(calls) > 1:
            return False, "capture in progress"
        return True, str(tmp_path / "prof-dir")

    srv = TelemetryHTTPServer(agg, port=0, prof=fake_prof)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/prof?ms=abc", timeout=5)
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/prof?ms=0", timeout=5)
        assert ei.value.code == 400
        assert calls == []  # validation failures never reach the profiler
        with urllib.request.urlopen(f"{base}/prof?ms=25", timeout=5) as r:
            doc = json.loads(r.read())
            assert r.status == 200 and doc["started"] and doc["ms"] == 25
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/prof?ms=25", timeout=5)
        assert ei.value.code == 409  # overlap refused
        assert calls == [25, 25]
    finally:
        srv.close()


@pytest.mark.timeout(60)
def test_http_concurrent_scrapes():
    """ThreadingHTTPServer must serve overlapping /metrics, /healthz and
    /slo scrapes without erroring or interleaving bodies."""
    agg = TelemetryAggregator()
    agg.registry.counter("storage-windows").inc(3)
    srv = TelemetryHTTPServer(agg, port=0, slo=lambda: {"ok": True})
    errors: list = []
    bodies: list = []

    def scrape(path):
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}{path}", timeout=10
            ) as r:
                bodies.append((path, r.status, r.read()))
        except Exception as e:  # noqa: BLE001 — collected for the assert
            errors.append((path, e))

    try:
        threads = [
            threading.Thread(target=scrape, args=(p,))
            for p in ("/metrics", "/healthz", "/slo") * 8
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=15)
        assert not errors
        assert len(bodies) == 24
        for path, status, body in bodies:
            assert status == 200
            if path == "/metrics":
                assert b"storage_windows" in body
            else:
                json.loads(body)
    finally:
        srv.close()


# ---------------------------------------------------------------- profiler
@pytest.mark.timeout(60)
def test_profiler_capture_serializes_and_bounds(tmp_path):
    prof = ProfilerCapture(str(tmp_path), default_ms=50)
    try:
        started, path = prof.capture_async(ms=200)
        assert started and os.path.isdir(path)
        again, reason = prof.capture_async(ms=10)
        assert not again and reason == "capture in progress"
        # stop_trace serialises on the CPU: under six loaded workers it has taken over 10 s
        deadline = time.monotonic() + 40
        while prof.active and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not prof.active and prof.n_captures == 1
        # trace landed (jax writes .trace/.pb under the dir)
        assert any(os.scandir(path))
        started, _ = prof.capture_async(ms=10)  # free again after the bound
        assert started
        deadline = time.monotonic() + 10
        while prof.active and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        prof.close()


@pytest.mark.timeout(60)
def test_crash_hook_stops_profiler(tmp_path):
    """dump_on_crash must stop an in-flight capture even when no flight
    recorder is installed — the trace meant to explain the crash survives."""
    from tpu_rl.obs import flightrec

    prof = ProfilerCapture(str(tmp_path))
    try:
        assert prof.start() is not None and prof.active
        flightrec.dump_on_crash(RuntimeError("boom"))
        assert not prof.active
        assert prof.n_captures == 1
    finally:
        prof.close()
    # close() unhooks: a later crash pass runs zero stale hooks
    assert prof._crash_stop not in flightrec._CRASH_HOOKS
