"""Goodput plane unit tests (tpu_rl.obs.goodput / audit / top): ledger
exhaustiveness (buckets sum to elapsed within tolerance, double-counting
surfaces as overcommit rather than silent renormalization), straggler
robust-z math on synthetic fleets, the GET /goodput endpoint matrix, the
curses dashboard's pure frame builder + mocked-terminal render, and the
shared resume-audit schema (learner and colocated must stay byte-layout
compatible). The live-fleet invariants (ledger sums on a running
deployment, SIGSTOP straggler surfacing) are pinned by
examples/goodput_smoke.py.
"""

import json
import urllib.error
import urllib.request
from types import SimpleNamespace
from unittest import mock

import pytest

from tpu_rl.obs import (
    BUCKETS,
    GoodputLedger,
    MetricsRegistry,
    TelemetryAggregator,
    TelemetryHTTPServer,
    append_jsonl,
    append_resume,
    maybe_ledger,
    render_prometheus,
    robust_z,
    straggler_report,
)
from tpu_rl.obs.goodput import CKPT, COMPUTE, IDLE, WIRE


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


# ----------------------------------------------------------------- ledger
def test_ledger_exhaustive_spill_into_overhead():
    """Unattributed wall time lands in overhead: buckets sum EXACTLY to
    elapsed, ratios to 1 — the invariant the smoke pins within 1% live."""
    clk = FakeClock()
    led = GoodputLedger("learner", clock=clk)
    led.add(COMPUTE, 3.0)
    led.add(WIRE, 0.5)
    led.add(IDLE, 0.5)
    clk.t += 5.0  # 1.0 s the loop never attributed
    snap = led.snapshot()
    assert snap["role"] == "learner"
    assert snap["elapsed_s"] == pytest.approx(5.0)
    assert sum(snap["buckets"].values()) == pytest.approx(5.0)
    assert snap["buckets"]["overhead"] == pytest.approx(1.0)
    assert sum(snap["ratios"].values()) == pytest.approx(1.0)
    assert snap["goodput"] == pytest.approx(3.0 / 5.0)
    assert snap["overcommit_s"] == 0.0
    assert snap["overcommit_ratio"] == 0.0


def test_ledger_overcommit_reports_double_counting():
    """Attributing MORE than elapsed (a second thread's spans leaking into
    the main lane) must surface as overcommit, not be normalized away."""
    clk = FakeClock()
    led = GoodputLedger("worker", clock=clk)
    led.add(COMPUTE, 4.0)
    led.add(WIRE, 2.0)
    clk.t += 5.0  # only 5 s elapsed; 6 s attributed
    snap = led.snapshot()
    assert snap["overcommit_s"] == pytest.approx(1.0)
    assert snap["overcommit_ratio"] == pytest.approx(1.0 / 6.0)
    # Ratios stay a valid breakdown over the attributed total.
    assert sum(snap["ratios"].values()) == pytest.approx(1.0)
    assert snap["buckets"]["overhead"] == 0.0


def test_ledger_add_ignores_nonpositive_and_accumulates():
    clk = FakeClock()
    led = GoodputLedger("storage", clock=clk)
    led.add(COMPUTE, -1.0)
    led.add(COMPUTE, 0.0)
    led.add(COMPUTE, 0.25)
    led.add(COMPUTE, 0.25)
    clk.t += 1.0
    assert led.snapshot()["buckets"]["compute"] == pytest.approx(0.5)


def test_ledger_zero_elapsed_snapshot_is_finite():
    led = GoodputLedger("x", clock=FakeClock())
    snap = led.snapshot()
    assert snap["goodput"] == 0.0
    assert all(v == 0.0 for v in snap["ratios"].values())


def test_ledger_publish_gauge_families_and_prometheus_names():
    """publish() sets the whole documented gauge family, and the names
    survive Prometheus sanitization the way tpu_rl.obs.top parses them."""
    clk = FakeClock()
    led = GoodputLedger("learner", clock=clk)
    led.add(COMPUTE, 6.0)
    led.add(CKPT, 1.0)
    clk.t += 10.0
    reg = MetricsRegistry(role="learner")
    snap = led.publish(reg)
    gauges = dict(
        ((name, tuple(labels.items())), value)
        for name, labels, value in reg.snapshot()["gauges"]
    )
    assert gauges[("learner-goodput-ratio", ())] == pytest.approx(0.6)
    for b in BUCKETS:
        assert (f"learner-time-{b}-ratio", ()) in gauges
    assert gauges[("learner-time-overcommit-ratio", ())] == 0.0
    assert snap["goodput"] == pytest.approx(0.6)

    agg = TelemetryAggregator(registry=reg)
    text = render_prometheus(agg)
    assert "learner_goodput_ratio{" in text and "} 0.6" in text
    assert "learner_time_queue_wait_ratio" in text

    from tpu_rl.obs import top

    rows = top.goodput_rows(top.parse_prometheus(text))
    assert rows["learner"]["goodput"] == pytest.approx(0.6)
    assert rows["learner"]["buckets"]["queue-wait"] == 0.0
    assert rows["learner"]["buckets"]["ckpt"] == pytest.approx(0.1)


def test_maybe_ledger_plane_gate():
    assert maybe_ledger("worker", False) is None
    led = maybe_ledger("worker", True)
    assert isinstance(led, GoodputLedger) and led.role == "worker"


# ------------------------------------------------------------- stragglers
def test_robust_z_uniform_fleet_no_stragglers():
    """A uniform fleet with measurement jitter must NOT flag stragglers:
    the MAD floor (5% of the median) keeps tiny jitter from exploding."""
    rates = {w: 10.0 + 0.01 * (w % 3) for w in range(8)}
    scores, top = straggler_report(frame_rate=rates)
    assert all(s < 1.0 for s in scores.values())


def test_straggler_one_slow_wid_is_top1():
    rates = {0: 10.0, 1: 10.2, 2: 9.9, 3: 1.0}  # wid 3 is SIGSTOP-slow
    scores, top = straggler_report(frame_rate=rates)
    assert top[0]["wid"] == 3
    assert top[0]["score"] > 2.0
    assert scores[3] == max(scores.values())
    # Frame rate is oriented: BELOW median = straggling (negated z).
    assert top[0]["z"]["frame-rate"] > 0


def test_straggler_staleness_and_rtt_oriented_above_median():
    stale = {0: 0.0, 1: 1.0, 2: 0.0, 3: 40.0}
    rtt = {0: 0.001, 1: 0.0012, 2: 0.0009, 3: 0.25}
    scores, top = straggler_report(staleness=stale, rtt=rtt)
    assert top[0]["wid"] == 3
    assert set(top[0]["signals"]) == {"staleness", "rtt"}


def test_straggler_missing_signals_tolerated():
    """A wid with only one signal (no rtt estimate yet) is judged on what
    it has; empty inputs produce an empty report."""
    scores, top = straggler_report(
        frame_rate={0: 10.0, 1: 10.0}, rtt={2: 0.5}
    )
    assert set(scores) == {0, 1, 2}
    assert scores[2] == 0.0  # a single-member signal has no fleet to lag
    assert straggler_report() == ({}, [])


def test_robust_z_empty_and_median():
    assert robust_z({}) == {}
    z = robust_z({0: 1.0, 1: 2.0, 2: 3.0})
    assert z[1] == pytest.approx(0.0)
    assert z[0] < 0 < z[2]


def test_robust_z_absolute_floor_bounds_zero_median_signals():
    """A fleet whose healthy median is exactly 0 (staleness) must not
    divide by ~0: the floor turns the z into 'excess in signal units'."""
    stale = {0: 0.0, 1: 0.0, 2: 0.0, 3: 40.0}
    z = robust_z(stale, floor=1.0)
    assert z[3] == pytest.approx(40.0)
    # straggler_report applies that floor: the score stays interpretable.
    scores, top = straggler_report(staleness=stale)
    assert top[0]["wid"] == 3
    assert 2.0 < scores[3] < 1e3


# ----------------------------------------------------------- /goodput HTTP
def test_http_goodput_endpoint_unwired_and_wired():
    agg = TelemetryAggregator()
    srv = TelemetryHTTPServer(agg, port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/goodput", timeout=5
            )
        assert ei.value.code == 404
        assert "not wired" in json.loads(ei.value.read())["error"]
    finally:
        srv.close()

    doc = {
        "storage": {"goodput": 0.8},
        "roles": {"learner/1": {"goodput": 0.5}},
        "stragglers": [{"wid": 3, "score": 9.0, "signals": {}}],
    }
    srv = TelemetryHTTPServer(agg, port=0, goodput=lambda: doc)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/goodput", timeout=5
        ) as r:
            assert r.status == 200
            got = json.loads(r.read())
        assert got == doc
    finally:
        srv.close()


# -------------------------------------------------------------- dashboard
def _frame_fixture():
    samples = [
        ("learner_goodput_ratio", {}, 0.7),
        ("learner_time_compute_ratio", {}, 0.7),
        ("learner_time_queue_wait_ratio", {}, 0.2),
        ("learner_time_idle_ratio", {}, 0.1),
        ("worker_goodput_ratio", {"wid": "1"}, 0.4),
        ("learner_throughput", {}, 12345.0),
        ("learner_mfu", {}, 0.31),
    ]
    goodput_doc = {
        "stragglers": [
            {
                "wid": 3,
                "score": 8.5,
                "signals": {"frame-rate": 1.0, "rtt": 0.2},
            }
        ]
    }
    slo_doc = {
        "ok": True,
        "rules": [{"rule": "gauge:learner-goodput-ratio>0.6", "ok": True}],
    }
    return samples, goodput_doc, slo_doc


def test_build_frame_golden():
    from tpu_rl.obs import top

    samples, goodput_doc, slo_doc = _frame_fixture()
    lines = top.build_frame(samples, goodput_doc, slo_doc, url="http://x/m")
    text = "\n".join(lines)
    assert "tpu_rl top" in lines[0] and "http://x/m" in lines[0]
    assert any(ln.startswith("  learner") and "70.0%" in ln for ln in lines)
    assert any("worker wid=1" in ln and "40.0%" in ln for ln in lines)
    assert "compute 70%" in text and "queue-wait 20%" in text
    assert "learner tps 12,345" in text and "mfu 31.00%" in text
    assert "wid 3: score 8.5" in text
    assert "SLO  PASS" in text
    assert "gauge:learner-goodput-ratio>0.6" in text
    # Degraded inputs must still render (empty fleet, no endpoints).
    empty = top.build_frame([], None, None)
    assert any("no goodput gauges yet" in ln for ln in empty)
    assert any("no /slo endpoint" in ln for ln in empty)
    # History plane off (history=None) renders byte-identical to the
    # default call — blank sparklines, never placeholders.
    assert top.build_frame(
        samples, goodput_doc, slo_doc, url="http://x/m", history=None
    ) == lines
    # With history, the matching panels gain trend lines.
    sparked = top.build_frame(
        samples, goodput_doc, slo_doc, url="http://x/m",
        history={
            "learner-goodput-ratio": [0.5, 0.6, 0.7],
            "learner-throughput": [100.0, 200.0, 150.0],
        },
    )
    text2 = "\n".join(sparked)
    assert top.SPARK_BLOCKS[0] in text2 and top.SPARK_BLOCKS[-1] in text2
    assert any(
        ln.startswith("  learner ") and "70.0%" in ln
        and any(c in top.SPARK_BLOCKS for c in ln) for ln in sparked
    )
    assert any(ln.strip().startswith("learner tps") and "▁" in ln
               for ln in sparked if "12,345" not in ln)


def test_top_bar_and_parse_prometheus():
    from tpu_rl.obs import top

    assert top.bar(0.0) == "-" * 20
    assert top.bar(1.5) == "#" * 20
    assert top.bar(0.5).count("#") == 10
    samples = top.parse_prometheus(
        '# HELP x y\nfoo_ratio{wid="2"} 0.25\nbad line\nnan_name oops\n'
        "plain_gauge 3\n"
    )
    assert ("foo_ratio", {"wid": "2"}, 0.25) in samples
    assert ("plain_gauge", {}, 3.0) in samples
    assert len(samples) == 2


def test_top_loop_renders_one_frame_with_mock_terminal():
    """_loop must render and exit on 'q' against a mocked stdscr — no tty,
    no real curses window (curs_set raises, which the loop tolerates)."""
    from tpu_rl.obs import top

    samples, goodput_doc, slo_doc = _frame_fixture()
    stdscr = mock.Mock()
    stdscr.getmaxyx.return_value = (40, 120)
    stdscr.getch.return_value = ord("q")
    args = SimpleNamespace(
        url="http://127.0.0.1:1/metrics", interval=0.01, timeout=0.1
    )
    with mock.patch.object(
        top, "collect",
        return_value=(samples, goodput_doc, slo_doc, None, None, False),
    ):
        assert top._loop(stdscr, args) == 0
    stdscr.erase.assert_called()
    stdscr.refresh.assert_called()
    drawn = [c.args[2] for c in stdscr.addnstr.call_args_list]
    assert any("unreachable" in ln for ln in drawn)
    assert any("GOODPUT" in ln for ln in drawn)


def test_top_once_unreachable_exits_nonzero(capsys):
    from tpu_rl.obs import top

    rc = top.main(["--once", "--url", "http://127.0.0.1:1/metrics"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "GOODPUT" in out and "STRAGGLERS" in out


# ------------------------------------------------------------------ audit
def test_append_jsonl_stamps_appends_and_swallows(tmp_path):
    assert append_jsonl(None, "x.jsonl", {"a": 1}) is False
    d = str(tmp_path / "r")
    assert append_jsonl(d, "x.jsonl", {"a": 1}) is True
    assert append_jsonl(d, "x.jsonl", {"a": 2, "t": 7.0}) is True
    recs = [
        json.loads(ln)
        for ln in (tmp_path / "r" / "x.jsonl").read_text().splitlines()
    ]
    assert [r["a"] for r in recs] == [1, 2]
    assert recs[0]["t"] > 0 and recs[1]["t"] == 7.0  # stamp kept if present
    # A result_dir that is actually a file: OSError swallowed, False back.
    blocked = tmp_path / "file"
    blocked.write_text("")
    assert append_jsonl(str(blocked), "x.jsonl", {"a": 3}) is False


def test_resume_audit_schema_identical_across_modes(tmp_path):
    """The learner's and the colocated loop's resume audit must emit the
    SAME schema into the same file — resume-smoke assertions work against
    either mode because both route through obs.audit.append_resume."""
    from tpu_rl.runtime.colocated import ColocatedLoop
    from tpu_rl.runtime.learner_service import LearnerService

    d_learner = tmp_path / "learner"
    d_colo = tmp_path / "colo"
    learner = SimpleNamespace(
        cfg=SimpleNamespace(result_dir=str(d_learner)), run_epoch=2
    )
    colo = SimpleNamespace(
        cfg=SimpleNamespace(result_dir=str(d_colo)), run_epoch=2
    )
    LearnerService._record_resume(learner, 17)
    ColocatedLoop._record_resume(colo, 17)
    rec_l = json.loads(
        (d_learner / "learner_resume.jsonl").read_text().splitlines()[0]
    )
    rec_c = json.loads(
        (d_colo / "learner_resume.jsonl").read_text().splitlines()[0]
    )
    assert set(rec_l) == set(rec_c) == {"idx", "epoch", "t"}
    assert rec_l["idx"] == rec_c["idx"] == 17
    assert rec_l["epoch"] == rec_c["epoch"] == 2


def test_append_resume_coerces_ints(tmp_path):
    import numpy as np

    assert append_resume(str(tmp_path), np.int64(5), np.int32(1)) is True
    rec = json.loads((tmp_path / "learner_resume.jsonl").read_text())
    assert rec["idx"] == 5 and rec["epoch"] == 1
