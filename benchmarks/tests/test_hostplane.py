"""The host lanes beside the device trace, on a capture recorded on a TPU v5e
(five updates of ``tf-longctx.learner`` with the spans of PR 23, cut down by
``benchmarks/cut_capture.py`` to one chip's ``XLA Modules`` / ``XLA Ops`` lines,
the ``tpu_rl/*`` events of the host plane and the ``Task Environment`` plane).
The hand-made cases are in ``tests/test_trace_lanes.py``, which tier-1 runs."""

import gzip
import json
import os

import pytest

from benchmarks import cut_capture, harness, hostplane, trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "tf_longctx_1chip_host.xplane.pb.gz")
DEVICE_ONLY = os.path.join(HERE, "data", "tf_longctx_1chip.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    return trace.load(RECORDED), hostplane.load(RECORDED)


def test_recorded_lanes_and_the_clock_they_share(recorded):
    tr, host = recorded
    assert tr.n_steps == 5 and tr.step_device_ms == pytest.approx(133.45, abs=0.05)
    assert {"main", "feeder", "publisher"} <= set(host.lanes)
    main = host.main()
    assert all(a.end <= b.start for a, b in zip(main, main[1:]))  # none nests
    updates = [s.args["update"] for s in main if s.name == "dispatch"]
    assert updates == list(range(updates[0], updates[0] + len(updates)))
    assert {s.name for s in host.lane("feeder")} == {"fetch", "assemble", "h2d-put", "queue-put"}
    assert host.clock_ok(tr)
    # every dispatch precedes its execution, by no more than two periods
    dev = tr.devices[0]
    runs = sorted((m for m in dev.modules if m.name == dev.step_name), key=lambda m: m.start)
    dispatches = [s for s in main if s.name == "dispatch"]
    for d, m in zip(reversed(dispatches), reversed(runs)):
        assert 0 < m.start - d.start < 2 * tr.window_s / tr.n_steps * 1e9
    # The check is as tight as the program is: an execution starts about a
    # millisecond into its dispatch span, so host spans 2 ms late are caught;
    # spans that are early are caught once a log-sync would end before the
    # execution it waited for (the loop is far behind the chip: tens of ms).
    def moved(ms):
        return hostplane.Host({
            lane: [hostplane.Span(s.name, s.start + ms * 1e6, s.dur, s.args) for s in spans]
            for lane, spans in host.lanes.items()
        })

    assert moved(+0.5).clock_ok(tr) and moved(-0.5).clock_ok(tr)
    assert not moved(+2.0).clock_ok(tr) and not moved(-150.0).clock_ok(tr)


def test_recorded_gaps_have_names(recorded):
    tr, host = recorded
    gaps = host.idle_gaps(tr)
    # the same gaps the device reduction reports, in the same order
    assert [s for _, s in gaps] == [s for _, s in tr.breakdown()["idle_gaps"]]
    assert "unattributed" not in {name for name, _ in gaps}
    assert host.attributed_share(tr) > 0.99
    # PR 23's finding: the chip waits while the main lane snapshots the actor
    # tree leaf by leaf (``publish``, 152 of this capture's 171 ms period);
    # what is left of the idle time is the launch and the log's read-back
    assert [name for name, _ in gaps[:6]] == ["publish"] * 6
    assert {name for name, _ in gaps[6:]} == {"dispatch", "diag-drain"}
    assert 0.059 < gaps[0][1] < 0.060


@pytest.mark.parametrize(
    "metric, low, high",
    [
        ("loop.idle_attributed_share", 99, 100),
        ("loop.host_ms_per_update", 166, 168),
        ("loop.sync_ms_per_update", 3.6, 3.8),
        ("publish.main_ms_per_update", 151, 153),
        ("publish.lane_ms_per_update", 170, 171),
        ("ckpt.main_ms_per_update", -1e-9, 1e-9),
        ("feed.starved_share", 0, 0.5),
    ],
)
def test_readers_on_the_recorded_capture(recorded, metric, low, high):
    tr, host = recorded
    run = harness.Run(
        spec=None, window=None, transitions_per_update=32 * 2048, bytes_per_update=0,
        device={"kind": "TPU v5 lite"}, parity={}, losses_finite=True,
        failed_updates=0, recompiles=0, paths={}, trace=tr,
    )
    read = harness.load_module(os.path.join(harness.HERE, "metrics", f"{metric}.py")).read
    hostplane.remember(tr, host)
    value = read(run)
    if isinstance(value, tuple):
        value, extra = value
        assert extra["clock_ok"] is True and len(extra["idle_gaps"]) == 10
        json.dumps(extra)  # goes into the result line as it is
    assert low <= value <= high
    # a capture without the program's spans (the parent's): nothing to read
    hostplane.remember(tr, None)
    assert read(run) is None
    hostplane.remember(tr, host)


def test_a_capture_without_a_host_plane_reads_as_nothing():
    assert hostplane.load(DEVICE_ONLY) is None  # PR 22's, cut to its device plane


def test_observer_slowdown_leaves_the_capture_out_of_the_run_period(recorded):
    tr, _ = recorded
    read = harness.load_module(
        os.path.join(harness.HERE, "metrics", "trace.observer_slowdown.py")
    ).read
    period = tr.window_s / tr.n_steps
    # lines every 5 updates at the capture's own period, but for the two
    # intervals the capture (updates 20..35) and its flush fall in
    stamps, t = [], 0.0
    for idx in range(10, 75, 5):
        t += 5 * period * (3.0 if 20 <= idx <= 40 else 1.0)
        stamps.append(harness.Seen(t, {"idx": idx, "ts": t}))
    spec = harness.Spec({}, {}, {"trace": {"start_update": 20, "updates": 15}}, 0, 20.0, True, 0.0)
    run = harness.Run(
        spec=spec, window=harness.Window(stamps[0], stamps[-1], stamps[1:]),
        transitions_per_update=1, bytes_per_update=0, device={}, parity={},
        losses_finite=True, failed_updates=0, recompiles=0, paths={}, trace=tr,
    )
    assert read(run) == pytest.approx(0.0, abs=1e-6)
    run.trace = None
    assert read(run) is None


def test_cutting_keeps_what_the_reductions_read(recorded):
    tr, host = recorded
    with gzip.open(RECORDED, "rb") as f:
        small = cut_capture.cut(f.read(), updates=2, skip=1)
    tr2, host2 = trace.read(small), hostplane.parse(small)
    assert tr2.n_steps == 2 and tr2.step_device_ms == pytest.approx(tr.step_device_ms, abs=0.3)
    assert tr2.scope_s("attn_flash_pallas") / tr2.n_steps == pytest.approx(
        tr.scope_s("attn_flash_pallas") / tr.n_steps, rel=0.01
    )
    assert host2.clock_ok(tr2) and host2.attributed_share(tr2) > 0.99
