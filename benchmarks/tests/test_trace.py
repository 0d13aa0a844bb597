"""The trace reduction, on a capture recorded on a TPU v5e (three updates of
``tf-longctx.learner``, cut down to one chip's ``XLA Modules`` / ``XLA Ops``
lines and the two metadata stats the reduction reads) and on hand-made events."""

import os

import numpy as np
import pytest

from benchmarks import harness, trace
from benchmarks.trace import DeviceTrace, Event

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "tf_longctx_1chip.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    return trace.load(RECORDED)


def raster(events, lo, hi, step_ns=100.0):
    """Covered time by painting a bitmap: an independent check of union_ns."""
    n = int((hi - lo) / step_ns) + 1
    bits = np.zeros(n, bool)
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            bits[int((a - lo) / step_ns) : int(np.ceil((b - lo) / step_ns))] = True
    return bits.sum() * step_ns


def test_union_and_subtract_on_hand_intervals():
    assert trace.union_ns([]) == 0
    assert trace.union_ns([(0, 10), (5, 12), (20, 21), (20.5, 20.75)]) == 13
    # [0,10] + [12,15] with [8,13] taken away: [0,8] + [13,15]
    assert trace.subtract_ns([(0, 10), (12, 15)], [(8, 13)]) == 10


def test_recorded_window_is_whole_periods(recorded):
    dev = recorded.devices[0]
    assert dev.name == "/device:TPU:0" and dev.step_name == "jit_train_step"
    runs = sorted((m for m in dev.modules if m.name == "jit_train_step"),
                  key=lambda m: m.start)
    # The capture opened in the middle of an execution: its event is short
    # and its start is the capture's, so it may not open the window.
    assert len(runs) == 5 and runs[0].dur < 0.6 * runs[1].dur
    assert dev.steps == runs[1:] and dev.n_steps == 3
    assert dev.window == (runs[1].start, runs[4].start)
    # one update of this cell took ~180 ms of wall time in the traced run
    assert 0.17 < recorded.window_s / 3 < 0.19
    assert recorded.step_device_ms == pytest.approx(133.46, abs=0.05)


def test_recorded_busy_matches_a_bitmap(recorded):
    dev = recorded.devices[0]
    lo, hi = dev.window
    assert recorded.busy_s * 1e9 == pytest.approx(raster(dev.ops, lo, hi), rel=2e-3)
    assert 0.2 < 1 - recorded.busy_s / recorded.window_s < 0.3


def test_recorded_scope_time_is_found_in_both_passes(recorded):
    dev = recorded.devices[0]
    lo, hi = dev.window
    under = [o for o in dev.ops if "attn_flash_pallas" in o.scope]
    assert any("transpose(" in o.scope for o in under)  # backward
    assert any("transpose(" not in o.scope for o in under)  # forward
    assert recorded.scope_s("attn_flash_pallas") * 1e9 == pytest.approx(
        raster(under, lo, hi), rel=2e-3
    )
    assert recorded.scope_s("lstm_pallas|lstm_scan") is None
    # attention is most of the update's device time in this cell
    assert recorded.scope_s("attn_flash_pallas") > 0.5 * recorded.busy_s


def test_labels_are_the_same_for_every_layer():
    op = Event("fusion.222", 0, 1, "jit(train_step)/transpose(jvp(TransformerActorCritic))"
               "/block0/block0._ff/ff1/dot_general:", "convolution fusion")
    assert trace.label(op) == "bwd block*._ff/ff*/dot_general"
    assert trace.label(Event("copy.449", 0, 1, "", "data formatting")) == "data formatting:copy"


def test_breakdown_shape(recorded):
    bd = recorded.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert bd["device_ops"][0][0].startswith("bwd attn_flash_pallas/flash_mha_bwd_dkv")
    secs = [s for _, s in bd["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all(name == "unattributed" for name, _ in bd["idle_gaps"])


def test_exposed_collective_time_on_hand_made_events():
    step = lambda t: Event("jit_step", t, 50)  # noqa: E731
    dev = DeviceTrace(
        "/device:TPU:0",
        modules=[step(-100), step(0), step(100)],
        ops=[
            Event("while.1", 0, 40),  # encloses its body: says nothing
            Event("fusion.1", 0, 10),
            Event("all-reduce-start.1", 8, 1),  # hidden under fusion.1
            Event("all-reduce-done.1", 12, 3),  # exposed: nothing else runs
            Event("fusion.2", 20, 10),
            Event("all-gather.3", 25, 10),  # half hidden
        ],
    )
    assert dev.window == (0, 100)
    assert dev.exposed_collective_ns() == 3 + 5
    assert trace.Trace([dev]).busy_s * 1e9 == 40  # the while event covers [0, 40]


def test_a_capture_without_a_tpu_plane_reads_as_nothing():
    assert trace.read(b"") is None


@pytest.mark.parametrize(
    "metric, low, high",
    [
        ("device.idle_share", 20, 30),
        ("step.device_ms", 133, 134),
        ("kernel.attn_ms_per_update", 83, 84),
        ("attn_flash_roofline", 9.9, 10.2),
        ("step.mfu", 18, 20),
    ],
)
def test_readers_on_the_recorded_trace(recorded, metric, low, high):
    config = harness.load_json(os.path.join(harness.HERE, "configs", "tf-longctx.json"))
    spec = harness.Spec({"name": "x", "chips": 1}, config, {}, 0, 20.0, True, 0.0)
    run = harness.Run(
        spec=spec, window=None, transitions_per_update=32 * 2048, bytes_per_update=0,
        device={"kind": "TPU v5 lite"}, parity={}, losses_finite=True,
        failed_updates=0, recompiles=0, paths={}, trace=recorded,
    )
    value = harness.load_module(
        os.path.join(harness.HERE, "metrics", f"{metric}.py")
    ).read(run)
    value = value[0] if isinstance(value, tuple) else value
    assert low < value < high
