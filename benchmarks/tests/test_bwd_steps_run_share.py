"""``attn.bwd_steps_run_share`` on ``learn.jsonl`` rows made by hand: the ratio
of the window's sums over the kinds of layer the rows carry; nothing to read,
and no error, on the rows of a program that ships no step counter (the
parent's)."""

import json
import types

import pytest

from benchmarks import harness

NAME = "attn.bwd_steps_run_share"
READER = harness.load_module(f"{harness.HERE}/metrics/{NAME}.py")
TILES = harness.load_module(f"{harness.HERE}/metrics/attn.tiles_run_share.py")


def run_with(*rows):
    seen = [harness.Seen(float(i), {"idx": i, "ts": float(i), **row}) for i, row in enumerate(rows)]
    return types.SimpleNamespace(window=types.SimpleNamespace(rows=seen))


def counters(run_global, run_window, steps_global, steps_window):
    return {"attn-tiles-run-global": run_global, "attn-tiles-run-window": run_window,
            "attn-tiles-band-global": 272.0, "attn-tiles-band-window": 420.0,
            "attn-bwd-steps-global": steps_global, "attn-bwd-steps-window": steps_window}


def test_the_share_is_the_ratio_of_the_windows_sums():
    # two windows an update, one global layer and three window layers; a backward that
    # steps over the rectangle: 2 x 256 and 3 x 2 x 256 steps a head
    rows = [counters(180.0, 370.0, 512.0, 1536.0), counters(272.0, 420.0, 512.0, 1536.0)]
    assert READER.read(run_with(*rows)) == pytest.approx(100 * (226.0 + 395.0) / 2048.0)


def test_a_grid_that_is_the_band_reads_the_tiles_run_share():
    rows = [counters(180.0, 370.0, 272.0, 420.0), counters(250.0, 401.0, 272.0, 420.0)]
    assert READER.read(run_with(*rows)) == pytest.approx(TILES.read(run_with(*rows)))
    assert READER.read(run_with(counters(272.0, 420.0, 272.0, 420.0))) == pytest.approx(100.0)


def test_a_family_of_global_layers_alone():
    row = {"attn-tiles-run-global": 90.0, "attn-tiles-band-global": 136.0,
           "attn-bwd-steps-global": 136.0}
    assert READER.read(run_with(row, row)) == pytest.approx(100 * 90.0 / 136.0)


def test_a_program_without_the_counter_reads_nothing():
    parents = {"attn-tiles-run-global": 180.0, "attn-tiles-run-window": 370.0,
               "attn-tiles-band-global": 272.0, "attn-tiles-band-window": 420.0}
    assert READER.read(run_with(parents, parents)) is None
    assert READER.read(run_with()) is None
    assert READER.read(run_with(counters(0.0, 0.0, 0.0, 0.0))) is None


def test_the_metric_is_registered_for_the_three_engaged_cells():
    with open(f"{harness.HERE}/../BENCHMARK.json") as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "kernels", "moves": "transitions_per_s",
        "workloads": ["smallthinker-21b-a3b.learner", "qwen3-next-80b-a3b.learner",
                      "glm-4.7-flash.learner"]}
