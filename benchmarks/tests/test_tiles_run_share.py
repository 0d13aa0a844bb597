"""``attn.tiles_run_share`` on ``learn.jsonl`` rows made by hand: the ratio of
the window's sums over both kinds of layer; nothing to read, and no error, on
the rows of a program that ships no tile counters (the parent's)."""

import types

import pytest

from benchmarks import harness

READER = harness.load_module(f"{harness.HERE}/metrics/attn.tiles_run_share.py")


def run_with(*rows):
    seen = [harness.Seen(float(i), {"idx": i, "ts": float(i), **row}) for i, row in enumerate(rows)]
    return types.SimpleNamespace(window=types.SimpleNamespace(rows=seen))


def tiles(run_global, run_window, band_global=272.0, band_window=420.0):
    return {"attn-tiles-run-global": run_global, "attn-tiles-run-window": run_window,
            "attn-tiles-band-global": band_global, "attn-tiles-band-window": band_window}


def test_the_share_is_the_ratio_of_the_windows_sums():
    # two windows an update: one global layer's 2 x 136 tiles, three window layers' 2 x 70
    got = READER.read(run_with(tiles(180.0, 370.0), tiles(272.0, 420.0)))
    assert got == pytest.approx(100 * (226.0 + 395.0) / (272.0 + 420.0))
    assert READER.read(run_with(tiles(272.0, 420.0))) == pytest.approx(100.0)  # no seam empties a tile


def test_a_program_without_the_counters_reads_nothing():
    pairs_only = {"attn-pairs-global": 150e6, "attn-pairs-window": 270e6}
    assert READER.read(run_with(pairs_only, pairs_only)) is None
    assert READER.read(run_with()) is None
    assert READER.read(run_with(tiles(0.0, 0.0, 0.0, 0.0))) is None
