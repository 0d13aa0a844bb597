"""``flops_smallthinker`` against counts made by hand at the published widths
of SmallThinker-21BA3B-Instruct, layers 0-3 (one global NoPE layer, three
4096-window RoPE layers), one rank of four, at the cell's batch."""

import numpy as np
import pytest

from benchmarks import flops_smallthinker as fs, harness, traffic

CONFIG = harness.load_json(f"{harness.HERE}/configs/smallthinker-21b-a3b.json")
PARAMS = CONFIG["params"]
T = 16384


def test_dense_layers_by_hand():
    d = 2560
    attention = d * 3584 + 2 * d * 512 + 3584 * d  # q (28 heads of 128), k and v (4 heads), o
    macs = 64 * d + 4 * (attention + d * 64) + d * (8 + 1)  # + the router over all 64
    assert fs.dense_forward_per_token(PARAMS) == 2 * macs == 169_456_640
    assert 4 * attention == 4 * 20_971_520  # the issue's bytes: attention's weights a layer


def pairs_without_seams() -> tuple[int, int]:
    """(a global layer's, a window layer's) kept pairs of one seamless window."""
    seen = np.arange(1, T + 1)
    return int(seen.sum()), int(np.minimum(seen, 4096).sum())


def test_attention_at_the_counted_pairs():
    whole, band = pairs_without_seams()
    assert whole == 134_225_920 and band == 58_722_304 and band / whole == pytest.approx(0.4375, abs=1e-3)
    assert fs.attention_forward_per_pair(PARAMS) == 4 * 3584
    pairs = 2 * (whole + 3 * band)  # two windows, one global and three window layers
    ops, nbytes = fs.attention_train(PARAMS, 2, pairs)
    assert ops == 3 * pairs * 4 * 3584
    # q, o, do, dq and their forward twins at 28 x 128; k, v, dk, dv and theirs at 4 x 128
    assert nbytes == 2 * 4 * T * (6 * 3584 + 6 * 512) * 2
    assert ops / 197e12 > nbytes / 819e9  # the MXU bounds it, also at the traffic's seams
    # with no seam attention is 53% of a token's forward operations, as the issue counts
    per_token = (whole + 3 * band) * 4 * 3584 / T
    total = per_token + fs.dense_forward_per_token(PARAMS) + 6 * 16 / 64 * fs.routed_forward_per_row(PARAMS) * 4
    assert per_token / total == pytest.approx(0.53, abs=0.01) and total == pytest.approx(511e6, rel=0.01)


def test_the_traffics_seams_cut_the_window_layers_share_to_about_three_fifths():
    """``traffic.firsts`` at the mix's mean episode length, many windows: the
    share of a global layer's pairs that a window layer keeps, as the cell's
    ``why`` and ``attn.window_kept_share`` state it (a count, not a time)."""
    mix = harness.load_json(f"{harness.HERE}/traffic/learner-long.json")["windows"]
    rng = np.random.default_rng(5)
    whole = band = 0
    for _ in range(400):
        fir = traffic.firsts(rng, T, mix["episode_len_mean"]) > 0
        t = np.arange(T)
        seen = t - np.maximum.accumulate(np.where(fir | (t == 0), t, 0)) + 1
        whole += seen.sum()
        band += np.minimum(seen, 4096).sum()
    assert band / whole == pytest.approx(0.61, abs=0.02)
    assert whole / 400 / T == pytest.approx(4632, rel=0.05)  # same-episode keys behind a query
    per_token = (whole + 3 * band) / 400 / T * 4 * 3584
    total = per_token + fs.dense_forward_per_token(PARAMS) + 1.5 * 4 * fs.routed_forward_per_row(PARAMS)
    # attention 44%, projections 39%, held experts 17% of a token's forward operations
    assert per_token / total == pytest.approx(0.44, abs=0.015)
    assert fs.dense_forward_per_token(PARAMS) / total == pytest.approx(0.39, abs=0.015)
    assert 6 * fs.routed_forward_per_row(PARAMS) / total == pytest.approx(0.17, abs=0.01)


def test_gated_experts_at_the_counted_rows():
    row = 2 * 3 * 2560 * 768  # W_down (relu(W_gate u) * W_up u): three products
    assert fs.routed_forward_per_row(PARAMS) == row == 11_796_480
    routed = 4 * 32768 * 6 / 4  # four layers, a quarter of the assignments each
    assert routed == 196_608 and routed / (4 * 16) == 3072  # rows a held expert, by count
    ops, nbytes = fs.gmm_train(PARAMS, routed)
    assert ops == 3 * routed * row
    assert nbytes == 3 * 2 * (routed * (2 * 2560 + 4 * 768) + 4 * 16 * 3 * 2560 * 768)
    assert fs.gmm_train(PARAMS, 0)[0] == 0  # no row routed here: only the weights' bytes
    whole, band = pairs_without_seams()
    pairs = 2 * (whole + 3 * band)
    assert fs.update(PARAMS, 2, pairs, routed) == pytest.approx(
        3 * (32768 * 169_456_640 + pairs * 4 * 3584 + routed * row))
    assert fs.update(PARAMS, 2, pairs, routed) == pytest.approx(50.2e12, rel=0.01)
    assert fs.update(PARAMS, 2, pairs, 2 * routed) - fs.update(PARAMS, 2, pairs, routed) == 3 * routed * row


def test_counted_reads_the_mean_of_the_lines_that_carry_the_key():
    rows = [harness.Seen(0.0, {"idx": 0, "ts": 0.0}), harness.Seen(1.0, {"idx": 2, "ts": 1.0, "moe-rows": 10.0}),
            harness.Seen(2.0, {"idx": 4, "ts": 2.0, "moe-rows": 20.0})]
    assert fs.counted(rows, "moe-rows") == 15.0 and fs.counted(rows, "attn-pairs-window") is None
