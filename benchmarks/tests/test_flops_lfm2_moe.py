"""``flops_lfm2_moe`` against counts made by hand at the published widths of
LFM2-24B-A2B, layers 1-5 (``D F C C C``: a dense convolution layer, an
attention layer and three convolution layers with experts), one rank of eight,
at the cell's batch of 4 windows of 8,192 steps."""

import numpy as np
import pytest

from benchmarks import flops_lfm2_moe as fl, harness, traffic

CONFIG = harness.load_json(f"{harness.HERE}/configs/lfm2-24b-a2b.json")
PARAMS = CONFIG["params"]
T, ROWS = 8192, 4


def test_the_parameter_counts_by_hand():
    d = 2048
    in_proj, taps, out_proj = d * 3 * d, 3 * d, d * d
    assert (in_proj, taps, out_proj) == (12_582_912, 6_144, 4_194_304)
    assert fl.conv_parameters(PARAMS) == in_proj + taps + out_proj == 16_783_360
    q, k, v, o = d * 32 * 64, d * 8 * 64, d * 8 * 64, 32 * 64 * d
    assert (q, k, v, o) == (4_194_304, 1_048_576, 1_048_576, 4_194_304)
    assert fl.attention_parameters(PARAMS) == q + k + v + o + 64 + 64 == 10_485_888
    mlp = 3 * d * 11776
    assert mlp == 72_351_744
    block = d * 64 + 64 + 8 * 3 * d * 1536  # router + bias, 8 held, no shared expert
    assert block == 131_136 + 75_497_472 == 75_628_608
    assert fl.layer_parameters(PARAMS, "conv", dense=True) == 16_783_360 + mlp + 2 * d == 89_139_200
    assert fl.layer_parameters(PARAMS, "full_attention", dense=False) == 86_118_592
    assert fl.layer_parameters(PARAMS, "conv", dense=False) == 92_416_064
    layers = 89_139_200 + 86_118_592 + 3 * 92_416_064
    assert layers == 452_505_984
    other = 64 * d + d + d + d * 8 + 8 + d + 1  # projection, last norm, the two heads
    assert other == 133_120 + 2_048 + 18_441
    total = layers + other
    assert total == 452_659_593 and total * 16 / 1e9 == pytest.approx(7.24, abs=0.01)
    assert total * 16 / 2**30 == pytest.approx(6.75, abs=0.01)
    assert 4 * 8 * 3 * d * 1536 / total == pytest.approx(0.67, abs=0.005)  # held experts


def test_the_projections_by_hand():
    d = 2048
    assert fl.conv_forward_per_token(PARAMS) == 2 * (d * 3 * d + d * d) == 33_554_432
    assert 2 * d * 3 * d == pytest.approx(25.2e6, rel=0.002) and 2 * d * d == pytest.approx(8.4e6, rel=0.002)
    assert fl.attention_projections_per_token(PARAMS) == 2 * (2 * d * 2048 + 2 * d * 512) == 20_971_520
    macs = (64 * d + 4 * (d * 3 * d + d * d) + (2 * d * 2048 + 2 * d * 512) + 3 * d * 11776
            + 4 * d * 64 + d * (8 + 1))
    assert fl.dense_forward_per_token(PARAMS) == 2 * macs == 301_240_320
    assert 2 * 3 * d * 11776 == pytest.approx(144.7e6, rel=0.001)  # the dense MLP, once
    assert 2 * d * 64 == 262_144  # a router


def test_attention_at_the_counted_pairs():
    """A kept pair costs QK^T and PV over 64 features in each of 32 query
    heads: 8,192 operations; the 8 key/value heads are read unrepeated."""
    assert fl.attention_forward_per_pair(PARAMS) == 2 * 32 * (64 + 64) == 8_192
    whole = int(np.arange(1, T + 1).sum())  # a seamless window's causal triangle
    ops, nbytes = fl.attention_train(PARAMS, ROWS, ROWS * whole)
    assert ops == 3 * ROWS * whole * 8_192
    # q, o twice, do, dq: six arrays of 32 x 64; k, v twice, dk, dv: six of 8 x 64; bf16; one layer
    assert nbytes == ROWS * T * 6 * (2048 + 512) * 2
    assert ops / 197e12 > 10 * nbytes / 819e9  # compute bounds it by far


def test_the_gates_and_taps_are_bytes():
    """Per token and convolution layer: the forward and its rematerialised
    twin read b, c, x~ and write the gated sum, the backward reads the three
    and the sum's gradient and writes three gradients: fifteen arrays of 2,048
    in bf16; the taps are 3 x 2,048 floats a layer."""
    nbytes = fl.gate_train(PARAMS, ROWS)
    per_token = 15 * 2048 * 2
    assert per_token == 61_440
    assert nbytes == 4 * (ROWS * T * per_token + 4 * 3 * 2048 * 4)
    assert nbytes / 819e9 == pytest.approx(9.8e-3, rel=0.01)  # the least an update could take
    assert fl.gate_train(PARAMS, 2 * ROWS) == pytest.approx(2 * nbytes, rel=1e-4)
    other = {**PARAMS, "compute_dtype": "float32"}
    assert fl.gate_train(other, ROWS) == pytest.approx(2 * nbytes, rel=1e-4)


def test_the_traffics_seams_leave_about_1600_keys_a_query():
    """``traffic.firsts`` at the mix's mean episode length, many windows: the
    pairs the attention layer keeps a token, and the shares of a token's
    forward operations the cell's ``why`` and the issue state (a count, not a
    time)."""
    mix = harness.load_json(f"{harness.HERE}/traffic/learner-packed.json")["windows"]
    assert mix["episode_len_mean"] == 2048
    rng = np.random.default_rng(5)
    kept = 0
    for _ in range(400):
        fir = traffic.firsts(rng, T, mix["episode_len_mean"]) > 0
        t = np.arange(T)
        kept += (t - np.maximum.accumulate(np.where(fir | (t == 0), t, 0)) + 1).sum()
    per_query = kept / 400 / T
    assert per_query == pytest.approx(1600, rel=0.1)  # same-episode keys behind a query
    # at 1,600 kept keys a query
    kernel = 1600 * 8_192
    held = 4 * 4 * 8 / 64 * fl.routed_forward_per_row(PARAMS)  # 0.5 held assignments a layer
    total = fl.dense_forward_per_token(PARAMS) + kernel + held
    in_the_layers = total - 2 * (64 * 2048 + 2048 * 9)  # without the projection and the heads
    assert kernel == 13_107_200 and held == 37_748_736
    assert in_the_layers == 351_797_248 and total == 352_096_256  # 351.7-352 MFLOP a token
    assert 4 * 33_554_432 / total == pytest.approx(0.38, abs=0.005)  # the four convolution mixers
    assert (20_971_520 + kernel) / total == pytest.approx(0.10, abs=0.005)  # the attention layer
    assert (held + 4 * 262_144) / total == pytest.approx(0.11, abs=0.005)  # experts and routers
    assert 2 * 3 * 2048 * 11776 / total == pytest.approx(0.41, abs=0.005)  # the one dense MLP


def test_swiglu_experts_at_the_counted_rows():
    row = 2 * 3 * 2048 * 1536  # W_out (silu(W_gate h) * W_in h): three products
    assert fl.routed_forward_per_row(PARAMS) == row == 18_874_368
    routed = 4 * ROWS * T * 4 / 8  # four expert layers, an eighth of the assignments each
    assert routed == 65_536 and routed / (4 * 8) == 2_048  # rows a held expert, by count
    assert 8 * T * 4 / 64 == 4_096  # and in the eight-rank deployment at a window a rank
    ops, nbytes = fl.gmm_train(PARAMS, routed)
    assert ops == 3 * routed * row
    assert nbytes == 3 * 2 * (routed * (2 * 2048 + 4 * 1536) + 4 * 8 * 3 * 2048 * 1536)
    assert ops / 197e12 > nbytes / 819e9  # 2,048 rows an expert: the products bound it
    assert fl.gmm_train(PARAMS, 0)[0] == 0  # no row routed here: only the weights' bytes
    pairs = ROWS * T * 1600.0
    assert fl.update(PARAMS, ROWS, pairs, routed) == pytest.approx(
        3 * (ROWS * T * 301_240_320 + pairs * 8_192 + routed * row))
    assert fl.update(PARAMS, ROWS, pairs, routed) == pytest.approx(34.6e12, rel=0.02)
    assert (fl.update(PARAMS, ROWS, pairs, 2 * routed) - fl.update(PARAMS, ROWS, pairs, routed)
            == 3 * routed * row)


def test_counted_reads_the_mean_of_the_lines_that_carry_the_key():
    rows = [harness.Seen(0.0, {"idx": 0, "ts": 0.0}),
            harness.Seen(1.0, {"idx": 2, "ts": 1.0, "attn-pairs-global": 10.0}),
            harness.Seen(2.0, {"idx": 4, "ts": 2.0, "attn-pairs-global": 20.0})]
    assert fl.counted(rows, "attn-pairs-global") == 15.0 and fl.counted(rows, "moe-rows") is None
