"""``flops_qwen3_next`` against counts made by hand at the published widths of
Qwen3-Next-80B-A3B-Instruct, layers 0-3 (three Gated-DeltaNet linear-attention
layers, one gated full-attention layer), one rank of sixteen, at the cell's
batch of 2 windows of 8,192 steps."""

import numpy as np
import pytest

from benchmarks import flops_qwen3_next as fq, harness, traffic

CONFIG = harness.load_json(f"{harness.HERE}/configs/qwen3-next-80b-a3b.json")
PARAMS = CONFIG["params"]
T = 8192


def test_dense_layers_by_hand():
    d = 2048
    linear = d * 12288 + d * 64 + 4096 * d  # in_proj_qkvz, in_proj_ba, out_proj
    full = d * 8192 + 2 * d * 512 + 4096 * d  # q with its gate (16 heads of 2 x 256), k, v, o
    experts = d * 512 + 3 * d * 512 + d  # the router over all 512, the shared expert, its gate
    assert (linear, full, experts) == (33_685_504, 27_262_976, 4_196_352)
    macs = 64 * d + 3 * linear + full + 4 * experts + d * (8 + 1)
    assert fq.dense_forward_per_token(PARAMS) == 2 * macs == 290_508_800
    # the issue's shares: ~67 MFLOP a token of projections a linear layer, ~55 the full layer
    assert 2 * linear == pytest.approx(67e6, rel=0.01) and 2 * full == pytest.approx(55e6, rel=0.01)


def test_the_scan_by_hand():
    """Per chunk of 64 steps: a key head's K K^T and Q K^T (64 x 64 x 128
    each); a value head's inverse by forward substitution (64^3 / 6), U and W
    (64 x 64 x 128 each), W S, Q S and K^T V' (64 x 128 x 128 each) and
    (Q K^T) V' (64 x 64 x 128)."""
    Q, dk, dv = 64, 128, 128
    per_chunk = 16 * 2 * Q * Q * dk + 32 * (Q**3 / 6 + 2 * Q * Q * dk + 3 * Q * dk * dv + Q * Q * dv)
    assert fq.gdn_forward_per_token(PARAMS) == pytest.approx(2 * per_chunk / Q)
    assert fq.gdn_forward_per_token(PARAMS) == pytest.approx(5.33e6, rel=0.01)  # the issue's ~5
    # q, k (16 x 128), v, o (32 x 128) in bf16, two float32 gates a value head, and a
    # float32 state of 32 x 128 x 128 written and read once every 64 steps
    nbytes = (2 * 2048 + 2 * 4096) * 2 + 2 * 32 * 4 + 2 * 32 * 128 * 128 * 4 / 64
    assert fq.gdn_forward_bytes_per_token(PARAMS) == nbytes == 90_368
    ops, total_bytes = fq.gdn_train(PARAMS, 2)
    assert ops == pytest.approx(3 * 3 * 2 * T * 2 * per_chunk / Q)
    assert total_bytes == 3 * 3 * 2 * T * 90_368
    assert total_bytes / 819e9 > ops / 197e12  # the memory bounds it: 16.3 ms against 4.0
    assert total_bytes / 819e9 == pytest.approx(16.3e-3, rel=0.01)
    assert fq.conv_forward_per_token(PARAMS) == 2 * 4 * 8192


def test_attention_at_the_counted_pairs():
    whole = int(np.arange(1, T + 1).sum())  # a seamless window's causal triangle
    assert whole == 33_558_528 and fq.attention_forward_per_pair(PARAMS) == 4 * 4096
    ops, nbytes = fq.attention_train(PARAMS, 2, 2 * whole)
    assert ops == 3 * 2 * whole * 4 * 4096
    # q, o, do, dq and their forward twins at 16 x 256; k, v, dk, dv and theirs at 2 x 256
    assert nbytes == 2 * 1 * T * (6 * 4096 + 6 * 512) * 2
    assert ops / 197e12 > nbytes / 819e9
    assert whole / T * 4 * 4096 == pytest.approx(67e6, rel=0.01)  # a token's, with no seam


def test_the_traffics_seams_leave_about_a_third_of_the_triangle():
    """``traffic.firsts`` at the mix's mean episode length, many windows: the
    pairs a full layer keeps a token, and the shares of a token's forward
    operations the cell's ``why`` and the issue state (a count, not a time)."""
    mix = harness.load_json(f"{harness.HERE}/traffic/learner-packed.json")["windows"]
    assert mix["episode_len_mean"] == 2048
    rng = np.random.default_rng(5)
    kept = 0
    for _ in range(400):
        fir = traffic.firsts(rng, T, mix["episode_len_mean"]) > 0
        t = np.arange(T)
        kept += (t - np.maximum.accumulate(np.where(fir | (t == 0), t, 0)) + 1).sum()
    per_query = kept / 400 / T
    assert per_query == pytest.approx(1600, rel=0.08)  # same-episode keys behind a query
    attention = per_query * 4 * 4096
    linear = 3 * (2 * 33_685_504 + fq.gdn_forward_per_token(PARAMS) + fq.conv_forward_per_token(PARAMS))
    held = 4 * 10 * 32 / 512 * fq.routed_forward_per_row(PARAMS)
    total = fq.dense_forward_per_token(PARAMS) + 3 * (
        fq.gdn_forward_per_token(PARAMS) + fq.conv_forward_per_token(PARAMS)) + attention + held
    assert linear / total == pytest.approx(0.64, abs=0.02)  # the delta-rule layers' mixers
    assert (2 * 27_262_976 + attention) / total == pytest.approx(0.24, abs=0.02)
    assert (4 * 2 * 4_196_352 + held) / total == pytest.approx(0.14, abs=0.02)
    assert 3 * total == pytest.approx(1.04e9, rel=0.03)  # forward + backward, a token


def test_swiglu_experts_at_the_counted_rows():
    row = 2 * 3 * 2048 * 512  # W_out (silu(W_gate h) * W_in h): three products
    assert fq.routed_forward_per_row(PARAMS) == row == 6_291_456
    routed = 4 * 16384 * 10 / 16  # four layers, a sixteenth of the assignments each
    assert routed == 40_960 and routed / (4 * 32) == 320  # rows a held expert, by count
    assert 16 * 16384 * 10 / 512 == 5_120  # and in the sixteen-rank deployment
    ops, nbytes = fq.gmm_train(PARAMS, routed)
    assert ops == 3 * routed * row
    assert nbytes == 3 * 2 * (routed * (2 * 2048 + 4 * 512) + 4 * 32 * 3 * 2048 * 512)
    assert nbytes / 819e9 > ops / 197e12  # 320 rows an expert: its weights' bytes bound it
    assert fq.gmm_train(PARAMS, 0)[0] == 0  # no row routed here: only the weights' bytes
    pairs = 2 * 13e6
    per_token = 290_508_800 + 3 * (fq.gdn_forward_per_token(PARAMS) + 65_536)
    assert fq.update(PARAMS, 2, pairs, routed) == pytest.approx(
        3 * (16384 * per_token + pairs * 4 * 4096 + routed * row))
    assert fq.update(PARAMS, 2, pairs, routed) == pytest.approx(17.1e12, rel=0.02)
    assert fq.update(PARAMS, 2, pairs, 2 * routed) - fq.update(PARAMS, 2, pairs, routed) == 3 * routed * row


def test_counted_reads_the_mean_of_the_lines_that_carry_the_key():
    rows = [harness.Seen(0.0, {"idx": 0, "ts": 0.0}),
            harness.Seen(1.0, {"idx": 2, "ts": 1.0, "attn-pairs-global": 10.0}),
            harness.Seen(2.0, {"idx": 4, "ts": 2.0, "attn-pairs-global": 20.0})]
    assert fq.counted(rows, "attn-pairs-global") == 15.0 and fq.counted(rows, "moe-rows") is None
