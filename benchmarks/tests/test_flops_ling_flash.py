"""``flops_ling_flash`` against counts made by hand at the published widths of
Ling-3.0-flash-VL, published layers 1-7 (six Kimi-Delta-Attention layers, one
latent-attention layer; a dense layer, then six expert layers), one rank of
sixty-four, at the cell's batch of 2 windows of 8,192 steps."""

import numpy as np
import pytest

from benchmarks import flops_ling_flash as fl, harness, traffic

CONFIG = harness.load_json(f"{harness.HERE}/configs/ling-3.0-flash-vl.json")
PARAMS = CONFIG["params"]
T = 8192


def test_the_widths_are_the_cuts():
    w = fl.widths(PARAMS)
    assert (w["n_kda"], w["n_latent"], w["n_dense"], w["n_expert"]) == (6, 1, 1, 6)
    assert (w["heads"], w["dk"], w["qk"], w["v"], w["conv_ch"]) == (32, 128, 192, 128, 12288)
    assert (w["held"], w["routed"], w["f"], w["shared"], w["mlp"]) == (8, 512, 768, 768, 6144)
    from tpu_rl.models import ling_flash
    from tpu_rl.ops import kda
    assert (fl.CHUNK, fl.SUB) == (ling_flash.CHUNK, kda.SUB)


def test_dense_layers_by_hand():
    d = 2560
    kda = d * 12288 + d * 4096 + d * 64 + 4096 * d  # in_proj_qkv, a_proj, in_proj_bz, o_proj
    latent = d * 32 * 192 + d * 576 + 512 * 32 * 256 + d * 32 + 4096 * d
    experts = d * 512 + 3 * d * 768  # the router over all 512, the shared expert
    mlp = 3 * d * 6144
    assert (kda, latent, experts, mlp) == (52_592_640, 31_965_184, 7_208_960, 47_185_920)
    macs = 64 * d + 6 * kda + latent + mlp + 6 * experts + d * (8 + 1)
    assert fl.dense_forward_per_token(PARAMS) == 2 * macs == 876_295_168
    # the issue counted ~922 MFLOP a token and the KDA mixers' projections at 68% of it; by
    # this file's count a token's forward is 951 MFLOP and they are 66%
    assert 6 * 2 * kda / 951e6 == pytest.approx(0.66, abs=0.01)


def test_the_scan_by_hand():
    """Per head and chunk of 64 steps in sub-blocks of 16: P and R as ten
    16 x 16 x 128 block products each, the inverse by forward substitution
    (64^3 / 6), U and W (64 x 64 x 128 each), W S, Q+ S and K^T Δ
    (64 x 128 x 128 each) and tril(R) Δ (64 x 64 x 128)."""
    Q, s, dk, dv = 64, 16, 128, 128
    per_chunk = 32 * (2 * 10 * s * s * dk + Q**3 / 6 + 2 * Q * Q * dk + 3 * Q * dk * dv + Q * Q * dv)
    assert fl.kda_forward_per_token(PARAMS) == pytest.approx(2 * per_chunk / Q)
    assert fl.kda_forward_per_token(PARAMS) == pytest.approx(5.42e6, rel=0.01)
    # q, k, v, o (32 x 128 each) in bf16, the decay a head and key channel and beta a head in
    # float32, and a float32 state of 32 x 128 x 128 written and read once every 64 steps
    nbytes = 4 * 4096 * 2 + (4096 + 32) * 4 + 2 * 32 * 128 * 128 * 4 / 64
    assert fl.kda_forward_bytes_per_token(PARAMS) == nbytes == 114_816
    assert 2 * T * 4096 * 4 == 268_435_456  # the decay alone, a layer and update
    ops, total_bytes = fl.kda_train(PARAMS, 2)
    assert ops == pytest.approx(6 * 3 * 2 * T * 2 * per_chunk / Q)
    assert total_bytes == 6 * 3 * 2 * T * 114_816
    assert total_bytes / 819e9 > ops / 197e12  # the memory bounds it: 41 ms against 8
    assert total_bytes / 819e9 == pytest.approx(41.3e-3, rel=0.01)
    assert fl.conv_forward_per_token(PARAMS) == 2 * 4 * 12288


def test_attention_at_the_counted_pairs_and_the_models_sizes():
    whole = int(np.arange(1, T + 1).sum())  # a seamless window's causal triangle
    assert fl.attention_forward_per_pair(PARAMS) == 32 * 640  # 2 x (192 + 128) a head
    ops, nbytes = fl.attention_train(PARAMS, 2, 2 * whole)
    assert ops == 3 * 2 * whole * 32 * 640
    # forward: q, k (192) and v in, o (128) out; backward: q, k, v, o, do in, dq, dk, dv out
    per_token = 32 * ((192 + 192 + 128 + 128) + (192 + 192 + 128 + 128 + 128) + (192 + 192 + 128))
    assert nbytes == 2 * 1 * T * per_token * 2 and per_token == 32 * (6 * 192 + 6 * 128)
    assert ops / 197e12 > nbytes / 819e9


def test_the_traffics_seams_and_the_shares_of_a_tokens_operations():
    """``traffic.firsts`` at the mix's mean episode length, many windows: the
    pairs the latent layer keeps a token, and the shares of a token's forward
    operations the cell's ``why`` and the issue state (a count, not a time)."""
    mix = harness.load_json(f"{harness.HERE}/traffic/learner-packed.json")["windows"]
    rng = np.random.default_rng(5)
    kept = 0
    for _ in range(400):
        fir = traffic.firsts(rng, T, mix["episode_len_mean"]) > 0
        t = np.arange(T)
        kept += (t - np.maximum.accumulate(np.where(fir | (t == 0), t, 0)) + 1).sum()
    per_query = kept / 400 / T
    assert per_query == pytest.approx(1600, rel=0.08)  # same-episode keys behind a query
    attention = per_query * 32 * 640
    scan = fl.kda_forward_per_token(PARAMS) + fl.conv_forward_per_token(PARAMS)
    held = 6 * 8 * 8 / 512 * fl.routed_forward_per_row(PARAMS)  # a fair router's rows a token
    total = fl.dense_forward_per_token(PARAMS) + 6 * scan + attention + held
    assert 6 * (2 * 52_592_640 + scan) / total == pytest.approx(0.70, abs=0.01)  # the KDA mixers (the issue: 72%)
    assert 6 * scan / total == pytest.approx(0.035, abs=0.01)
    assert 2 * 47_185_920 / total == pytest.approx(0.10, abs=0.01)  # the dense MLP
    assert (6 * 2 * 7_208_960 + held) / total == pytest.approx(0.10, abs=0.01)
    assert (2 * 31_965_184 + attention) / total == pytest.approx(0.10, abs=0.015)
    assert total == pytest.approx(950e6, rel=0.03)


def test_swiglu_experts_at_the_counted_rows():
    row = 2 * 3 * 2560 * 768  # W_out (silu(W_gate h) * W_in h): three products
    assert fl.routed_forward_per_row(PARAMS) == row == 11_796_480
    routed = 6 * 16384 * 8 / 64  # six expert layers, a sixty-fourth of the assignments each
    assert routed == 12_288 and routed / (6 * 8) == 256  # rows a held expert, by count
    assert 64 * 16384 * 8 / 512 == 16_384  # and in the sixty-four-rank deployment
    ops, nbytes = fl.gmm_train(PARAMS, routed)
    assert ops == 3 * routed * row
    assert nbytes == 3 * 2 * (routed * (2 * 2560 + 4 * 768) + 6 * 8 * 3 * 2560 * 768)
    assert nbytes / 819e9 > ops / 197e12  # 256 rows an expert: its weights' bytes bound it
    assert fl.gmm_train(PARAMS, 0)[0] == 0
    pairs = 2 * 13e6
    per_token = 876_295_168 + 6 * (fl.kda_forward_per_token(PARAMS) + 98_304)
    assert fl.update(PARAMS, 2, pairs, routed) == pytest.approx(
        3 * (16384 * per_token + pairs * 32 * 640 + routed * row))
    assert fl.update(PARAMS, 2, pairs, routed) == pytest.approx(46.7e12, rel=0.02)
    assert fl.update(PARAMS, 2, pairs, 2 * routed) - fl.update(PARAMS, 2, pairs, routed) == 3 * routed * row
