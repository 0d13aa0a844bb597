"""``flops_glm4_moe_lite`` against counts made by hand at the published widths
of GLM-4.7-Flash, layers 0-4 (one dense layer, four expert layers, latent
attention in each), one rank of eight, at the cell's batch of 1 window of
16,384 steps."""

import numpy as np
import pytest

from benchmarks import flops_glm4_moe_lite as fg, harness, traffic

CONFIG = harness.load_json(f"{harness.HERE}/configs/glm-4.7-flash.json")
PARAMS = CONFIG["params"]
T = 16384


def test_the_parameter_counts_by_hand():
    d = 2048
    q_a, q_b = d * 768, 768 * 20 * (192 + 64)
    kv_a, kv_b = d * (512 + 64), 512 * 20 * (192 + 256)
    o = 20 * 256 * d
    assert (q_a, q_b, kv_a, kv_b, o) == (1_572_864, 3_932_160, 1_179_648, 4_587_520, 10_485_760)
    attention = q_a + 768 + q_b + kv_a + 512 + kv_b + o
    assert fg.attention_parameters(PARAMS) == attention == 21_759_232
    mlp = 3 * d * 10240
    assert mlp == 62_914_560
    assert fg.layer_parameters(PARAMS, dense=True) == attention + mlp + 2 * d == 84_677_888
    block = d * 64 + 64 + 3 * d * 1536 + 8 * 3 * d * 1536  # router + bias, shared, 8 held
    assert block == 85_065_792
    assert fg.layer_parameters(PARAMS, dense=False) == attention + block + 2 * d == 106_829_120
    other = 64 * d + d + d + d * 8 + 8 + d + 1  # projection, last norm, the two heads
    total = 84_677_888 + 4 * 106_829_120 + other
    assert total == 512_147_977 and total * 16 / 1e9 == pytest.approx(8.19, abs=0.01)
    assert 4 * 8 * 3 * d * 1536 / total == pytest.approx(0.59, abs=0.005)  # held experts


def test_dense_layers_by_hand():
    d = 2048
    latent = 1_572_864 + 1_179_648 + 3_932_160 + 4_587_520 + 10_485_760  # q_a kv_a q_b kv_b o
    assert fg.latent_forward_per_token(PARAMS) == 2 * latent == 43_515_904
    experts = d * 64 + 3 * d * 1536  # the router over all 64, the shared expert
    macs = 64 * d + 5 * latent + 3 * d * 10240 + 4 * experts + d * (8 + 1)
    assert fg.dense_forward_per_token(PARAMS) == 2 * macs == 420_253_696
    assert 2 * 3 * d * 10240 == pytest.approx(125.8e6, rel=0.001)  # the dense MLP, once
    assert 2 * 3 * d * 1536 == pytest.approx(18.9e6, rel=0.002)  # the shared expert


def test_attention_at_the_counted_pairs():
    """A kept pair costs QK^T over 192 + 64 features and PV over 256, in each
    of 20 heads: 20,480 operations. Training in the absorbed form (576-wide
    keys, 512-wide values) would cost 2.1x and earns nothing for it."""
    assert fg.attention_forward_per_pair(PARAMS) == 2 * 20 * (256 + 256) == 20_480
    assert 2 * 20 * (576 + 512) / 20_480 == pytest.approx(2.1, abs=0.03)
    whole = int(np.arange(1, T + 1).sum())  # a seamless window's causal triangle
    ops, nbytes = fg.attention_train(PARAMS, 1, 5 * whole)
    assert ops == 3 * 5 * whole * 20_480
    # q, k, v, o, do, dq, dk, dv and the backward's second reading of q, k, v, o:
    # twelve arrays of 20 x 256 in bf16, per layer
    assert nbytes == 5 * T * 12 * 5120 * 2
    assert ops / 197e12 > 10 * nbytes / 819e9  # compute bounds it by far
    # ten times qwen3-next's K/V bytes a tile: 20 key/value heads against 2
    assert 20 * 256 / (2 * 256) == 10


def test_the_traffics_seams_leave_about_half_of_the_triangle():
    """``traffic.firsts`` at the mix's mean episode length, many windows: the
    pairs a layer keeps a token, and the shares of a token's forward
    operations the cell's ``why`` and the issue state (a count, not a time)."""
    mix = harness.load_json(f"{harness.HERE}/traffic/learner-long.json")["windows"]
    assert mix["episode_len_mean"] == 8192
    rng = np.random.default_rng(5)
    kept = 0
    for _ in range(200):
        fir = traffic.firsts(rng, T, mix["episode_len_mean"]) > 0
        t = np.arange(T)
        kept += (t - np.maximum.accumulate(np.where(fir | (t == 0), t, 0)) + 1).sum()
    per_query = kept / 200 / T
    assert per_query == pytest.approx(4600, rel=0.08)  # same-episode keys behind a query
    kernel = 5 * per_query * 20_480
    latent = 5 * 43_515_904
    held = 4 * 4 * 8 / 64 * fg.routed_forward_per_row(PARAMS)  # 0.5 held assignments a layer
    total = fg.dense_forward_per_token(PARAMS) + kernel + held
    assert total == pytest.approx(927e6, rel=0.06)
    assert kernel / total == pytest.approx(0.51, abs=0.04)
    assert latent / total == pytest.approx(0.23, abs=0.02)
    assert 2 * 3 * 2048 * 10240 / total == pytest.approx(0.14, abs=0.01)  # the dense MLP
    shared_and_router = 4 * 2 * (2048 * 64 + 3 * 2048 * 1536)
    assert (shared_and_router + held) / total == pytest.approx(0.12, abs=0.01)


def test_swiglu_experts_at_the_counted_rows():
    row = 2 * 3 * 2048 * 1536  # W_out (silu(W_gate h) * W_in h): three products
    assert fg.routed_forward_per_row(PARAMS) == row == 18_874_368
    routed = 4 * T * 4 / 8  # four expert layers, an eighth of the assignments each
    assert routed == 32_768 and routed / (4 * 8) == 1_024  # rows a held expert, by count
    assert 8 * T * 4 / 64 == 8_192  # and in the eight-rank deployment at a window a rank
    ops, nbytes = fg.gmm_train(PARAMS, routed)
    assert ops == 3 * routed * row
    assert nbytes == 3 * 2 * (routed * (2 * 2048 + 4 * 1536) + 4 * 8 * 3 * 2048 * 1536)
    assert ops / 197e12 > nbytes / 819e9  # 1,024 rows an expert: the products bound it
    assert fg.gmm_train(PARAMS, 0)[0] == 0  # no row routed here: only the weights' bytes
    pairs = 5 * 75e6
    assert fg.update(PARAMS, 1, pairs, routed) == pytest.approx(
        3 * (T * 420_253_696 + pairs * 20_480 + routed * row))
    assert fg.update(PARAMS, 1, pairs, routed) == pytest.approx(45.6e12, rel=0.02)
    assert fg.update(PARAMS, 1, pairs, 2 * routed) - fg.update(PARAMS, 1, pairs, routed) == 3 * routed * row


def test_counted_reads_the_mean_of_the_lines_that_carry_the_key():
    rows = [harness.Seen(0.0, {"idx": 0, "ts": 0.0}),
            harness.Seen(1.0, {"idx": 2, "ts": 1.0, "attn-pairs-global": 10.0}),
            harness.Seen(2.0, {"idx": 4, "ts": 2.0, "attn-pairs-global": 20.0})]
    assert fg.counted(rows, "attn-pairs-global") == 15.0 and fg.counted(rows, "moe-rows") is None
