"""``flops_nemotron_h`` against counts made by hand at the published widths of
NVIDIA-Nemotron-3-Nano-30B-A3B, layers 0-8 (4 Mamba-2, 4 expert, 1 attention
layer), one rank of sixteen."""

import pytest

from benchmarks import flops_nemotron_h as fn, harness

CONFIG = harness.load_json(f"{harness.HERE}/configs/nemotron-3-nano-30b-a3b.json")
PARAMS = CONFIG["params"]


def test_dense_layers_by_hand():
    d = 2688
    mamba = d * (4096 + 6144 + 64) + 4096 * d  # in_proj (z, xBC with 8 groups, dt), out_proj
    attention = d * 4096 + 2 * d * 256 + 4096 * d  # q (32 heads of 128), k and v (2 heads), o
    experts = d * 128 + d * 3712 + 3712 * d  # the router over all 128, the shared expert
    macs = 64 * d + 4 * mamba + attention + 4 * experts + d * (8 + 1)
    assert fn.dense_forward_per_token(PARAMS) == 2 * macs == 519_240_960
    # the issue's shares of the forward pass: projections 53%, shared experts 27%
    assert 2 * 4 * mamba / 582.7e6 == pytest.approx(0.53, abs=0.01)
    assert 2 * 4 * 2 * d * 3712 / 582.7e6 == pytest.approx(0.27, abs=0.01)


def test_attention_and_scan_by_hand():
    assert fn.attention_forward_per_sequence(PARAMS) == 2 * 4096 * 4096 * 4096
    Q, h, p, n, g = 128, 64, 64, 128, 8
    per_layer = (2 * Q * n * g + 2 * Q * p * h + 2 * 2 * n * p * h + 2 * h * p * n / Q
                 + 2 * 4 * 6144)
    scan = fn.flops_granite_hybrid.ssd_forward_per_token(fn._granite_keys(PARAMS))
    assert scan == 4 * per_layer == pytest.approx(13.86e6, rel=1e-3)


def test_the_kernels_rooflines_by_hand():
    """What ``moe_hybrid_attn_flash_roofline`` and ``moe_hybrid_ssd_scan_roofline``
    divide by the trace's time, at 4 windows of 4096 steps in bf16."""
    ops, nbytes = fn.attention_train(PARAMS, 4)
    assert ops == 3 * 4 * 2 * 4096 * 4096 * 4096
    # q, o, do, dq and their forward twins at 32 x 128; k, v, dk, dv and theirs at 2 x 128
    assert nbytes == 4 * 4096 * (6 * 4096 + 6 * 256) * 2
    ops, nbytes = fn.ssd_train(PARAMS, 4)
    assert ops == 3 * 16384 * 13_860_864
    conv, scan = 2 * 6144 * 2, (4096 + 2 * 8 * 128) * 2 + 64 * 4 + 4096 * 2
    states = 2 * 64 * 64 * 128 * 4 / 128
    assert nbytes == 3 * 16384 * 4 * (conv + scan + states)
    # both are shares of a v5e's peaks that the traced times (23.7, 64.0 ms) keep under 100%
    assert 3 * 4 * 2 * 4096**3 / 197e12 < 23.7e-3 and nbytes / 819e9 < 64.0e-3


def test_routed_experts_at_the_counted_rows():
    row = 2 * (2688 * 1856 + 1856 * 2688)  # relu(u W1)^2 W2: two products, no gate matrix
    assert fn.routed_forward_per_row(PARAMS) == row == 19_955_712
    routed = 4 * 16384 * 6 / 16  # four layers, a sixteenth of the assignments each
    ops, nbytes = fn.gmm_train(PARAMS, routed)
    assert ops == 3 * routed * row
    assert nbytes == 3 * 2 * (routed * (2 * 2688 + 2 * 1856) + 4 * 8 * 2 * 2688 * 1856)
    assert fn.gmm_train(PARAMS, 0)[0] == 0  # no row routed here: only the weights' bytes
    per_transition = (fn.dense_forward_per_token(PARAMS) + 13_860_864 + 2 * 4096 * 4096
                      + row * 6 * 4 / 16)
    assert fn.update(PARAMS, 4, routed) == pytest.approx(3 * 16384 * per_transition)
    assert per_transition == pytest.approx(596.6e6, rel=1e-3)  # 582.7e6 without the scan
    # the held routed experts are a twentieth of the work, as the cell's why says
    assert row * 6 * 4 / 16 / per_transition == pytest.approx(0.05, abs=0.005)
    assert fn.update(PARAMS, 4, 2 * routed) - fn.update(PARAMS, 4, routed) == 3 * routed * row
