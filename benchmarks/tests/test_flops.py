"""The shape functions against counts made by hand."""

import pytest

from benchmarks import flops, harness

TF = dict(model="transformer", hidden_size=512, n_heads=8, n_layers=4, seq_len=2048,
          obs_shape=[64], action_space=8, compute_dtype="bfloat16")
LSTM = dict(hidden_size=64, seq_len=5, obs_shape=[4], action_space=2)


def test_transformer_by_hand():
    d = 512
    per_layer = 3 * d * d + d * d + d * 4 * d + 4 * d * d  # qkv, out, ff1, ff2 MACs
    assert per_layer == 12 * d * d
    macs = 64 * d + 4 * per_layer + d * (8 + 1)
    assert flops.transformer_forward_per_token(TF) == 2 * macs == 25_240_576
    # causal attention: two T x T x d products, half of each needed
    assert flops.attention_forward_per_sequence(TF) == 4 * 2 * 2048 * 2048 * 512
    fwd = 32 * (2048 * 25_240_576 + 4 * 2 * 2048 * 2048 * 512)
    assert flops.update(TF, 32) == 3 * fwd
    assert flops.update(TF, 32) == pytest.approx(6.61e12, rel=1e-3)


def test_attention_operations_and_bytes_by_hand():
    ops, nbytes = flops.attention_train(TF, 32)
    assert ops == 3 * 32 * 4 * 2 * 2048 * 2048 * 512
    assert nbytes == 12 * 32 * 4 * 2048 * 512 * 2  # twelve (T, d) bf16 arrays
    # on a v5e: 8.4 ms of matmuls against 3.9 ms of HBM traffic -> compute-bound
    peak = flops.peaks("TPU v5 lite")
    assert ops / peak["bf16_flops_per_s"] == pytest.approx(8.37e-3, rel=1e-2)
    assert nbytes / peak["hbm_bytes_per_s"] == pytest.approx(3.93e-3, rel=1e-2)


def test_lstm_by_hand():
    macs = 4 * 64 + 64 * 256 + 64 * 256 + 64 * (2 + 1)
    assert flops.lstm_forward_per_step(LSTM) == 2 * macs == 66_432
    assert flops.update(LSTM, 128) == 3 * 128 * 5 * 66_432
    # the colocated program also acts once for every step it trains on
    assert flops.update(LSTM, 128, acts_in_program=True) == 4 * 128 * 5 * 66_432
    assert flops.lstm_cell_train(LSTM, 128) == 3 * 128 * 5 * 2 * 64 * 256


def test_an_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")
    with pytest.raises(KeyError):
        flops.peaks("source")


def test_config_files_carry_what_the_functions_read():
    for name in ("tf-longctx", "lstm-ref"):
        params = harness.load_json(f"{harness.HERE}/configs/{name}.json")["params"]
        assert flops.update(params, params["batch_size"]) > 0
