"""``benchmarks/flops_evabyte.py`` against sums by hand at the published
widths: a layer's parameters and the whole stage's, the operations a kept pair
costs, the pairs a query keeps with no seam, the shares of a token's
operations, the bytes of the two rooflines."""

import pytest

from benchmarks import flops_evabyte, harness

CONFIG = harness.load_json(f"{harness.HERE}/configs/evabyte.json")
PARAMS = CONFIG["params"]


def test_parameters_by_hand():
    assert flops_evabyte.layer_parameters(PARAMS) == (
        4 * 4096 * 4096 + 2 * 32 * 128 + 3 * 4096 * 11008 + 2 * 4096) == 202_391_552
    assert flops_evabyte.parameters(PARAMS) == (
        4 * 202_391_552 + 65 * 4096 + 4096 + 4097 * 9) == 809_873_417
    assert 4 * 202_391_552 == 809_566_208 and 65 * 4096 == 266_240 and 4097 * 9 == 36_873


def test_a_kept_pair_costs_16384_operations_and_a_query_keeps_1472_5_of_them():
    assert flops_evabyte.attention_forward_per_pair(PARAMS) == 16_384
    exact, summary = flops_evabyte.pairs_without_a_seam(PARAMS)
    assert (exact, summary) == (1024.5, 448.0) and exact + summary == 1472.5
    assert summary / (exact + summary) == pytest.approx(0.304, abs=1e-3)
    # a causal mask over the whole window would keep 8,192.5: EVA keeps 18% of it
    assert 1472.5 / 8192.5 == pytest.approx(0.18, abs=2e-3)
    # at most T / C = 1,024 complete chunks in a window: the candidates' static bound
    assert PARAMS["seq_len"] // PARAMS["arch"]["chunk_size"] == 1024


def test_the_shares_of_a_tokens_operations():
    per_layer_proj = 2 * 4 * 4096 * 4096
    per_layer_mlp = 2 * 3 * 4096 * 11008
    assert per_layer_proj == 134_217_728 and per_layer_mlp == 270_532_608
    dense = flops_evabyte.dense_forward_per_token(PARAMS)
    assert dense == 4 * (per_layer_proj + per_layer_mlp) + 2 * (64 * 4096 + 4096 * 9)
    pairs = 4 * 1300 * 16_384.0  # ~1,300 kept pairs a query and layer at the cell's mix
    total = dense + pairs
    assert 4 * per_layer_mlp / total == pytest.approx(0.64, abs=0.01)
    assert 4 * per_layer_proj / total == pytest.approx(0.315, abs=0.01)
    assert pairs / total == pytest.approx(0.05, abs=0.005)
    # one update trained: 16,384 tokens x 1.70 GFLOP x 3
    ops = flops_evabyte.update(PARAMS, 1, 4 * 1300 * 16_384.0)
    assert ops == pytest.approx(83.8e12, rel=0.01)
    assert ops == 3 * (16_384 * dense + 4 * 1300 * 16_384.0 * 16_384)


def test_the_two_rooflines_bytes():
    # a (T, hidden) bf16 array is 134 MB; the read moves 12 + 6/16 of them a layer
    array = 16_384 * 4096 * 2
    ops, nbytes = flops_evabyte.attention_train(PARAMS, 1, 1e6)
    assert ops == 3 * 1e6 * 16_384 and nbytes == 4 * (12 + 6 / 16) * array
    assert flops_evabyte.pool_train(PARAMS, 1) == 4 * (2 * (2 + 2 / 16) + 4 + 2 / 16) * array
    assert flops_evabyte.pool_train(PARAMS, 2) == 2 * flops_evabyte.pool_train(PARAMS, 1)
    assert flops_evabyte.pool_train({**PARAMS, "compute_dtype": "float32"}, 1) == (
        2 * flops_evabyte.pool_train(PARAMS, 1))


def test_the_counters_helper():
    rows = [harness.Seen(0.0, {"attn-pairs-block": 10.0, "attn-pairs-summary": 2.0}),
            harness.Seen(1.0, {"attn-pairs-block": 14.0, "attn-pairs-summary": 6.0})]
    assert flops_evabyte.counted_pairs(rows) == (12.0, 4.0)
    assert flops_evabyte.counted_pairs([harness.Seen(0.0, {"idx": 1})]) is None
