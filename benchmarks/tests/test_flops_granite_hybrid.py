"""``flops_granite_hybrid`` against counts made by hand at the published
widths of granite-4.0-h-micro, layers 0-9."""

import json

import pytest

from benchmarks import flops, flops_granite_hybrid as fg, harness

CONFIG = harness.load_json(f"{harness.HERE}/configs/granite-4.0-h-micro.json")
PARAMS = CONFIG["params"]


def test_dense_layers_by_hand():
    d = 2048
    mamba = d * (4096 + 4352 + 64) + 4096 * d  # in_proj (z, xBC, dt), out_proj MACs
    assert mamba == d * 8512 + 4096 * d
    attention = d * 2048 + 2 * d * 512 + 2048 * d  # q, k and v (8 heads of 64), o
    mlp = d * 16384 + 8192 * d  # [a, b] = W_in u; W_out (silu(a) * b)
    macs = 64 * d + 9 * (mamba + mlp) + 1 * (attention + mlp) + d * (8 + 1)
    assert fg.dense_forward_per_token(PARAMS) == 2 * macs == 1_492_684_800


def test_attention_by_hand():
    # one attention layer, 32 query heads of 64; causal: half of two T x T x d products
    assert fg.attention_forward_per_sequence(PARAMS) == 2 * 4096 * 4096 * 2048


def test_scan_operations_and_bytes_by_hand():
    Q, h, p, n = 256, 64, 64, 128
    per_layer = (
        2 * Q * n          # C B^T: each step against the Q steps of its chunk, one group
        + 2 * Q * p * h    # (C B^T * L) X: whole Q x Q blocks, every head
        + 2 * n * p * h    # the chunk's end state: B^T (decay * X)
        + 2 * n * p * h    # the entering state's part of y: C h
        + 2 * h * p * n / Q  # the recurrence over chunks, once per chunk
        + 2 * 4 * 4352     # the depthwise convolution, kernel 4
    )
    assert fg.ssd_forward_per_token(PARAMS) == 9 * per_layer
    assert per_layer == pytest.approx(4.298e6, rel=1e-3)
    conv = 2 * 4352 * 2               # xBC read and written, bf16
    scan = (4096 + 256) * 2 + 64 * 4 + 4096 * 2  # x, B, C bf16; dt f32; y bf16
    states = 2 * h * p * n * 4 / Q    # each chunk's f32 state written and read
    assert fg.ssd_forward_bytes_per_token(PARAMS) == 9 * (conv + scan + states)
    ops, nbytes = fg.ssd_train(PARAMS, 2)
    assert ops == 3 * 8192 * 9 * per_layer
    # on a v5e: 4.8 ms of matmuls against 13.8 ms of HBM traffic -> memory-bound
    peak = flops.peaks("TPU v5 lite")
    assert ops / peak["bf16_flops_per_s"] == pytest.approx(4.83e-3, rel=1e-2)
    assert nbytes / peak["hbm_bytes_per_s"] == pytest.approx(13.76e-3, rel=1e-2)


def test_an_update_by_hand():
    per_token = 1_492_684_800 + fg.ssd_forward_per_token(PARAMS)
    fwd = 2 * (4096 * per_token + 2 * 4096 * 4096 * 2048)
    assert fg.update(PARAMS, 2) == 3 * fwd
    assert fg.update(PARAMS, 2) == pytest.approx(38.05e12, rel=1e-3)
    assert fg.update(PARAMS, 2) / 8192 == pytest.approx(4.644e9, rel=1e-3)


def test_the_accepted_readers_find_nothing_to_price_here():
    """``step.mfu`` would price this model as an LSTM and ``attn_flash_roofline``
    multiplies by ``n_layers``: without those keys both raise, ``run.py``
    leaves them out, and ``BENCHMARK.json`` lists them for the cells they read."""
    assert not {"hidden_size", "n_layers", "n_heads"} & set(PARAMS)
    with pytest.raises(KeyError):
        flops.update(PARAMS, 2)
    with pytest.raises(KeyError):
        flops.attention_train(PARAMS, 2)
    bench = harness.load_json(f"{harness.ROOT}/BENCHMARK.json")
    for m in bench["per_layer"]:
        if m["name"] in ("step.mfu", "attn_flash_roofline"):
            assert "granite-4.0-h-micro.learner" not in m["workloads"]


def test_the_file_holds_the_published_config():
    """Every key of the catalog's entry under the same name at the file's top
    level and in ``params.arch``, but for what ``reduced`` lists."""
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "granite-4.0-h-micro"]
    published = row["config"]
    assert CONFIG["reduced"] == ["layer_types", "vocab_size"]
    for where in (CONFIG, PARAMS["arch"]):
        for key, value in published.items():
            if key == "layer_types":
                assert where[key] == value[:10] and value[:10].count("attention") == 1
            elif key == "vocab_size":
                assert key not in where
            else:
                assert where[key] == value, key
    assert len(CONFIG["source"]) <= 200 and "layers 0-9" in CONFIG["source"]
    bench = harness.load_json(f"{harness.ROOT}/BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "granite-4.0-h-micro"]
    assert entry["source"] == row["source_url"] and entry["reduced"] == CONFIG["reduced"]
