"""Operations and bytes an update of the smallthinker family needs, from shapes
(``params["arch"]``: the model's published ``config.json`` keys) and from two
things the program **counts** because the data decides them (``diag``, per
update): the query-key pairs the attention masks keep (``attn-pairs-global``
and ``attn-pairs-window``, each summed over the layers of its kind — an
episode seam and the sliding window both cut pairs) and the rows the held
experts compute (``moe-rows``, summed over the layers). The readers hand the
counts in.

Same rules as ``benchmarks/flops.py``, ``flops_granite_hybrid.py`` and
``flops_nemotron_h.py``: multiply-adds of the forward pass (2 per MAC) and
twice that for the backward pass; nothing recomputed (every layer is
rematerialised: its second forward is not counted; a kernel that visits a
tile the mask empties gets nothing for it), no elementwise work (the rotation,
the softmaxes, the gate's product), no optimizer, no sort or gather.

Attention: QK^T and PV, 2 x 2 x (query heads x head size) operations per kept
pair. Its bytes as ``flops_nemotron_h.attention_train`` counts them: the
forward reads q, k, v and writes o; the backward reads q, k, v, o, do and
writes dq, dk, dv: six arrays of the query width and six of the key/value
width per sequence and layer.

A routed expert is gated: three products per row, ``d x f`` twice and
``f x d``. The grouped products' bytes, per layer and pass: each row read
(``d``) and written (``d``) once, the two first products' activations (``f``
each) written and read once, and every held expert's three matrices read
once, all in the compute dtype. The backward pass is charged twice the
forward's operations and bytes.
"""

from __future__ import annotations

import math

TRAIN_OVER_FORWARD = 3  # forward + backward (2x forward)


def widths(params: dict) -> dict:
    a = params["arch"]
    return dict(
        d=a["hidden_size"], layers=a["num_hidden_layers"],
        q=a["num_attention_heads"] * a["head_dim"], kv=a["num_key_value_heads"] * a["head_dim"],
        f=a["moe_ffn_hidden_size"], held=a["moe_num_primary_experts"],
        routed=a.get("expert_parallel", {}).get(
            "published_n_routed_experts", a["moe_num_primary_experts"]),
    )


def dense_forward_per_token(params: dict) -> float:
    """Every projection each token passes through: the observation projection,
    per layer q, k, v, o and the router, and the two heads."""
    w = widths(params)
    obs = math.prod(params.get("obs_shape", [4]))
    heads = params.get("action_space", 2) + 1
    layer = 2 * w["d"] * w["q"] + 2 * w["d"] * w["kv"] + w["d"] * w["routed"]
    return 2.0 * (obs * w["d"] + w["layers"] * layer + w["d"] * heads)


def attention_forward_per_pair(params: dict) -> float:
    """QK^T and PV for one kept query-key pair, over every query head."""
    return 2.0 * 2 * widths(params)["q"]


def attention_train(params: dict, rows: int, pairs: float) -> tuple[float, float]:
    """(operations, HBM bytes) attention needs for one update of ``rows``
    sequences whose masks kept ``pairs`` query-key pairs over all layers."""
    w, T = widths(params), params["seq_len"]
    width = 2 if params.get("compute_dtype") == "bfloat16" else 4
    ops = TRAIN_OVER_FORWARD * pairs * attention_forward_per_pair(params)
    return ops, 6.0 * rows * w["layers"] * T * (w["q"] + w["kv"]) * width


def routed_forward_per_row(params: dict) -> float:
    w = widths(params)
    return 2.0 * 3 * w["d"] * w["f"]


def gmm_train(params: dict, routed_rows: float) -> tuple[float, float]:
    """(operations, HBM bytes) of the grouped products of one update whose
    layers computed ``routed_rows`` rows in all, forward and backward."""
    w = widths(params)
    width = 2 if params.get("compute_dtype") == "bfloat16" else 4
    per_row = 2 * w["d"] + 4 * w["f"]
    weights = w["layers"] * w["held"] * 3 * w["d"] * w["f"]
    return (
        TRAIN_OVER_FORWARD * routed_rows * routed_forward_per_row(params),
        TRAIN_OVER_FORWARD * float(routed_rows * per_row + weights) * width,
    )


def update(params: dict, rows: int, pairs: float, routed_rows: float) -> float:
    """Operations one update of ``rows`` windows needs, forward and backward,
    at ``pairs`` kept query-key pairs and ``routed_rows`` computed rows."""
    tokens = rows * params["seq_len"]
    return TRAIN_OVER_FORWARD * (
        tokens * dense_forward_per_token(params)
        + pairs * attention_forward_per_pair(params)
        + routed_rows * routed_forward_per_row(params)
    )


def counted(window_rows, key: str) -> float | None:
    """The mean per update of one of the program's counters over the
    ``learn.jsonl`` lines of the window; None where no line carries it."""
    vals = [r.row[key] for r in window_rows if key in r.row]
    return sum(vals) / len(vals) if vals else None
