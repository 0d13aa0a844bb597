"""Operations and bytes an update of the lfm2_moe family needs, from shapes
(``params["arch"]``: the model's published ``config.json`` keys) and from two
things the program **counts** because the data decides them (``diag``, per
update): the query-key pairs the attention layers' masks keep
(``attn-pairs-global``, summed over those layers — an episode seam cuts
pairs) and the rows the held experts compute (``moe-rows``, summed over the
expert layers). The readers hand the counts in. The convolution does no
data-dependent work: its part follows from shapes alone.

Same rules as ``benchmarks/flops.py`` and its siblings: multiply-adds of the
forward pass (2 per MAC) and twice that for the backward pass; nothing
recomputed (every layer is rematerialised: its second forward is not counted),
no elementwise work (the norms, the rotation, the softmaxes, the gates'
products, **the convolution's gates and taps**), no optimizer, no sort or
gather.

A convolution mixer's operations are its two projections (``hidden x 3
hidden`` and ``hidden x hidden``); what lies between them — ``b * x~``, the
``conv_L_cache`` taps a channel and ``c * h`` — is bytes, not operations, and
``gate_train`` counts what one fused pass over it must move: the forward
reads ``b``, ``c``, ``x~`` and writes the gated sum; the rematerialised second
forward the same (the layer is rematerialised and the gate's float32 products
are not kept, so the recomputation is part of the algorithm as the memory
budget forces it — it is charged here, and only here, because this is a
roofline of bytes and not a count of useful operations); the backward reads
the three and the gated sum's gradient and writes three gradients and the
taps'. A kernel that moved less would read above what it is credited for; one
that kept the float32 products would move more.

Attention: QK^T and PV, 2 x 2 x (query heads x head size) operations per kept
pair; bytes as ``flops_qwen3_next.attention_train`` counts them (grouped
key/value heads unrepeated). A routed expert is gated: three products per
row; bytes as ``flops_smallthinker.gmm_train`` counts them, over the expert
layers alone. The dense layers' MLP is three products per token. The backward
pass is charged twice the forward's operations and bytes.
"""

from __future__ import annotations

import math

from benchmarks.flops_smallthinker import counted  # noqa: F401 — the readers' helper

TRAIN_OVER_FORWARD = 3  # forward + backward (2x forward)


def widths(params: dict) -> dict:
    a = params["arch"]
    kinds = a["layer_types"]
    head = a["hidden_size"] // a["num_attention_heads"]
    dense = a["num_dense_layers"]
    return dict(
        d=a["hidden_size"], layers=len(kinds), n_conv=kinds.count("conv"),
        n_attn=kinds.count("full_attention"), n_dense=dense, n_expert=len(kinds) - dense,
        K=a["conv_L_cache"], head=head, q=a["num_attention_heads"] * head,
        kv=a["num_key_value_heads"] * head, mlp=a["intermediate_size"],
        f=a["moe_intermediate_size"], held=a["num_experts"],
        routed=a.get("expert_parallel", {}).get("published_n_routed_experts", a["num_experts"]),
    )


def _width(params: dict) -> int:
    return 2 if params.get("compute_dtype") == "bfloat16" else 4


def conv_parameters(params: dict) -> int:
    """One short-convolution mixer: ``in_proj``, the taps, ``out_proj``."""
    w = widths(params)
    return w["d"] * 3 * w["d"] + w["K"] * w["d"] + w["d"] * w["d"]


def attention_parameters(params: dict) -> int:
    """One attention mixer: four projections and the two per-head norms."""
    w = widths(params)
    return 2 * w["d"] * w["q"] + 2 * w["d"] * w["kv"] + 2 * w["head"]


def layer_parameters(params: dict, kind: str, dense: bool) -> int:
    """A layer: its mixer, its MLP or expert block (router, expert bias, held
    experts; no shared expert), two norms."""
    w = widths(params)
    mixer = conv_parameters(params) if kind == "conv" else attention_parameters(params)
    if dense:
        block = 3 * w["d"] * w["mlp"]
    else:
        block = w["d"] * w["routed"] + w["routed"] + w["held"] * 3 * w["d"] * w["f"]
    return mixer + block + 2 * w["d"]


def conv_forward_per_token(params: dict) -> float:
    """One convolution mixer's two projections."""
    w = widths(params)
    return 2.0 * (w["d"] * 3 * w["d"] + w["d"] * w["d"])


def attention_projections_per_token(params: dict) -> float:
    w = widths(params)
    return 2.0 * (2 * w["d"] * w["q"] + 2 * w["d"] * w["kv"])


def dense_forward_per_token(params: dict) -> float:
    """Every projection each token passes through: the observation projection;
    per convolution layer its two, per attention layer its four; per dense
    layer the MLP's three products; per expert layer the router; and the two
    heads."""
    w = widths(params)
    obs = math.prod(params.get("obs_shape", [4]))
    heads = params.get("action_space", 2) + 1
    return (
        2.0 * (obs * w["d"] + w["n_dense"] * 3 * w["d"] * w["mlp"]
               + w["n_expert"] * w["d"] * w["routed"] + w["d"] * heads)
        + w["n_conv"] * conv_forward_per_token(params)
        + w["n_attn"] * attention_projections_per_token(params)
    )


def attention_forward_per_pair(params: dict) -> float:
    """QK^T and PV for one kept query-key pair, over every query head."""
    return 2.0 * 2 * widths(params)["q"]


def attention_train(params: dict, rows: int, pairs: float) -> tuple[float, float]:
    """(operations, HBM bytes) attention needs for one update of ``rows``
    sequences whose masks kept ``pairs`` query-key pairs over the attention
    layers: the forward reads q, k, v and writes o; the backward reads q, k,
    v, o, do and writes dq, dk, dv."""
    w, T = widths(params), params["seq_len"]
    ops = TRAIN_OVER_FORWARD * pairs * attention_forward_per_pair(params)
    return ops, 6.0 * rows * w["n_attn"] * T * (w["q"] + w["kv"]) * _width(params)


def gate_train(params: dict, rows: int) -> float:
    """HBM bytes one fused pass over the convolution mixers' gates and taps
    must move for one update of ``rows`` sequences: per token and layer the
    forward and its rematerialised twin read b, c, x~ and write the gated sum
    (4 arrays of ``hidden`` each, compute dtype), the backward reads the three
    and the sum's gradient and writes three gradients (7); per layer the taps
    read thrice and their float32 gradient written once."""
    w, T = widths(params), params["seq_len"]
    per_token = (4 + 4 + 7) * w["d"] * _width(params)
    return float(w["n_conv"] * (rows * T * per_token + 4 * w["K"] * w["d"] * 4))


def routed_forward_per_row(params: dict) -> float:
    w = widths(params)
    return 2.0 * 3 * w["d"] * w["f"]


def gmm_train(params: dict, routed_rows: float) -> tuple[float, float]:
    """(operations, HBM bytes) of the grouped products of one update whose
    expert layers computed ``routed_rows`` rows in all, forward and backward:
    each row read and written once (``d``), the two first products'
    activations (``f`` each) written and read once, every held expert's three
    matrices read once."""
    w = widths(params)
    per_row = 2 * w["d"] + 4 * w["f"]
    weights = w["n_expert"] * w["held"] * 3 * w["d"] * w["f"]
    return (
        TRAIN_OVER_FORWARD * routed_rows * routed_forward_per_row(params),
        TRAIN_OVER_FORWARD * float(routed_rows * per_row + weights) * _width(params),
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
