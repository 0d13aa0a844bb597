"""Operations and bytes an update of the qwen3_next family needs, from shapes
(``params["arch"]``: the model's published ``config.json`` keys) and from two
things the program **counts** because the data decides them (``diag``, per
update): the query-key pairs the full-attention layers' masks keep
(``attn-pairs-global``, summed over those layers — an episode seam cuts
pairs) and the rows the held experts compute (``moe-rows``, summed over the
layers). The readers hand the counts in.

Same rules as ``benchmarks/flops.py`` and its siblings: multiply-adds of the
forward pass (2 per MAC) and twice that for the backward pass; nothing
recomputed (every layer is rematerialised, and the scan's spans once more:
neither second forward is counted), no elementwise work (the L2 norms, the
rotation, the softmaxes, the gates' products), no optimizer, no sort or gather.

The delta-rule scan is counted as the published chunked algorithm (Gated
DeltaNet, arXiv:2412.06464, section 3.3) computes it, per chunk of ``Q``
steps: per **key** head ``K K^T`` and ``Q K^T`` (``Q x Q x d_k`` each; the
value heads of a key head share them); per **value** head the inverse of the
unit lower triangle by forward substitution (``Q^3 / 6`` multiply-adds: row
``i`` costs ``i^2 / 2``), ``U = A (beta V)`` and ``W = A (beta K)`` (whole
``Q x Q`` blocks: the triangle is a mask), ``W S``, ``Q S``, ``(Q K^T) V'``
and the state's update ``K^T V'``. A program that inverts the triangle by
repeated squaring (eleven whole products) gets nothing for the extra. Its
bytes are what that algorithm must move with the chunk's matrices kept on the
chip: it reads q, k, v (compute dtype) and the two gates (float32), writes o
(compute dtype), and writes and reads each chunk's float32 state. The
convolution before it (``gdn_conv``) is counted apart: ``K`` taps a channel,
its input read and its output written.

Attention: QK^T and PV, 2 x 2 x (query heads x head size) operations per kept
pair; bytes as ``flops_smallthinker.attention_train`` counts them. A routed
expert is gated: three products per row; bytes as
``flops_smallthinker.gmm_train`` counts them. The backward pass is charged
twice the forward's operations and bytes.
"""

from __future__ import annotations

import math

from benchmarks.flops_smallthinker import counted  # noqa: F401 — the readers' helper

TRAIN_OVER_FORWARD = 3  # forward + backward (2x forward)
CHUNK = 64  # the family's convention (tpu_rl/models/qwen3_next.py), not a config key


def widths(params: dict) -> dict:
    a = params["arch"]
    kinds = [(i + 1) % a["full_attention_interval"] == 0 for i in range(a["num_hidden_layers"])]
    hk, hv = a["linear_num_key_heads"], a["linear_num_value_heads"]
    dk, dv = a["linear_key_head_dim"], a["linear_value_head_dim"]
    return dict(
        d=a["hidden_size"], layers=len(kinds), n_full=sum(kinds), n_linear=len(kinds) - sum(kinds),
        hk=hk, hv=hv, dk=dk, dv=dv, keys=hk * dk, values=hv * dv,
        conv_ch=2 * hk * dk + hv * dv, K=a["linear_conv_kernel_dim"],
        q=a["num_attention_heads"] * a["head_dim"], kv=a["num_key_value_heads"] * a["head_dim"],
        f=a["moe_intermediate_size"], shared=a["shared_expert_intermediate_size"],
        held=a["num_experts"],
        routed=a.get("expert_parallel", {}).get("published_n_routed_experts", a["num_experts"]),
    )


def _width(params: dict) -> int:
    return 2 if params.get("compute_dtype") == "bfloat16" else 4


def dense_forward_per_token(params: dict) -> float:
    """Every projection each token passes through: the observation projection;
    per linear layer ``in_proj_qkvz``, ``in_proj_ba`` and ``out_proj``; per
    full layer q (with its gate), k, v and o; per layer the router, the shared
    expert's three products and its gate; and the two heads."""
    w = widths(params)
    obs = math.prod(params.get("obs_shape", [4]))
    heads = params.get("action_space", 2) + 1
    linear = w["d"] * (w["conv_ch"] + w["values"] + 2 * w["hv"]) + w["values"] * w["d"]
    full = w["d"] * 2 * w["q"] + 2 * w["d"] * w["kv"] + w["q"] * w["d"]
    experts = w["d"] * w["routed"] + 3 * w["d"] * w["shared"] + w["d"]
    return 2.0 * (obs * w["d"] + w["n_linear"] * linear + w["n_full"] * full
                  + w["layers"] * experts + w["d"] * heads)


def gdn_forward_per_token(params: dict) -> float:
    """One linear layer's scan, per token (see above)."""
    w, Q = widths(params), CHUNK
    per_key_head = 2 * Q * w["dk"]  # K K^T, Q K^T
    per_value_head = (
        Q * Q / 6  # the triangle's inverse
        + Q * (w["dv"] + w["dk"])  # U, W
        + 3 * w["dk"] * w["dv"]  # W S, Q S, K^T V'
        + Q * w["dv"]  # (Q K^T) V'
    )
    return 2.0 * (w["hk"] * per_key_head + w["hv"] * per_value_head)


def gdn_forward_bytes_per_token(params: dict) -> float:
    w, Q, width = widths(params), CHUNK, _width(params)
    streams = (2 * w["keys"] + 2 * w["values"]) * width + 2 * w["hv"] * 4  # q k v o; g, beta
    states = 2 * w["hv"] * w["dk"] * w["dv"] * 4 / Q
    return float(streams + states)


def conv_forward_per_token(params: dict) -> float:
    w = widths(params)
    return 2.0 * w["K"] * w["conv_ch"]


def gdn_train(params: dict, rows: int) -> tuple[float, float]:
    """(operations, HBM bytes) the scans of one update of ``rows`` windows
    need, forward and backward, over all linear layers (no convolution)."""
    tokens = rows * params["seq_len"] * widths(params)["n_linear"]
    return (
        TRAIN_OVER_FORWARD * tokens * gdn_forward_per_token(params),
        TRAIN_OVER_FORWARD * tokens * gdn_forward_bytes_per_token(params),
    )


def attention_forward_per_pair(params: dict) -> float:
    """QK^T and PV for one kept query-key pair, over every query head."""
    return 2.0 * 2 * widths(params)["q"]


def attention_train(params: dict, rows: int, pairs: float) -> tuple[float, float]:
    """(operations, HBM bytes) attention needs for one update of ``rows``
    sequences whose masks kept ``pairs`` query-key pairs over the full layers:
    the forward reads q, k, v and writes o; the backward reads q, k, v, o, do
    and writes dq, dk, dv."""
    w, T = widths(params), params["seq_len"]
    ops = TRAIN_OVER_FORWARD * pairs * attention_forward_per_pair(params)
    return ops, 6.0 * rows * w["n_full"] * T * (w["q"] + w["kv"]) * _width(params)


def routed_forward_per_row(params: dict) -> float:
    w = widths(params)
    return 2.0 * 3 * w["d"] * w["f"]


def gmm_train(params: dict, routed_rows: float) -> tuple[float, float]:
    """(operations, HBM bytes) of the grouped products of one update whose
    layers computed ``routed_rows`` rows in all, forward and backward: each
    row read and written once (``d``), the two first products' activations
    (``f`` each) written and read once, every held expert's three matrices
    read once."""
    w = widths(params)
    per_row = 2 * w["d"] + 4 * w["f"]
    weights = w["layers"] * w["held"] * 3 * w["d"] * w["f"]
    return (
        TRAIN_OVER_FORWARD * routed_rows * routed_forward_per_row(params),
        TRAIN_OVER_FORWARD * float(routed_rows * per_row + weights) * _width(params),
    )


def update(params: dict, rows: int, pairs: float, routed_rows: float) -> float:
    """Operations one update of ``rows`` windows needs, forward and backward,
    at ``pairs`` kept query-key pairs and ``routed_rows`` computed rows."""
    w = widths(params)
    tokens = rows * params["seq_len"]
    per_token = dense_forward_per_token(params) + w["n_linear"] * (
        gdn_forward_per_token(params) + conv_forward_per_token(params))
    return TRAIN_OVER_FORWARD * (
        tokens * per_token
        + pairs * attention_forward_per_pair(params)
        + routed_rows * routed_forward_per_row(params)
    )
