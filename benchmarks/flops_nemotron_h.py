"""Operations and bytes an update of the nemotron_h family needs, from shapes
(``params["arch"]``: the model's published ``config.json`` keys) and from the
**counted** routed rows: how many rows the held experts compute depends on the
router, so the program counts them (``diag``: ``moe-rows``, summed over the
expert layers of one update) and the readers hand the count in.

Same rules as ``benchmarks/flops.py`` and ``flops_granite_hybrid.py``:
multiply-adds of the forward pass (2 per MAC) and twice that for the backward
pass; nothing recomputed (every layer is rematerialised: its second forward is
not counted), no elementwise work, no optimizer, no sort or gather. Causal
attention is charged half of the T x T product. The Mamba-2 scan and its
convolution are counted as ``flops_granite_hybrid`` counts them, at this
model's widths (8 B/C groups, chunks of 128).

A routed expert is two products per row, ``d x f`` and ``f x d``. The grouped
products' bytes, per expert layer and pass: each row read (``d``) and written
(``d``) once, its hidden activations (``f``) written and read once, and every
held expert's two matrices read once, all in the compute dtype. The backward
pass is charged twice the forward's operations and bytes.
"""

from __future__ import annotations

import math

from benchmarks import flops_granite_hybrid

TRAIN_OVER_FORWARD = 3  # forward + backward (2x forward)


def _granite_keys(params: dict) -> dict:
    """This model's scan widths under the names ``flops_granite_hybrid``'s
    scan functions read (they read nothing else of ``arch``)."""
    a = params["arch"]
    pattern = a["hybrid_override_pattern"]
    arch = {
        "hidden_size": a["hidden_size"], "mamba_n_heads": a["mamba_num_heads"],
        "mamba_d_head": a["mamba_head_dim"], "mamba_n_groups": a["n_groups"],
        "mamba_d_state": a["ssm_state_size"], "mamba_d_conv": a["conv_kernel"],
        "mamba_chunk_size": a["chunk_size"], "num_key_value_heads": a["num_key_value_heads"],
        "num_attention_heads": a["num_attention_heads"], "intermediate_size": 0,
        "layer_types": ["mamba"] * pattern.count("M") + ["attention"] * pattern.count("*"),
    }
    return {**params, "arch": arch}


def widths(params: dict) -> dict:
    a = params["arch"]
    inner = a["mamba_num_heads"] * a["mamba_head_dim"]
    pattern = a["hybrid_override_pattern"]
    return dict(
        d=a["hidden_size"], inner=inner,
        in_proj=2 * inner + 2 * a["n_groups"] * a["ssm_state_size"] + a["mamba_num_heads"],
        q=a["num_attention_heads"] * a["head_dim"], kv=a["num_key_value_heads"] * a["head_dim"],
        f=a["moe_intermediate_size"], shared=a["moe_shared_expert_intermediate_size"],
        routed=a.get("expert_parallel", {}).get("published_n_routed_experts", a["n_routed_experts"]),
        held=a["n_routed_experts"],
        n_mamba=pattern.count("M"), n_attn=pattern.count("*"), n_experts=pattern.count("E"),
    )


def dense_forward_per_token(params: dict) -> float:
    """Every projection each token passes through: the observation projection,
    per Mamba layer in_proj and out_proj, per attention layer q, k, v and o,
    per expert layer the router and the shared expert, and the two heads."""
    w = widths(params)
    obs = math.prod(params.get("obs_shape", [4]))
    heads = params.get("action_space", 2) + 1
    mamba = w["d"] * w["in_proj"] + w["inner"] * w["d"]
    attn = 2 * w["d"] * w["q"] + 2 * w["d"] * w["kv"]
    experts = w["d"] * w["routed"] + 2 * w["d"] * w["shared"]
    return 2.0 * (obs * w["d"] + w["n_mamba"] * mamba + w["n_attn"] * attn
                  + w["n_experts"] * experts + w["d"] * heads)


def attention_forward_per_sequence(params: dict) -> float:
    """QK^T and PV of every attention layer, causal: 2 matmuls x 2 T^2 q / 2."""
    w, T = widths(params), params["seq_len"]
    return w["n_attn"] * 2.0 * T * T * w["q"]


def attention_train(params: dict, rows: int) -> tuple[float, float]:
    """(operations, HBM bytes) attention needs for one update of ``rows``
    sequences, forward and backward. Bytes as ``flops.attention_train`` counts
    them, at grouped widths: the forward reads q, k, v and writes o; the
    backward reads q, k, v, o, do and writes dq, dk, dv: six arrays of the
    query width and six of the key/value width per sequence and layer."""
    w, T = widths(params), params["seq_len"]
    width = 2 if params.get("compute_dtype") == "bfloat16" else 4
    ops = TRAIN_OVER_FORWARD * rows * attention_forward_per_sequence(params)
    return ops, 6.0 * rows * w["n_attn"] * T * (w["q"] + w["kv"]) * width


def ssd_train(params: dict, rows: int) -> tuple[float, float]:
    """(operations, HBM bytes) of the scans and convolutions of one update:
    ``flops_granite_hybrid.ssd_train`` at this model's widths."""
    return flops_granite_hybrid.ssd_train(_granite_keys(params), rows)


def routed_forward_per_row(params: dict) -> float:
    w = widths(params)
    return 2.0 * 2 * w["d"] * w["f"]


def gmm_train(params: dict, routed_rows: float) -> tuple[float, float]:
    """(operations, HBM bytes) of the grouped products of one update whose
    expert layers computed ``routed_rows`` rows in all, forward and backward."""
    w = widths(params)
    width = 2 if params.get("compute_dtype") == "bfloat16" else 4
    rows = 2 * w["d"] + 2 * w["f"]
    weights = w["n_experts"] * w["held"] * 2 * w["d"] * w["f"]
    return (
        TRAIN_OVER_FORWARD * routed_rows * routed_forward_per_row(params),
        TRAIN_OVER_FORWARD * float(routed_rows * rows + weights) * width,
    )


def update(params: dict, rows: int, routed_rows: float) -> float:
    """Operations one update of ``rows`` windows needs, forward and backward,
    with ``routed_rows`` rows computed by the held experts of all its layers."""
    T = params["seq_len"]
    per_token = dense_forward_per_token(params) + flops_granite_hybrid.ssd_forward_per_token(
        _granite_keys(params))
    return TRAIN_OVER_FORWARD * (
        rows * (T * per_token + attention_forward_per_sequence(params))
        + routed_rows * routed_forward_per_row(params)
    )
