"""Operations and bytes an update of the granite_hybrid family needs, from
shapes alone (``params["arch"]``: the model's published ``config.json`` keys).

Same rules as ``benchmarks/flops.py``: multiply-adds of the forward pass (2
per MAC) and twice that for the backward pass; nothing recomputed (every layer
is rematerialised: its second forward is not counted), no elementwise work, no
optimizer. Causal attention is charged half of the T x T product.

The scan is counted as the published chunked algorithm (SSD, arXiv:2405.21060,
listing 1) computes it, per chunk of Q steps: ``C B^T`` (Q x Q per group), the
masked ``(C B^T * L) X`` (whole Q x Q blocks: the mask is elementwise), each
chunk's end state ``B^T (decay * X)``, the state's contribution ``C h`` to
every step, and the recurrence over chunks; plus the depthwise convolution.
Bytes are what that algorithm must move with the decay matrices kept on the
chip: the convolution reads and writes ``xBC``; the scan reads ``x, B, C``
(compute dtype) and ``dt`` (float32), writes ``y`` and writes and reads each
chunk's float32 state. The backward pass is charged twice the forward's
operations and bytes.
"""

from __future__ import annotations

import math

TRAIN_OVER_FORWARD = 3  # forward + backward (2x forward)


def _shapes(arch: dict) -> dict:
    h, p = arch["mamba_n_heads"], arch["mamba_d_head"]
    g, n = arch["mamba_n_groups"], arch["mamba_d_state"]
    return dict(
        d=arch["hidden_size"], h=h, p=p, g=g, n=n, inner=h * p,
        conv_ch=h * p + 2 * g * n, K=arch["mamba_d_conv"], Q=arch["mamba_chunk_size"],
        q_width=arch["hidden_size"],  # query heads x head size
        kv_width=arch["num_key_value_heads"]
        * (arch["hidden_size"] // arch["num_attention_heads"]),
        mlp=arch["intermediate_size"],
        n_mamba=arch["layer_types"].count("mamba"),
        n_attn=arch["layer_types"].count("attention"),
    )


def dense_forward_per_token(params: dict) -> float:
    """Every projection one token passes through: the observation projection,
    per Mamba layer in_proj and out_proj, per attention layer q, k, v and o,
    per layer the gated MLP, and the two heads."""
    s = _shapes(params["arch"])
    obs = math.prod(params.get("obs_shape", [4]))
    heads = params.get("action_space", 2) + 1
    mamba = s["d"] * (s["inner"] + s["conv_ch"] + s["h"]) + s["inner"] * s["d"]
    attn = 2 * s["d"] * s["q_width"] + 2 * s["d"] * s["kv_width"]
    mlp = s["d"] * 2 * s["mlp"] + s["mlp"] * s["d"]
    layers = s["n_mamba"] * (mamba + mlp) + s["n_attn"] * (attn + mlp)
    return 2.0 * (obs * s["d"] + layers + s["d"] * heads)


def attention_forward_per_sequence(params: dict) -> float:
    """QK^T and PV of every attention layer, causal: 2 matmuls x 2 T^2 d / 2."""
    s, T = _shapes(params["arch"]), params["seq_len"]
    return s["n_attn"] * 2.0 * T * T * s["q_width"]


def ssd_forward_per_token(params: dict) -> float:
    """Scan and convolution of every Mamba layer, per token (see above)."""
    s = _shapes(params["arch"])
    cb = 2.0 * s["Q"] * s["n"] * s["g"]
    y_diag = 2.0 * s["Q"] * s["p"] * s["h"]
    states = 2.0 * s["n"] * s["p"] * s["h"]
    y_state = 2.0 * s["n"] * s["p"] * s["h"]
    across = 2.0 * s["h"] * s["p"] * s["n"] / s["Q"]
    conv = 2.0 * s["K"] * s["conv_ch"]
    return s["n_mamba"] * (cb + y_diag + states + y_state + across + conv)


def ssd_forward_bytes_per_token(params: dict) -> float:
    s = _shapes(params["arch"])
    w = 2 if params.get("compute_dtype") == "bfloat16" else 4
    conv = 2 * s["conv_ch"] * w
    scan = (s["inner"] + 2 * s["g"] * s["n"]) * w + s["h"] * 4 + s["inner"] * w
    states = 2 * s["h"] * s["p"] * s["n"] * 4 / s["Q"]
    return s["n_mamba"] * float(conv + scan + states)


def ssd_train(params: dict, rows: int) -> tuple[float, float]:
    """(operations, HBM bytes) the scans and convolutions of one update of
    ``rows`` windows need, forward and backward."""
    tokens = rows * params["seq_len"]
    return (
        TRAIN_OVER_FORWARD * tokens * ssd_forward_per_token(params),
        TRAIN_OVER_FORWARD * tokens * ssd_forward_bytes_per_token(params),
    )


def update(params: dict, rows: int) -> float:
    """Operations one update of ``rows`` windows needs, forward and backward."""
    T = params["seq_len"]
    per_token = dense_forward_per_token(params) + ssd_forward_per_token(params)
    return TRAIN_OVER_FORWARD * rows * (
        T * per_token + attention_forward_per_sequence(params)
    )
