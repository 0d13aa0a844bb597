"""Operations and bytes an update of the ling_flash family needs, from shapes
(``params["arch"]``: the model's published ``config.json`` keys) and from two
things the program **counts** because the data decides them (``diag``, per
update): the query-key pairs the latent layers' masks keep
(``attn-pairs-global`` — an episode seam cuts pairs) and the rows the held
experts compute (``moe-rows``, summed over the expert layers; under the
group-limited router a token whose kept groups hold no held expert sends
none). The readers hand the counts in.

Same rules as ``benchmarks/flops.py`` and its siblings: multiply-adds of the
forward pass (2 per MAC) and twice that for the backward pass; nothing
recomputed (every layer is rematerialised, and the scan's spans once more:
neither second forward is counted), no elementwise work (the L2 norms, the
gates' exponentials and sigmoids, the decay factors, the rotation, the
softmaxes), no optimizer, no sort or gather, and nothing for padding: latent
attention is charged the model's 640 operations a pair and head (192-wide
scores, 128-wide values) whatever head size the kernels run.

The per-channel delta-rule scan (Kimi Delta Attention, arXiv:2510.26692) is
counted as the chunked algorithm of ``tpu_rl/ops/kda.py``'s header computes it,
per head and chunk of ``Q`` = 64 steps in sub-blocks of ``SUB`` = 16: the pair
terms ``P`` and ``R`` as ten ``SUB x SUB x d_k`` block products each (the four
diagonal sub-blocks and the six pairs under them — the factored decay is only
finite sub-block by sub-block, and what lies above the diagonal is never
needed); the inverse of the unit lower triangle by forward substitution
(``Q^3 / 6`` multiply-adds); ``U = A (beta V)`` and ``W = A (beta K+)`` (whole
``Q x Q`` blocks: the triangle is a mask); ``W S``, ``Q+ S``, ``tril(R) Δ`` and
the state's update. A program that inverts the triangle by repeated squaring
gets nothing for the extra. Its bytes are what that algorithm must move with a
chunk's matrices kept on the chip: it reads q, k, v (compute dtype), the decay
``g`` (float32, one number a head, step and key channel: 268 MB a layer and
update at the cell's shapes) and ``beta`` (float32), writes o (compute dtype),
and writes and reads each chunk's float32 state. The convolution before it
(``kda_conv``) is counted apart: ``K`` taps a channel.

A routed expert is gated: three products per row; bytes as
``flops_smallthinker.gmm_train`` counts them. The backward pass is charged
twice the forward's operations and bytes.
"""

from __future__ import annotations

import math

from benchmarks.flops_smallthinker import counted  # noqa: F401 — the readers' helper

TRAIN_OVER_FORWARD = 3  # forward + backward (2x forward)
CHUNK = 64  # the family's convention (tpu_rl/models/ling_flash.py), not a config key
SUB = 16  # tpu_rl/ops/kda.py: the steps whose pair decays share a reference step


def widths(params: dict) -> dict:
    a = params["arch"]
    depth, offset = a["num_hidden_layers"], a.get("layer_offset", 0)
    latent = [(j + offset + 1) % a["layer_group_size"] == 0 for j in range(depth)]
    heads, size = a["num_attention_heads"], a["head_dim"]
    return dict(
        d=a["hidden_size"], layers=depth, n_latent=sum(latent), n_kda=depth - sum(latent),
        n_dense=a["first_k_dense_replace"], n_expert=depth - a["first_k_dense_replace"],
        heads=heads, dk=size, dv=size, keys=heads * size, conv_ch=3 * heads * size,
        K=a["short_conv_kernel_size"], qk=a["qk_nope_head_dim"] + a["qk_rope_head_dim"],
        rope=a["qk_rope_head_dim"], nope=a["qk_nope_head_dim"], v=a["v_head_dim"],
        rank=a["kv_lora_rank"], mlp=a["intermediate_size"], f=a["moe_intermediate_size"],
        shared=a["moe_shared_expert_intermediate_size"], held=a["num_experts"],
        routed=a.get("expert_parallel", {}).get("published_n_routed_experts", a["num_experts"]),
    )


def _width(params: dict) -> int:
    return 2 if params.get("compute_dtype") == "bfloat16" else 4


def dense_forward_per_token(params: dict) -> float:
    """Every projection each token passes through: the observation projection;
    per KDA layer ``in_proj_qkv``, the decay's full-rank ``a_proj``,
    ``in_proj_bz`` and ``o_proj``; per latent layer ``q_proj``, ``kv_a_proj``,
    ``kv_b_proj``, the head gate and ``o_proj``; the dense MLP's three; per
    expert layer the router and the shared expert's three; and the two heads."""
    w = widths(params)
    obs = math.prod(params.get("obs_shape", [4]))
    heads = params.get("action_space", 2) + 1
    kda = w["d"] * (w["conv_ch"] + w["keys"] + 2 * w["heads"]) + w["keys"] * w["d"]
    latent = (
        w["d"] * w["heads"] * w["qk"] + w["d"] * (w["rank"] + w["rope"])
        + w["rank"] * w["heads"] * (w["nope"] + w["v"]) + w["d"] * w["heads"]
        + w["heads"] * w["v"] * w["d"]
    )
    experts = w["d"] * w["routed"] + 3 * w["d"] * w["shared"]
    return 2.0 * (obs * w["d"] + w["n_kda"] * kda + w["n_latent"] * latent
                  + w["n_dense"] * 3 * w["d"] * w["mlp"] + w["n_expert"] * experts
                  + w["d"] * heads)


def kda_forward_per_token(params: dict) -> float:
    """One KDA layer's scan, per token (see above)."""
    w, Q = widths(params), CHUNK
    blocks = (Q // SUB) * (Q // SUB + 1) // 2  # sub-block pairs on and under the diagonal: 10
    per_head = (
        2 * blocks * SUB * SUB * w["dk"] / Q  # P and R
        + Q * Q / 6  # the triangle's inverse
        + Q * (w["dv"] + w["dk"])  # U, W
        + 3 * w["dk"] * w["dv"]  # W S, Q+ S, K^T Δ
        + Q * w["dv"]  # tril(R) Δ
    )
    return 2.0 * w["heads"] * per_head


def kda_forward_bytes_per_token(params: dict) -> float:
    w, Q, width = widths(params), CHUNK, _width(params)
    streams = 4 * w["keys"] * width + (w["keys"] + w["heads"]) * 4  # q k v o; g, beta
    states = 2 * w["heads"] * w["dk"] * w["dv"] * 4 / Q
    return float(streams + states)


def conv_forward_per_token(params: dict) -> float:
    w = widths(params)
    return 2.0 * w["K"] * w["conv_ch"]


def kda_train(params: dict, rows: int) -> tuple[float, float]:
    """(operations, HBM bytes) the scans of one update of ``rows`` windows
    need, forward and backward, over all KDA layers (no convolution)."""
    tokens = rows * params["seq_len"] * widths(params)["n_kda"]
    return (
        TRAIN_OVER_FORWARD * tokens * kda_forward_per_token(params),
        TRAIN_OVER_FORWARD * tokens * kda_forward_bytes_per_token(params),
    )


def attention_forward_per_pair(params: dict) -> float:
    """QK^T over the query/key size and PV over the value size for one kept
    query-key pair, over every head: 2 x (192 + 128) = 640 a head."""
    w = widths(params)
    return 2.0 * w["heads"] * (w["qk"] + w["v"])


def attention_train(params: dict, rows: int, pairs: float) -> tuple[float, float]:
    """(operations, HBM bytes) attention needs for one update of ``rows``
    sequences whose masks kept ``pairs`` query-key pairs over the latent
    layers: the forward reads q, k, v and writes o; the backward reads q, k, v,
    o, do and writes dq, dk, dv — q and k ``qk`` wide, v, o and do ``v`` wide."""
    w, T = widths(params), params["seq_len"]
    ops = TRAIN_OVER_FORWARD * pairs * attention_forward_per_pair(params)
    per_token = w["heads"] * (6 * w["qk"] + 6 * w["v"])
    return ops, float(rows * w["n_latent"] * T * per_token * _width(params))


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
    w = widths(params)
    tokens = rows * params["seq_len"]
    per_token = dense_forward_per_token(params) + w["n_kda"] * (
        kda_forward_per_token(params) + conv_forward_per_token(params))
    return TRAIN_OVER_FORWARD * (
        tokens * per_token
        + pairs * attention_forward_per_pair(params)
        + routed_rows * routed_forward_per_row(params)
    )
