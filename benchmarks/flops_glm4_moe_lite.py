"""Operations and bytes an update of the glm4_moe_lite family needs, from
shapes (``params["arch"]``: the model's published ``config.json`` keys) and
from two things the program **counts** because the data decides them
(``diag``, per update): the query-key pairs the attention masks keep
(``attn-pairs-global``, summed over the layers — an episode seam cuts pairs)
and the rows the held experts compute (``moe-rows``, summed over the expert
layers). The readers hand the counts in.

Same rules as ``benchmarks/flops.py`` and its siblings: multiply-adds of the
forward pass (2 per MAC) and twice that for the backward pass; nothing
recomputed (every layer is rematerialised: its second forward is not counted),
no elementwise work (the norms, the rotation, the softmaxes, the gates'
products), no optimizer, no sort or gather.

Latent attention is counted in the **expanded** form, the one the published
modelling code trains and this program runs: per token the two down
projections (``hidden x q_lora_rank`` and ``hidden x (kv_lora_rank +
qk_rope_head_dim)``), the two up projections (``q_lora_rank x heads x
(qk_nope + qk_rope)`` and ``kv_lora_rank x heads x (qk_nope + v)``) and the
output projection; per kept query-key pair and head ``QK^T`` over ``qk_nope +
qk_rope`` features and ``PV`` over ``v_head_dim``. A program that trains in the
absorbed form (one key head of ``kv_lora_rank + qk_rope`` features,
``kv_lora_rank``-wide values: 2.1x the pair's operations) gets nothing for the
extra. The kernel's bytes are what it must move with every head's keys and
values expanded, as it is given them: the forward reads q, k, v and writes o;
the backward reads q, k, v, o, do and writes dq, dk, dv. A kernel that read
the latent and the shared key unbroadcast would move less and is not credited
for more.

A routed expert is gated: three products per row; bytes as
``flops_smallthinker.gmm_train`` counts them, over the expert layers alone.
The dense layers' MLP is three products per token. The backward pass is
charged twice the forward's operations and bytes.
"""

from __future__ import annotations

import math

from benchmarks.flops_smallthinker import counted  # noqa: F401 — the readers' helper

TRAIN_OVER_FORWARD = 3  # forward + backward (2x forward)


def widths(params: dict) -> dict:
    a = params["arch"]
    heads = a["num_attention_heads"]
    qk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
    dense = a["first_k_dense_replace"]
    return dict(
        d=a["hidden_size"], layers=a["num_hidden_layers"], n_dense=dense,
        n_expert=a["num_hidden_layers"] - dense, heads=heads, qk=qk, v=a["v_head_dim"],
        q_rank=a["q_lora_rank"], kv_rank=a["kv_lora_rank"], rope=a["qk_rope_head_dim"],
        nope=a["qk_nope_head_dim"], mlp=a["intermediate_size"], f=a["moe_intermediate_size"],
        shared=a["n_shared_experts"] * a["moe_intermediate_size"], held=a["n_routed_experts"],
        routed=a.get("expert_parallel", {}).get(
            "published_n_routed_experts", a["n_routed_experts"]),
    )


def _width(params: dict) -> int:
    return 2 if params.get("compute_dtype") == "bfloat16" else 4


def attention_parameters(params: dict) -> int:
    """One layer's latent attention: five projections and two latent norms."""
    w = widths(params)
    return (
        w["d"] * w["q_rank"] + w["q_rank"] + w["q_rank"] * w["heads"] * w["qk"]
        + w["d"] * (w["kv_rank"] + w["rope"]) + w["kv_rank"]
        + w["kv_rank"] * w["heads"] * (w["nope"] + w["v"]) + w["heads"] * w["v"] * w["d"]
    )


def layer_parameters(params: dict, dense: bool) -> int:
    """A dense or an expert layer: attention, its MLP or expert block (router,
    correction bias, shared expert, held experts), two norms."""
    w = widths(params)
    if dense:
        block = 3 * w["d"] * w["mlp"]
    else:
        block = (w["d"] * w["routed"] + w["routed"] + 3 * w["d"] * w["shared"]
                 + w["held"] * 3 * w["d"] * w["f"])
    return attention_parameters(params) + block + 2 * w["d"]


def latent_forward_per_token(params: dict) -> float:
    """The low-rank path outside the kernel and the output projection, one
    layer: ``q_a``, ``kv_a``, ``q_b``, ``kv_b``, ``o``."""
    w = widths(params)
    down = w["d"] * w["q_rank"] + w["d"] * (w["kv_rank"] + w["rope"])
    up = w["q_rank"] * w["heads"] * w["qk"] + w["kv_rank"] * w["heads"] * (w["nope"] + w["v"])
    return 2.0 * (down + up + w["heads"] * w["v"] * w["d"])


def dense_forward_per_token(params: dict) -> float:
    """Every projection each token passes through: the observation projection;
    per layer latent attention's five; per dense layer the MLP's three
    products; per expert layer the router and the shared expert's three; and
    the two heads."""
    w = widths(params)
    obs = math.prod(params.get("obs_shape", [4]))
    heads = params.get("action_space", 2) + 1
    experts = w["d"] * w["routed"] + 3 * w["d"] * w["shared"]
    return (
        2.0 * (obs * w["d"] + w["n_dense"] * 3 * w["d"] * w["mlp"] + w["n_expert"] * experts
               + w["d"] * heads)
        + w["layers"] * latent_forward_per_token(params)
    )


def attention_forward_per_pair(params: dict) -> float:
    """QK^T (over a head's unrotated and rotated features) and PV for one kept
    query-key pair, over every head."""
    w = widths(params)
    return 2.0 * w["heads"] * (w["qk"] + w["v"])


def attention_train(params: dict, rows: int, pairs: float) -> tuple[float, float]:
    """(operations, HBM bytes) the attention kernels need for one update of
    ``rows`` sequences whose masks kept ``pairs`` query-key pairs over all
    layers. The forward reads q, k, v and writes o; the backward reads q, k,
    v, o, do and writes dq, dk, dv: per sequence and layer six arrays of
    ``heads x (qk_nope + qk_rope)`` (q and k twice, dq, dk) and six of
    ``heads x v_head_dim`` (v and o twice, do, dv)."""
    w, T = widths(params), params["seq_len"]
    ops = TRAIN_OVER_FORWARD * pairs * attention_forward_per_pair(params)
    return ops, 6.0 * rows * w["layers"] * T * w["heads"] * (w["qk"] + w["v"]) * _width(params)


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
