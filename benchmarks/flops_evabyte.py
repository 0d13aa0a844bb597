"""Operations and bytes an update of the evabyte family needs, from shapes
(``params["arch"]``: the model's published ``config.json`` keys) and from the
two things the program **counts** because the data decides them (``diag``, per
update, each summed over the layers): the exact query-key pairs the blocks
keep (``attn-pairs-block``: ``sum_t (p(t) mod W) + 1``) and the summaries the
queries read (``attn-pairs-summary``: ``sum_t (W / C) b(t)``) — an episode
seam restarts both. The readers hand the counts in.

Same rules as ``benchmarks/flops.py`` and its siblings: multiply-adds of the
forward pass (2 per MAC) and twice that for the backward pass; nothing
recomputed (every layer is rematerialised: its second forward is not counted),
no elementwise work (the norms, the rotation, the softmaxes, **the chunk
pooling**), no optimizer, no gather.

A kept pair — an exact key or a summary, the same two products — is QK^T and
PV over every head: 2 x 2 x hidden = 16,384 operations at hidden 4096. A pair
earns where the mask keeps it and nowhere else: a candidate summary a block of
queries is scored against and the mask drops earns nothing, nor does a tile
the kernel visits and a boundary empties.

The chunk pooling does no matrix product: it is bytes, and ``pool_train``
counts what one fused pass must move: the forward reads k and v and writes
k~ and v~ (1 / C of them); the rematerialised second forward the same (the
layer is rematerialised and the float32 members are not kept, so the
recomputation is part of the algorithm as the memory budget forces it — it is
charged here, and only here, because this is a roofline of bytes and not a
count of useful operations); the backward reads k, v, dk~ and dv~ and writes
the two gradients' parts. A kernel that moved less would read above what it is
credited for; a gather that materialises the members moves more.
"""

from __future__ import annotations

import math

from benchmarks.flops_smallthinker import counted  # noqa: F401 — the readers' helper

TRAIN_OVER_FORWARD = 3  # forward + backward (2x forward)


def widths(params: dict) -> dict:
    a = params["arch"]
    return dict(
        d=a["hidden_size"], layers=a["num_hidden_layers"], heads=a["num_attention_heads"],
        head=a["hidden_size"] // a["num_attention_heads"], mlp=a["intermediate_size"],
        W=a["window_size"], C=a["chunk_size"],
    )


def _width(params: dict) -> int:
    return 2 if params.get("compute_dtype") == "bfloat16" else 4


def layer_parameters(params: dict) -> int:
    """A layer: four projections, the two pooling vectors a head, the MLP's
    three matrices, two norms."""
    w = widths(params)
    return 4 * w["d"] * w["d"] + 2 * w["heads"] * w["head"] + 3 * w["d"] * w["mlp"] + 2 * w["d"]


def parameters(params: dict) -> int:
    """The whole actor-critic: the layers, the observation projection (with
    bias), the last norm, the policy and value heads (with bias)."""
    w = widths(params)
    obs = math.prod(params.get("obs_shape", [4]))
    heads = params.get("action_space", 2) + 1
    return (w["layers"] * layer_parameters(params) + (obs + 1) * w["d"] + w["d"]
            + (w["d"] + 1) * heads)


def dense_forward_per_token(params: dict) -> float:
    """Every projection each token passes through: the observation projection;
    per layer q, k, v, o and the MLP's three products; the two heads."""
    w = widths(params)
    obs = math.prod(params.get("obs_shape", [4]))
    heads = params.get("action_space", 2) + 1
    return 2.0 * (obs * w["d"] + w["layers"] * (4 * w["d"] * w["d"] + 3 * w["d"] * w["mlp"])
                  + w["d"] * heads)


def attention_forward_per_pair(params: dict) -> float:
    """QK^T and PV for one kept pair, over every head."""
    return 2.0 * 2 * widths(params)["d"]


def pairs_without_a_seam(params: dict) -> tuple[float, float]:
    """(exact pairs, summaries) one query keeps in the mean over a window of
    ``seq_len`` steps that is one episode: ``(W + 1) / 2`` and
    ``(W / C) (T / W - 1) / 2``."""
    w, T = widths(params), params["seq_len"]
    return (w["W"] + 1) / 2.0, (w["W"] // w["C"]) * (T // w["W"] - 1) / 2.0


def attention_train(params: dict, rows: int, pairs: float) -> tuple[float, float]:
    """(operations, HBM bytes) the two-resolution read needs for one update of
    ``rows`` sequences whose masks kept ``pairs`` pairs of both kinds over the
    layers: the forward reads q, k, v and the summaries and writes o; the
    backward reads q, k, v, o, do and the summaries and writes dq, dk, dv and
    the summaries' gradients."""
    w, T = widths(params), params["seq_len"]
    ops = TRAIN_OVER_FORWARD * pairs * attention_forward_per_pair(params)
    arrays = 12.0 + 6.0 / w["C"]  # of (T, hidden); a summary array is 1 / C of one
    return ops, arrays * rows * w["layers"] * T * w["d"] * _width(params)


def pool_train(params: dict, rows: int) -> float:
    """HBM bytes one fused pass over the chunk pooling must move for one
    update of ``rows`` sequences: per layer, in arrays of (T, hidden) in the
    compute dtype, the forward and its rematerialised twin read k, v and write
    k~, v~ (2 + 2 / C each), the backward reads k, v, dk~, dv~ and writes the
    two gradients' parts (4 + 2 / C)."""
    w, T = widths(params), params["seq_len"]
    arrays = 2 * (2 + 2.0 / w["C"]) + (4 + 2.0 / w["C"])
    return float(arrays * rows * w["layers"] * T * w["d"] * _width(params))


def update(params: dict, rows: int, pairs: float) -> float:
    """Operations one update of ``rows`` windows needs, forward and backward,
    at ``pairs`` kept pairs (both kinds, over the layers)."""
    tokens = rows * params["seq_len"]
    return TRAIN_OVER_FORWARD * (
        tokens * dense_forward_per_token(params) + pairs * attention_forward_per_pair(params))


def counted_pairs(window_rows) -> tuple[float, float] | None:
    """(exact pairs, summaries read) an update, the mean over the window's
    ``learn.jsonl`` lines; None where the program ships no such counter."""
    block = counted(window_rows, "attn-pairs-block")
    summary = counted(window_rows, "attn-pairs-summary")
    return None if block is None or summary is None else (block, summary)
