"""Operations and bytes an update needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. Counted: the multiply-adds of the forward pass (2 per MAC) and
twice that for the backward pass. Not counted: anything recomputed (flash
attention's backward recomputes the scores; a scan body is counted once per
step, not once), elementwise work, the optimizer. ``compiled.cost_analysis()``
is not used: it counts the recompute and counts a scan body once.

Causal attention is charged half of the full T x T product: the other half is
masked and a kernel may skip it. Episode seams mask more, by an amount that
depends on the data, and are not discounted.

``params`` is the program's configuration as the cell runs it (the config
file's ``params`` under the traffic mix's).
"""

from __future__ import annotations

import json
import math
import os

TRAIN_OVER_FORWARD = 3  # forward + backward (2x forward)


def _obs(params: dict) -> int:
    return math.prod(params.get("obs_shape", [4]))


def transformer_forward_per_token(params: dict) -> float:
    """Dense layers of one token: embed, per block qkv + out + two MLP
    matmuls (ff_mult 4), the two heads."""
    d, L = params["hidden_size"], params["n_layers"]
    heads = params.get("action_space", 2) + 1
    return 2.0 * (_obs(params) * d + L * (3 * d * d + d * d + 8 * d * d) + d * heads)


def attention_forward_per_sequence(params: dict) -> float:
    """QK^T and PV of every layer, causal: 2 matmuls x 2 T^2 d / 2."""
    T, d, L = params["seq_len"], params["hidden_size"], params["n_layers"]
    return L * 2.0 * T * T * d


def attention_train(params: dict, rows: int) -> tuple[float, float]:
    """(operations, HBM bytes) attention needs for one update of ``rows``
    sequences, forward and backward. Bytes: the forward reads q, k, v and
    writes o; the backward reads q, k, v, o, do and writes dq, dk, dv: twelve
    (T, d) arrays per sequence and layer in the compute dtype (the per-row
    softmax statistics are 1/64 of that and left out)."""
    T, d, L = params["seq_len"], params["hidden_size"], params["n_layers"]
    width = 2 if params.get("compute_dtype") == "bfloat16" else 4
    ops = TRAIN_OVER_FORWARD * rows * attention_forward_per_sequence(params)
    return ops, 12.0 * rows * L * T * d * width


def lstm_forward_per_step(params: dict) -> float:
    """One env step of the MLP + LSTM actor-critic: torso, input and
    recurrent projections into the four gates, the two heads."""
    H = params["hidden_size"]
    heads = params.get("action_space", 2) + 1
    return 2.0 * (_obs(params) * H + 2 * H * 4 * H + H * heads)


def lstm_cell_train(params: dict, rows: int) -> float:
    """The recurrent matmul alone (what the LSTM kernel or scan computes;
    the input projection is one batched matmul outside it)."""
    H = params["hidden_size"]
    return TRAIN_OVER_FORWARD * rows * params["seq_len"] * 2.0 * H * 4 * H


def update(params: dict, rows: int, acts_in_program: bool = False) -> float:
    """Operations one update of ``rows`` windows needs. ``acts_in_program``:
    the colocated program also runs the acting forward for every step it
    trains on."""
    T = params["seq_len"]
    if params.get("model", "lstm") == "transformer":
        fwd = rows * (T * transformer_forward_per_token(params)
                      + attention_forward_per_sequence(params))
    else:
        fwd = rows * T * lstm_forward_per_step(params)
    return fwd * (TRAIN_OVER_FORWARD + (1 if acts_in_program else 0))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind the table lacks is an error."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}: add it to "
            "benchmarks/peaks.json with its source"
        )
    return table[device_kind]
