"""``learner_feed`` for a family with sparse-expert layers: the same run, and
beside ``parity.check`` a *routed* comparison that joins what decides
``correct``.

Why a second comparison. The system's hidden states differ from the float32
reference's by bf16 rounding, so wherever two experts' scores lie closer than
that the two choose differently, and a different expert is an O(1) change at
that step which the scan then carries forward. The free-running check
(``parity.check``, unchanged) therefore holds the loss tightly and the maximum
over all logits only loosely. Here the reference is run **on the system's
choices** (``reference.forward_routed(..., choices=...)``):

- logits and values must then agree at tolerances that hold the precision
  line (``parity["routed"]["tol"]``: a reference with operands of the next
  precision down fails them);
- the choices themselves are held: wherever the system's chosen set is not
  the reference's own choice on the states it reached, the reference's margin
  between its lowest chosen and highest unchosen score is under ``delta``, and
  the share of such assignments is under ``flip_share``.

It runs before the learner starts, on the first ``routed["rows"]`` windows of
the parity check's own seeded batch (the cell: all four of the timed batch), so
it counts as set-up; the verdict goes into ``run.notes["checks"]``
(``Run.correct`` folds it in) and the readings into the result line's
``parity`` block.

``--set routed.operand_dtype='"float8_e4m3fn"'`` (sweeps only) is the control:
the reference computed in the next precision down. The routed comparison then
reads that reference, and ``parity["routed"]["free_control"]`` holds what the
free-running limits (``parity["tol"]``) read against it — the same three
quantities ``parity.check`` compares — so that each limit has its control
reading on record. ``correct`` comes out false.
"""

from __future__ import annotations

import os

import numpy as np

from benchmarks import harness, parity, traffic
from benchmarks.runners import learner_feed

HERE = os.path.dirname(os.path.abspath(__file__))


def routed_check(params: dict, block: dict, seed: int, operand_dtype: str | None = None) -> dict:
    """``block``: the configuration file's ``parity`` block."""
    import jax
    import jax.numpy as jnp

    from tpu_rl.config import Config

    # first of all: a program that cannot build this configuration says so here
    cfg = Config.from_dict({**params, "result_dir": None, "model_dir": None})

    from tpu_rl.algos.ppo import policy_outputs_routed
    from tpu_rl.algos.registry import get_algo
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.types import BATCH_FIELDS, Batch

    routed = block["routed"]
    layout = BatchLayout.from_config(cfg)
    windows = traffic.make_windows(
        {f: layout.width(f) for f in BATCH_FIELDS}, cfg.seq_len,
        cfg.action_space, block["windows"], seed,
    )
    rows = min(int(routed.get("rows", block["rows"])), cfg.batch_size)
    host = traffic.stack(windows, rows)
    family, state, _ = get_algo(cfg.algo).build(cfg, jax.random.key(seed))
    actor = state.params["actor"]
    del state

    _, _, sys_value, sys_logits, routes = jax.jit(
        lambda p, b: policy_outputs_routed(family, {"actor": p}, b)
    )(actor, Batch.from_mapping(host))
    choices = [np.asarray(r["choice"]) for r in routes]
    sys_logits, sys_value = np.asarray(sys_logits), np.asarray(sys_value)

    ref = harness.load_module(
        os.path.join(os.path.dirname(HERE), "reference", f"{block['reference']}.py")
    )
    dtype = None if operand_dtype is None else jnp.dtype(operand_dtype)
    fwd = jax.jit(lambda p, b, c: ref.forward_routed(p, b, params, c, dtype))
    chunk = int(block.get("chunk_rows", rows))
    logits, value, own, margin = [], [], [], []
    with jax.default_matmul_precision("highest"):
        for i in range(0, rows, chunk):
            part = {f: jnp.asarray(host[f][i : i + chunk]) for f in BATCH_FIELDS}
            lg, v, ref_routes = fwd(actor, part, [c[i : i + chunk] for c in choices])
            logits.append(np.asarray(lg))
            value.append(np.asarray(v))
            own.append([np.asarray(r["choice"]) for r in ref_routes])
            margin.append([np.asarray(r["margin"]) for r in ref_routes])
    free = None
    if dtype is not None:
        free = _free_control(ref, actor, host, params, block, dtype, sys_logits, sys_value)
    del actor

    # per expert layer: the system's assignments that the reference would not
    # have made, and the reference's margin at the steps where the sets differ
    flipped, assignments, worst = 0, 0, 0.0
    for layer, mine in enumerate(choices):
        theirs = np.concatenate([o[layer] for o in own])
        gap = np.concatenate([m[layer] for m in margin])
        stray = ~(mine[..., :, None] == theirs[..., None, :]).any(-1)  # (rows, T, k)
        flipped += int(stray.sum())
        assignments += stray.size
        if stray.any():
            worst = max(worst, float(gap[stray.any(-1)].max()))
    err = {
        "logits": parity.rel_err(sys_logits, np.concatenate(logits)),
        "value": parity.rel_err(sys_value, np.concatenate(value)),
        "flip_share": flipped / assignments,
        "flip_margin": worst,
    }
    limit = {**routed["tol"], "flip_share": routed["flip_share"], "flip_margin": routed["delta"]}
    verdict = {
        "ok": all(err[k] <= limit[k] for k in limit),
        "err": err,
        "tol": limit,
        "operand_dtype": operand_dtype,
        "assignments": assignments,
    }
    if free is not None:
        verdict["free_control"] = free
    return verdict


def _free_control(ref, actor, host, params, block, dtype, sys_logits, sys_value) -> dict:
    """The free-running comparison's three readings against the reference at
    ``dtype`` operands, choosing for itself: the system's logits and values as
    ``parity.check`` compares them, and the loss that reference's outputs give
    against the one the system's give (``reference/losses.py`` on both, one
    scale: the control reference's policy and value parts)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference.losses import LOSSES
    from tpu_rl.types import BATCH_FIELDS

    fwd = jax.jit(lambda p, b: ref.forward_routed(p, b, params, None, dtype)[:2])
    rows = sys_logits.shape[0]
    chunk = int(block.get("chunk_rows", rows))
    logits, value = [], []
    with jax.default_matmul_precision("highest"):
        for i in range(0, rows, chunk):
            part = {f: jnp.asarray(host[f][i : i + chunk]) for f in BATCH_FIELDS}
            lg, v = fwd(actor, part)
            logits.append(np.asarray(lg))
            value.append(np.asarray(v))
    logits, value = np.concatenate(logits), np.concatenate(value)
    loss_fn = LOSSES[params["algo"]]
    theirs, mine = loss_fn(logits, value, host, params), loss_fn(sys_logits, sys_value, host, params)
    scale = abs(theirs["policy-loss"]) + abs(theirs["value-loss"]) + 1e-12
    err = {
        "logits": parity.rel_err(sys_logits, logits),
        "value": parity.rel_err(sys_value, value),
        "loss": abs(mine["loss"] - theirs["loss"]) / scale,
    }
    tol = block["tol"]
    return {"err": err, "tol": tol, "fails": sorted(k for k in tol if err[k] > tol[k])}


def run(spec: harness.Spec) -> harness.Run:
    import jax

    from tpu_rl.utils.platform import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    harness.check_device(devices[0].platform, len(devices), spec.chips)
    verdict = routed_check(
        spec.params, spec.config["parity"], spec.seed,
        spec.traffic.get("routed", {}).get("operand_dtype"),
    )
    run = learner_feed.run(spec)
    run.parity = {**run.parity, "routed": verdict}
    run.notes.setdefault("checks", {})["routed_parity"] = verdict["ok"]
    return run
