"""System against plain reference, on the chip, outside the timed window.

One seeded batch at the cell's widths goes through the system's own jitted
train step (the program the window runs, kernels and mixed precision included)
and through ``benchmarks/reference/<family>.py`` + ``reference/losses.py``
(float32 at ``highest`` matmul precision, NumPy float64 loss). Compared:

- log-softmax policy logits and values on the first ``rows`` rows, as
  max |system - reference| / max |reference|,
- the scalar loss and its policy / value / entropy parts on the whole batch,
  as |system - reference| / (|policy part| + |value part|).

The tolerances sit in the configuration file beside their reason.

In-process for runners that own the chip; ``python benchmarks/parity.py`` is
the same check as a child for runners whose chip owner is the program's own
process (the child must have exited before that process starts, or start
after it has ended: one process per chip).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import harness, traffic  # noqa: E402


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-12))


def check(params: dict, parity: dict, seed: int) -> dict:
    """``params``: the program's configuration as the cell runs it;
    ``parity``: the configuration file's ``parity`` block."""
    import jax
    import jax.numpy as jnp

    from tpu_rl.algos.ppo import policy_outputs
    from tpu_rl.algos.registry import get_algo
    from tpu_rl.config import Config
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.types import BATCH_FIELDS, Batch
    from tpu_rl.utils.platform import enable_compile_cache

    enable_compile_cache()
    cfg = Config.from_dict({**params, "result_dir": None, "model_dir": None})
    layout = BatchLayout.from_config(cfg)
    windows = traffic.make_windows(
        {f: layout.width(f) for f in BATCH_FIELDS}, cfg.seq_len,
        cfg.action_space, parity["windows"], seed,
    )
    host = traffic.stack(windows, cfg.batch_size)
    family, state, step = get_algo(cfg.algo).build(cfg, jax.random.key(seed))
    actor = jax.device_get(state.params["actor"])  # the step donates the state

    # ---- the system: the window's own train-step program, and its forward
    batch = Batch.from_mapping(host)
    if cfg.mesh_data > 1:
        from tpu_rl.parallel.dp import make_parallel_train_step, replicate, shard_batch
        from tpu_rl.parallel.mesh import make_mesh

        mesh = make_mesh(cfg.mesh_data)
        jstep = make_parallel_train_step(step, mesh, cfg)
        state, batch = replicate(state, mesh), shard_batch(batch, mesh)
        key = replicate(jax.random.key(seed + 1), mesh)
    else:
        jstep = jax.jit(step, donate_argnums=(0,))
        key = jax.random.key(seed + 1)
    _, metrics = jstep(state, batch, key)
    sys_loss = {
        k: float(metrics[k])
        for k in ("loss", "policy-loss", "value-loss", "policy-entropy")
    }
    rows = min(int(parity["rows"]), cfg.batch_size)
    head = Batch.from_mapping({f: host[f][:rows] for f in BATCH_FIELDS})
    _, _, sys_value, sys_logits = jax.jit(
        lambda p, b: policy_outputs(family, {"actor": p}, b)
    )(actor, head)
    sys_logits, sys_value = np.asarray(sys_logits), np.asarray(sys_value)
    del state, batch, metrics, head

    # ---- the reference, in chunks of rows it can hold
    ref = harness.load_module(
        os.path.join(HERE, "reference", f"{parity['reference']}.py")
    )
    from benchmarks.reference.losses import LOSSES

    fwd = jax.jit(lambda p, b: ref.forward(p, b, params))
    chunk = int(parity.get("chunk_rows", cfg.batch_size))
    logits, value = [], []
    with jax.default_matmul_precision("highest"):
        for i in range(0, cfg.batch_size, chunk):
            part = {f: jnp.asarray(host[f][i : i + chunk]) for f in BATCH_FIELDS}
            lg, v = fwd(actor, part)
            logits.append(np.asarray(lg))
            value.append(np.asarray(v))
    logits, value = np.concatenate(logits), np.concatenate(value)
    loss_fn = (
        harness.load_module(os.path.join(HERE, "reference", f"{parity['loss']}.py")).loss
        if "loss" in parity  # an algorithm a later PR brought, in a file of its own
        else LOSSES[cfg.algo]
    )
    ref_loss = loss_fn(logits, value, host, params)

    # A mean of signed terms can cancel to near 0: the loss and its parts are
    # held to one scale, the size of the reference's policy and value parts.
    scale = abs(ref_loss["policy-loss"]) + abs(ref_loss["value-loss"]) + 1e-12
    err = {
        "logits": rel_err(sys_logits, logits[:rows]),
        "value": rel_err(sys_value, value[:rows]),
        **{k: abs(sys_loss[k] - ref_loss[k]) / scale for k in sys_loss},
    }
    tol = parity["tol"]
    dev = jax.devices()[0]
    return {
        "ok": all(err[k] <= tol[k] for k in tol),
        "err": err,
        "tol": tol,
        "loss": {"system": sys_loss["loss"], "reference": ref_loss["loss"]},
        "platform": dev.platform,
        "device_count": len(jax.devices()),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--spec", required=True,
                    help="JSON file: {params, parity, seed}")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    spec = harness.load_json(args.spec)
    result = check(spec["params"], spec["parity"], spec["seed"])
    with open(args.out, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
