"""On-chip kernel-vs-scan benchmark for the fused Pallas LSTM.

Times the LSTM sequence unroll (forward and forward+grad) with the Pallas
kernel (``set_pallas_mode("auto")``) against the ``lax.scan`` path
(``"off"``), at the reference batch quantum and at MXU-loading widths —
including shapes whose batch is grid-tiled over VMEM (``batch_tile``).

Run on the TPU (no JAX_PLATFORMS override):
  PYTHONPATH=/root/repo python examples/bench_lstm_kernel.py

Writes ``bench_lstm_kernel.json`` and prints one row per (shape, pass).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from tpu_rl.models import cells
from tpu_rl.models.cells import LSTMCell
from tpu_rl.ops.pallas_lstm import batch_tile, bwd_batch_tile

SHAPES = [
    # (B, S, IN, H, iters) — reference quantum, mid, wide (grid-tiled)
    (128, 5, 4, 64, 300),
    (256, 16, 64, 256, 100),
    (1024, 16, 64, 1024, 30),
]


def _run(cell, params, x, firsts, carry0, mode: str, grad: bool, iters: int):
    def fwd(params, x):
        cells.set_pallas_mode(mode)
        try:
            (hN, cN), hs = cell.apply(
                params, x, carry0, firsts, True, method=LSTMCell.unroll
            )
        finally:
            cells.set_pallas_mode("auto")
        return (hs**2).mean() + (hN + cN).mean()

    fn = jax.jit(jax.grad(fwd) if grad else fwd)
    out = fn(params, x)  # compile
    jax.block_until_ready(out)
    # device_get forces true chain completion: block_until_ready on one
    # output can return before the whole chain of dispatches has run
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(params, x)
    np.asarray(
        jax.device_get(jax.tree_util.tree_leaves(out)[0])
    ).ravel()[:1]
    return (time.perf_counter() - t0) / iters


def main() -> None:
    rows = []
    for B, S, IN, H, iters in SHAPES:
        rng = np.random.default_rng(0)
        cell = LSTMCell(H)
        x = jnp.asarray(rng.normal(size=(B, S, IN)).astype(np.float32))
        firsts = np.zeros((B, S, 1), np.float32)
        firsts[:, 0] = 1.0
        firsts = jnp.asarray(firsts)
        carry0 = (jnp.zeros((B, H)), jnp.zeros((B, H)))
        params = cell.init(jax.random.key(0), (carry0[0], carry0[1]), x[:, 0])
        for grad in (False, True):
            # "force" runs the REAL kernel wherever a tiling fits (auto now
            # dispatches by measured win, so auto's fwd-only path is the
            # scan — forcing is the only way to keep timing the kernel).
            t_scan = _run(cell, params, x, firsts, carry0, "off", grad, iters)
            t_kern = _run(cell, params, x, firsts, carry0, "force", grad, iters)
            # What auto-dispatch picks at this (shape, pass): the kernel only
            # under AD at whole-batch-single-tile shapes (cells._use_pallas +
            # the lstm_unroll primal's scan body).
            single_tile = (
                batch_tile(B, S, H) == B and bwd_batch_tile(B, S, H) == B
            )
            chosen = "kernel" if (grad and single_tile) else "scan"
            chosen_ms = t_kern if chosen == "kernel" else t_scan
            row = {
                "shape": f"B{B} S{S} H{H}",
                "pass": "fwd+grad" if grad else "fwd",
                "batch_tile": batch_tile(B, S, H),
                "scan_ms": round(t_scan * 1e3, 3),
                "kernel_ms": round(t_kern * 1e3, 3),
                "speedup": round(t_scan / t_kern, 2),
                "tokens_per_s_kernel": round(B * S / t_kern, 1),
                "auto_chooses": chosen,
                "auto_regression": round(
                    chosen_ms / min(t_scan, t_kern), 3
                ),  # 1.0 = auto picked the measured-fastest path
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = {
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "rows": rows,
    }
    with open("bench_lstm_kernel.json", "w") as f:
        json.dump(out, f, indent=1)
    print("wrote bench_lstm_kernel.json", flush=True)


if __name__ == "__main__":
    main()
