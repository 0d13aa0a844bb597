"""Attention-impl microbench at the long-context workload shape.

Round-4 on-chip bench showed the stock-default flash row LOSING to both
full attention and blockwise at (B16, T2048, H8, D64):

    full 72.0 ms/step, blockwise 136.2, flash 190.7   (whole train step)

This isolates the attention op itself (fwd and fwd+grad) and sweeps the
Pallas kernel's BlockSizes — the defaults are 128-everywhere with
block_b=1 (`BlockSizes.get_default`, annotated "TODO: select better
parameters"), which at this shape means a 128x16x16 grid of tiny tiles.
The result decides the dispatch policy in
`tpu_rl/parallel/sequence.flash_attention_tpu` (measured-win-only, the
same lesson as the LSTM kernel: VERDICT r3 #5).

Run ON the TPU:

    PYTHONPATH=/root/repo python examples/bench_flash_attention.py

Writes bench_flash.json next to the repo root (untracked: the sweep this
file was written around is gone with the machine it ran on; ROADMAP S2
re-measures).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from tpu_rl.parallel import sequence as seqlib

B, T, H, D = 16, 2048, 8, 64
DTYPE = jnp.bfloat16
WARMUP, ITERS = 3, 20


def _inputs():
    rng = np.random.default_rng(0)
    shape = (B, T, H, D)
    q = jnp.asarray(rng.normal(size=shape), DTYPE) * 0.1
    k = jnp.asarray(rng.normal(size=shape), DTYPE) * 0.1
    v = jnp.asarray(rng.normal(size=shape), DTYPE) * 0.1
    # Two episode segments per row, seam mid-sequence — exercises the
    # segment mask the real workload always carries.
    firsts = np.zeros((B, T, 1), np.float32)
    firsts[:, 0] = 1.0
    firsts[:, T // 2] = 1.0
    seg = seqlib.segment_ids_from_firsts(jnp.asarray(firsts))
    q_pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    return q, k, v, q_pos, seg


def _force_done(out) -> None:
    # Read one scalar back to the host: the timed region ends when the
    # device has finished (same as bench.py _sync).
    s = jax.tree.map(lambda x: jnp.sum(x.astype(jnp.float32)), out)
    float(np.asarray(jax.device_get(jax.tree.leaves(s)[0])))


def _time(fn, *args) -> float:
    out = None
    for _ in range(WARMUP):
        out = fn(*args)
    # Same sync as the timed region, so the first recorded row cannot
    # absorb still-draining warmup/compile work.
    _force_done(out)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = fn(*args)
    _force_done(out)
    return (time.perf_counter() - t0) / ITERS * 1e3


def _flash_fn(block: int | None):
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds,
        flash_attention,
    )

    from tpu_rl.parallel.sequence import _uniform_block_sizes

    bs = None if block is None else _uniform_block_sizes(min(block, T))

    def fn(q, k, v, q_pos, seg):
        qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        seg32 = seg.astype(jnp.int32)
        o = flash_attention(
            qt, kt, vt, segment_ids=SegmentIds(q=seg32, kv=seg32),
            causal=True, sm_scale=float(1.0 / np.sqrt(D)), block_sizes=bs,
        )
        return o.transpose(0, 2, 1, 3)

    return fn


def main() -> None:
    q, k, v, q_pos, seg = _inputs()
    impls: dict[str, object] = {
        "full": functools.partial(seqlib.full_attention, causal=True),
        "blockwise": functools.partial(seqlib.blockwise_attention, causal=True),
        "flash@128(default)": _flash_fn(None),
        "flash@256": _flash_fn(256),
        "flash@512": _flash_fn(512),
        "flash@1024": _flash_fn(1024),
        "flash@2048": _flash_fn(2048),
    }
    rows = []
    for name, fn in impls.items():
        row = {"name": name, "shape": [B, T, H, D], "dtype": "bfloat16"}
        try:
            fwd = jax.jit(fn)
            row["fwd_ms"] = round(_time(fwd, q, k, v, q_pos, seg), 3)

            def loss(q_, k_, v_):
                return jnp.sum(fn(q_, k_, v_, q_pos, seg).astype(jnp.float32))

            grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            row["fwdbwd_ms"] = round(_time(grad, q, k, v), 3)
        except Exception as e:  # noqa: BLE001 — record the failure, keep rows
            row["error"] = f"{type(e).__name__}: {e}"[:300]
        rows.append(row)
        print(json.dumps(row), flush=True)

    out = {
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "warmup": WARMUP,
        "iters": ITERS,
        "rows": rows,
    }
    path = os.path.join(os.path.dirname(__file__), "..", "bench_flash.json")
    if jax.default_backend() != "tpu":
        path = path.replace(".json", ".cpu.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", os.path.normpath(path))


if __name__ == "__main__":
    main()
