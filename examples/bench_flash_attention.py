"""Attention microbench at the shapes the benchmark's cells run: the sweep
behind ``tpu_rl/parallel/sequence._splash_block_sizes``.

Times the PRODUCTION construction (``sequence._splash_mha``: scale
folded into q, the transposes, ``vmap`` over rows, causal + segment mask),
forward alone and forward + backward, over tile edges x {single-pass,
two-pass backward} x compute sub-tiles, beside ``blockwise`` and
the library kernel the dispatch called before PR 27 (``legacy@512``). The row
``rule`` is what the dispatch picks. A variant the compiler refuses is a row
with an ``error``, not a crash.

Run ON the TPU (seconds per row):

    python examples/bench_flash_attention.py [--out chiprun_out/bench_flash.json]
        [--shapes tf-longctx,granite] [--only rule,legacy@512]
"""

from __future__ import annotations

import argparse
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

# name -> (B, T, H, Hkv, D, sm_scale): one layer of each registered configuration
SHAPES = {
    "tf-longctx": (32, 2048, 8, 8, 64, None),
    "granite": (2, 4096, 32, 8, 64, 1.0 / 64),
}
DTYPE = jnp.bfloat16
WARMUP, ITERS = 2, 10


def _inputs(B, T, H, Hkv, D):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), DTYPE)
    k, v = (jnp.asarray(rng.normal(size=(B, T, Hkv, D)), DTYPE) for _ in range(2))
    # ~8 seams a row (the granite cell's traffic: episodes of mean T / 8)
    firsts = rng.random((B, T, 1)) < 8.0 / T
    firsts[:, 0] = True
    seg = seqlib.segment_ids_from_firsts(jnp.asarray(firsts, jnp.float32))
    q_pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    return q, k, v, q_pos, seg


def _force_done(out) -> None:
    # Read one scalar back to the host: the timed region ends when the
    # device has finished.
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


def _tiles(bq, bkv, compute, fused, seq_minor=""):
    """BlockSizes with (bq, bkv) tiles in both passes, ``compute`` columns per
    inner step, and the operands named in ``seq_minor`` ("kv": k and v) laid
    out (head dim, T) in HBM instead of (T, head dim)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes, QKVLayout

    dq = {} if fused else dict(block_q_dq=bq, block_kv_dq=bkv)
    layouts = {f"{x}_layout": QKVLayout.SEQ_MINOR for x in seq_minor}
    return BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=min(compute, bkv),
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=min(compute, bkv),
        use_fused_bwd_kernel=fused, **dq, **layouts,
    )


def _splash_fn(block_sizes, scale):
    def fn(q, k, v, q_pos, seg):
        return seqlib._splash_mha(
            q, k, v, seg, causal=True, scale=scale, block_sizes=block_sizes
        )

    return fn


def _legacy_fn(scale, rep):
    """What ``flash_attention_tpu`` called until PR 27, as it called it."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        SegmentIds,
        flash_attention,
    )

    names = (
        "block_q block_k_major block_k block_q_major_dkv block_k_major_dkv block_k_dkv "
        "block_q_dkv block_k_major_dq block_k_dq block_q_dq"
    ).split()
    bs = BlockSizes(block_b=1, **dict.fromkeys(names, 512))

    def fn(q, k, v, q_pos, seg):
        k, v = (jnp.repeat(x, rep, axis=2) for x in (k, v))
        qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        seg32 = seg.astype(jnp.int32)
        o = flash_attention(
            qt, kt, vt, segment_ids=SegmentIds(q=seg32, kv=seg32),
            causal=True, sm_scale=scale, block_sizes=bs,
        )
        return o.transpose(0, 2, 1, 3)

    return fn


def _impls(B, T, H, Hkv, D, sm_scale):
    rep = H // Hkv
    scale = float(1.0 / np.sqrt(D) if sm_scale is None else sm_scale)
    impls = {
        "rule": _splash_fn(seqlib._splash_block_sizes(T), scale),
        "legacy@512": _legacy_fn(scale, rep),
    }
    # one edge everywhere; a whole-T tile (2048, 4096) does not fit VMEM
    for edge in (512, 1024, 256):
        for fused in (True, False):
            for compute in sorted({min(512, edge), 256}, reverse=True):
                name = f"splash@{edge}/c{compute}/{'fused' if fused else 'two-pass'}"
                impls[name] = _splash_fn(_tiles(edge, edge, compute, fused), scale)
    # long q tiles over short k/v tiles and the reverse, single pass
    for bq, bkv in ((1024, 512), (2048, 512), (512, 1024), (512, 2048), (2048, 1024), (1024, 2048)):
        impls[f"splash@q{bq}xkv{bkv}/c512/fused"] = _splash_fn(
            _tiles(bq, bkv, 512, True), scale
        )
    impls["splash@1024/c1024/fused"] = _splash_fn(_tiles(1024, 1024, 1024, True), scale)
    # operands transposed in HBM (head dim 64 fills half a 128-lane row)
    for seq_minor in ("k", "v", "kv", "qkv"):
        impls[f"splash@1024/c512/fused/{seq_minor}-seq-minor"] = _splash_fn(
            _tiles(1024, 1024, 512, True, seq_minor), scale
        )
    if sm_scale is None and rep == 1:  # the plain-jnp tiled path, for scale
        impls["blockwise"] = functools.partial(seqlib.blockwise_attention, causal=True)
    return impls


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(os.path.dirname(__file__), "..", "bench_flash.json"))
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--only", default="", help="comma-separated row names; all if empty")
    args = ap.parse_args()
    only = set(filter(None, args.only.split(",")))
    out = {
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "warmup": WARMUP,
        "iters": ITERS,
        "rows": [],
    }
    for shape_name in args.shapes.split(","):
        B, T, H, Hkv, D, sm_scale = SHAPES[shape_name]
        q, k, v, q_pos, seg = _inputs(B, T, H, Hkv, D)
        for name, fn in _impls(B, T, H, Hkv, D, sm_scale).items():
            if only and name not in only:
                continue
            row = {"shape": shape_name, "name": name, "dims": [B, T, H, Hkv, D]}
            try:
                row["fwd_ms"] = round(_time(jax.jit(fn), q, k, v, q_pos, seg), 3)

                def loss(q_, k_, v_, fn=fn):
                    return jnp.sum(fn(q_, k_, v_, q_pos, seg).astype(jnp.float32))

                grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                row["fwdbwd_ms"] = round(_time(grad, q, k, v), 3)
                row["bwd_ms"] = round(row["fwdbwd_ms"] - row["fwd_ms"], 3)
            except Exception as e:  # noqa: BLE001 — record the failure, keep rows
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "w") as f:  # after every row: a crash keeps the rest
                json.dump(out, f, indent=1)
    print("wrote", os.path.normpath(args.out))


if __name__ == "__main__":
    main()
