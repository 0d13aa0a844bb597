"""Data-parallel train-step compilation (GSPMD).

The idiomatic TPU answer to the reference's single-device update loop
(``/root/reference/agents/learner_module/*/learning.py``): jit the pure
``train_step(state, batch, key)`` with the batch sharded over the mesh's
``"data"`` axis and everything else replicated. XLA partitions the program and
inserts the cross-chip gradient all-reduce (``psum`` over ICI) where the loss
reduces over the batch dimension — no hand-written collectives, per the GSPMD
recipe (SNIPPETS.md). Train state is donated so parameter buffers are updated
in place on device.

Per-batch global statistics (e.g. V-MPO's top-half advantage selection over
the whole batch, ``/root/reference/agents/learner_module/v_mpo/learning.py:60-64``)
remain correct under sharding because GSPMD lowers ``top_k``/``sort`` over a
sharded dimension with the required cross-device exchanges.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_rl.config import Config
from tpu_rl.parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    check_divisible,
    replicated,
)
from tpu_rl.types import Batch


def make_parallel_train_step(
    train_step: Callable, mesh, cfg: Config | None = None, chain: int = 1
) -> Callable:
    """Wrap a pure ``train_step(state, batch, key) -> (state, metrics)`` in a
    jit with DP shardings. Returns the compiled callable.

    ``chain > 1`` compiles K sequential optimizer updates per dispatched
    program: the batch gains a leading ``chain`` axis (one slice per update,
    sharded ``P(None, "data")``), an inner ``lax.scan`` folds a fresh RNG key
    per update, and the last update's metrics are returned. Per-update math is
    identical to K separate calls; what changes is that fixed per-dispatch
    host overhead is paid once per K updates instead of per update."""
    if cfg is not None:
        check_divisible(cfg.batch_size, mesh)

    # Register the mesh ONLY while this step traces, so LSTM unrolls emit the
    # fused Pallas kernel as a shard_map island over the data axis (the
    # Mosaic call cannot be auto-partitioned by GSPMD) — without leaking the
    # mesh into unrelated traces in the same process.
    from tpu_rl.models import cells

    def traced_step(state, batch, key):
        prev = cells._DATA_MESH
        cells.set_data_mesh(mesh)
        try:
            if chain == 1:
                return train_step(state, batch, key)

            def body(st, xs):
                b, i = xs
                st, m = train_step(st, b, jax.random.fold_in(key, i))
                return st, m

            state, ms = jax.lax.scan(
                body, state, (batch, jnp.arange(chain))
            )
            diag = ms.pop("diag", None)
            out = jax.tree.map(lambda x: x[-1], ms)
            if "nonfinite-updates" in ms:
                # Guard-skip counts are per-update; summing over the chain
                # axis keeps the dispatched program's count exact (the other
                # metrics stay last-update snapshots).
                out["nonfinite-updates"] = jnp.sum(ms["nonfinite-updates"])
            if diag is not None:
                # Learning-dynamics diag is ACCUMULATED, not snapshotted:
                # row channels from every chained update flatten to
                # (chain*B,) — aligned with the learner's flattened per-row
                # staleness — and scalars sum, with the update count riding
                # along so the accumulator can renormalize (obs/learn.py).
                out["diag"] = {
                    "rows": {
                        k: v.reshape(-1) for k, v in diag["rows"].items()
                    },
                    "scalars": {
                        k: jnp.sum(v) for k, v in diag["scalars"].items()
                    },
                    "n-updates": jnp.float32(chain),
                }
            return state, out
        finally:
            cells.set_data_mesh(prev)

    rs = replicated(mesh)
    bs = (
        batch_sharding(mesh)
        if chain == 1
        else NamedSharding(mesh, P(None, DATA_AXIS))
    )
    return jax.jit(
        traced_step,
        # Pytree-prefix shardings: state & key replicated, every batch leaf
        # sharded along its leading dim (update axis first when chained).
        in_shardings=(rs, bs, rs),
        out_shardings=(rs, rs),
        donate_argnums=(0,),
    )


def make_sp_train_step(train_step: Callable, mesh, cfg: Config | None = None):
    """Compile a train step over a 2-D (data, seq) mesh: batch leaves are
    sharded on BOTH leading dims — batch over ``"data"``, time over
    ``"seq"`` — state/key replicated. The model's ring/Ulysses attention
    (a shard_map island inside this GSPMD program) keeps K/V sharded; the
    cheap loss scans (GAE/V-trace over (B, T) scalars) are resharded by XLA
    as needed. This is the long-context training entry point."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_rl.parallel.sequence import DATA_AXIS, SEQ_AXIS

    if cfg is not None:
        if cfg.batch_size % mesh.shape[DATA_AXIS] != 0:
            raise ValueError("batch_size not divisible by data axis")
        if cfg.seq_len % mesh.shape[SEQ_AXIS] != 0:
            raise ValueError("seq_len not divisible by seq axis")
    bs = NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS))
    rs = NamedSharding(mesh, P())
    return jax.jit(
        train_step,
        in_shardings=(rs, bs, rs),
        out_shardings=(rs, rs),
        donate_argnums=(0,),
    )


def shard_chained_batch(batches: Sequence[Batch], mesh) -> Batch:
    """Stack K per-update batches on a leading update axis and place them for
    a ``make_parallel_train_step(chain=K)`` program: update axis replicated
    (scan consumes it sequentially), batch axis sharded on ``"data"``. The
    single source of the chained-batch layout contract."""
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    return jax.device_put(stacked, NamedSharding(mesh, P(None, DATA_AXIS)))


def shard_batch(batch: Batch, mesh) -> Batch:
    """Host numpy/jax batch -> device-sharded batch (each chip gets its slice
    of the leading dim). This is the HOST->DEVICE boundary the reference
    crosses with ``.to(device)`` per tensor (``utils/utils.py:101-103``)."""
    return jax.device_put(batch, batch_sharding(mesh))


def replicate(tree: Any, mesh) -> Any:
    """Replicate a host pytree (train state, RNG key) onto every mesh device.

    On a multi-process mesh ``jax.device_put`` refuses committed host-local
    arrays (the sharding spans non-addressable devices); route through an
    SPMD identity jit with global ``out_shardings`` instead — valid because
    every host holds identical values by construction (same seed or the same
    restored checkpoint; ``tests/multihost_child.py`` exercises this with a
    real 2-process runtime)."""
    rs = replicated(mesh)
    local = jax.process_index()
    if all(d.process_index == local for d in mesh.devices.flat):
        return jax.device_put(tree, rs)
    return jax.jit(lambda t: t, out_shardings=rs)(tree)
