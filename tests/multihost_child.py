"""Subprocess body for the real N-process ``jax.distributed`` tests.

Run as: ``python tests/multihost_child.py <process_id> <coordinator_port>
[<num_processes>=2]``. Each process contributes 2 virtual CPU devices -> a
``2N``-device global mesh. Validates, with ACTUAL cross-process collectives
(gloo):

1. ``tpu_rl.parallel.multihost.init_multihost`` brings up the runtime;
2. the DP learner feed: ``host_local_batch_to_global`` under ``P("data")``
   (contiguous-rows assumption) + ``make_parallel_train_step`` over the
   global mesh == plain single-device jit on the same global batch;
3. the sequence-parallel feed: ``P("data","seq")`` placement (non-batch
   index dims preserved — the round-2 fix) + ring attention whose K/V
   rotation crosses the process boundary == single-device full attention;
4. the PRODUCTION service feed: ``LearnerService._to_batch`` with the
   multihost placement armed (``_setup_multihost_feed``) places this host's
   raw shm-style rows as the correct slice of the global array — the same
   train step through the service's own batching code == the oracle.

Not collected by pytest (no ``test_`` prefix); driven by
``tests/test_multihost.py``.
"""

from __future__ import annotations

import os
import sys


def main() -> None:
    pid = int(sys.argv[1])
    port = sys.argv[2]
    nprocs = int(sys.argv[3]) if len(sys.argv) > 3 else 2
    # CPU backend, 2 virtual devices per process — both set before jax
    # imports (the parent strips any inherited XLA_FLAGS).
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    import jax

    from tpu_rl.parallel.multihost import init_multihost, is_multihost

    init_multihost(
        coordinator=f"127.0.0.1:{port}", num_processes=nprocs, process_id=pid
    )
    assert is_multihost(), "process_count must be > 1 after init_multihost"
    assert len(jax.devices()) == 2 * nprocs, jax.devices()
    assert len(jax.local_devices()) == 2

    import jax.numpy as jnp
    import numpy as np

    from tpu_rl.algos.registry import get_algo
    from tpu_rl.config import Config
    from tpu_rl.parallel.dp import (
        make_parallel_train_step,
        make_sp_train_step,
        replicate,
    )
    from tpu_rl.parallel.mesh import batch_sharding, make_mesh
    from tpu_rl.parallel.multihost import host_local_batch_to_global
    from tpu_rl.types import BATCH_FIELDS, Batch

    # ------------- 2. DP path: global batch 8 rows, 8/nprocs per host ------
    cfg = Config.from_dict(
        dict(
            algo="IMPALA", hidden_size=16, seq_len=5, batch_size=8,
            obs_shape=(4,), action_space=2,
        )
    )
    family, state, train_step = get_algo(cfg.algo).build(cfg, jax.random.key(0))

    rng = np.random.default_rng(0)  # same seed both hosts -> same global batch
    zb = Batch.zeros(
        cfg.batch_size, cfg.seq_len, cfg.obs_shape, cfg.action_space,
        cfg.hidden_size,
    )
    global_batch = zb.replace(
        obs=jnp.asarray(rng.normal(size=zb.obs.shape).astype(np.float32)),
        act=jnp.asarray(
            rng.integers(0, 2, size=zb.act.shape).astype(np.float32)
        ),
        rew=jnp.asarray(rng.normal(size=zb.rew.shape).astype(np.float32) * 0.1),
        log_prob=jnp.full(zb.log_prob.shape, -float(np.log(2.0))),
    )
    key = jax.random.key(7)

    # Single-device oracle on the full global batch (local jit, cpu:0).
    s_ref, m_ref = jax.jit(train_step)(state, global_batch, key)
    loss_ref = float(np.asarray(m_ref["loss"]))

    # DP over the 2N-device global mesh, each host feeding its own rows.
    mesh = make_mesh(2 * nprocs)
    pstep = make_parallel_train_step(train_step, mesh, cfg)
    rows = cfg.batch_size // nprocs
    local_rows = {
        f: np.asarray(getattr(global_batch, f))[pid * rows:(pid + 1) * rows]
        for f in BATCH_FIELDS
    }
    fed = Batch(**host_local_batch_to_global(local_rows, batch_sharding(mesh)))
    _f2, state2, _t2 = get_algo(cfg.algo).build(cfg, jax.random.key(0))
    state2 = replicate(state2, mesh)
    key_r = replicate(key, mesh)
    s_dp, m_dp = pstep(state2, fed, key_r)
    loss_dp = float(np.asarray(m_dp["loss"]))
    assert abs(loss_dp - loss_ref) < 1e-4 * max(1.0, abs(loss_ref)), (
        loss_dp, loss_ref,
    )

    # ---------- 3. Seq-sharded path: (data=nprocs, seq=2) mesh, ring -------
    from tpu_rl.parallel import make_sp_mesh

    n_data, n_seq = nprocs, 2  # uses every device: n_data * n_seq == 2N
    cfg_sp = Config.from_dict(
        dict(
            algo="PPO", model="transformer", attention_impl="ring",
            hidden_size=16, n_heads=2, n_layers=1, seq_len=8,
            batch_size=max(4, n_data),
            obs_shape=(4,), action_space=2, mesh_data=n_data, mesh_seq=n_seq,
        )
    )
    sp_mesh = make_sp_mesh(n_data, n_seq)
    fam_sp, state_sp, step_sp = get_algo("PPO").build(
        cfg_sp, jax.random.key(1), mesh=sp_mesh
    )
    rng2 = np.random.default_rng(1)
    B, S = cfg_sp.batch_size, cfg_sp.seq_len
    firsts = np.zeros((B, S, 1), np.float32)
    firsts[:, 0] = 1.0
    gb = dict(
        obs=rng2.normal(size=(B, S, 4)).astype(np.float32),
        act=rng2.integers(0, 2, size=(B, S, 1)).astype(np.float32),
        rew=(rng2.normal(size=(B, S, 1)) * 0.1).astype(np.float32),
        logits=np.zeros((B, S, 2), np.float32),
        log_prob=np.full((B, S, 1), -float(np.log(2.0)), np.float32),
        is_fir=firsts,
        hx=np.zeros((B, S, 1), np.float32),
        cx=np.zeros((B, S, 1), np.float32),
    )

    # Single-device oracle: same params, full attention.
    cfg_full = cfg_sp.replace(attention_impl="full", mesh_data=1, mesh_seq=1)
    _ff, state_full, step_full = get_algo("PPO").build(
        cfg_full, jax.random.key(1)
    )
    key2 = jax.random.key(9)
    _sf, m_full = jax.jit(step_full)(
        state_full, Batch.from_mapping(gb), key2
    )
    loss_full = float(np.asarray(m_full["loss"]))

    from jax.sharding import NamedSharding, PartitionSpec as P
    from tpu_rl.parallel.sequence import DATA_AXIS, SEQ_AXIS

    sp_sharding = NamedSharding(sp_mesh, P(DATA_AXIS, SEQ_AXIS))
    # Host rows of the (data, seq)-sharded batch; the trailing (seq) dim
    # stays global-sized locally and is sliced per device by
    # host_local_batch_to_global (the round-2 fix under test).
    sp_rows = cfg_sp.batch_size // nprocs
    local_sp = {
        f: v[pid * sp_rows:(pid + 1) * sp_rows] for f, v in gb.items()
    }
    fed_sp = Batch(**host_local_batch_to_global(local_sp, sp_sharding))
    pstep_sp = make_sp_train_step(step_sp, sp_mesh, cfg_sp)
    state_sp = replicate(state_sp, sp_mesh)
    s_sp, m_sp = pstep_sp(state_sp, fed_sp, replicate(key2, sp_mesh))
    loss_sp = float(np.asarray(m_sp["loss"]))
    assert abs(loss_sp - loss_full) < 5e-4 * max(1.0, abs(loss_full)), (
        loss_sp, loss_full,
    )

    # ------- 4. Production service feed: LearnerService._to_batch ---------
    # The service arms multihost placement in run() via _setup_multihost_feed
    # (jax.process_count() > 1); drive the same code path directly: raw
    # host-local rows (what its shm store consume() yields on this host) must
    # place as THIS host's slice of the global batch, and the DP step through
    # the service's own batching must match the single-device oracle.
    from tpu_rl.runtime.learner_service import LearnerService

    svc = LearnerService(cfg, handles=None, model_port=0)
    svc._place_global = None
    svc._setup_multihost_feed(batch_sharding(mesh))
    assert svc._place_global is not None, "service must arm multihost feed"
    fed_svc = svc._to_batch(local_rows)
    _f3, state3, _t3 = get_algo(cfg.algo).build(cfg, jax.random.key(0))
    s_svc, m_svc = pstep(replicate(state3, mesh), fed_svc, replicate(key, mesh))
    loss_svc = float(np.asarray(m_svc["loss"]))
    assert abs(loss_svc - loss_ref) < 1e-4 * max(1.0, abs(loss_ref)), (
        loss_svc, loss_ref,
    )

    print(
        f"MULTIHOST_CHILD_OK pid={pid} nprocs={nprocs} loss_dp={loss_dp:.6f} "
        f"loss_sp={loss_sp:.6f} loss_svc={loss_svc:.6f}",
        flush=True,
    )


if __name__ == "__main__":
    main()
