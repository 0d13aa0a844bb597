"""Fused Pallas LSTM kernel: forward + gradient equivalence against the
lax.scan path, in interpreter mode on CPU (real-TPU execution is covered by
chip_smoke.py's ``kernels`` phase)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_rl.models import cells
from tpu_rl.models.cells import LSTMCell


@pytest.fixture
def lstm_setup(rng):
    B, S, IN, H = 4, 6, 5, 16
    cell = LSTMCell(H)
    x = jnp.asarray(rng.normal(size=(B, S, IN)).astype(np.float32))
    firsts = np.zeros((B, S, 1), np.float32)
    firsts[:, 0] = 1.0
    firsts[1, 3] = 1.0  # mid-sequence reset in one row
    firsts = jnp.asarray(firsts)
    h0 = jnp.asarray(rng.normal(size=(B, H)).astype(np.float32))
    c0 = jnp.asarray(rng.normal(size=(B, H)).astype(np.float32))
    params = cell.init(jax.random.key(0), (h0, c0), x[:, 0])
    return cell, params, x, firsts, (h0, c0)


def _unroll(cell, params, x, carry0, firsts, reset=True):
    return cell.apply(
        params, x, carry0, firsts, reset, method=LSTMCell.unroll
    )


@pytest.mark.parametrize("reset", [True, False])
def test_kernel_matches_scan_forward(lstm_setup, reset):
    cell, params, x, firsts, carry0 = lstm_setup
    cells.set_pallas_mode("off")
    try:
        (hf, cf), hs_scan = _unroll(cell, params, x, carry0, firsts, reset)
        cells.set_pallas_mode("interpret")
        (hk, ck), hs_kern = _unroll(cell, params, x, carry0, firsts, reset)
    finally:
        cells.set_pallas_mode("auto")
    np.testing.assert_allclose(np.asarray(hs_kern), np.asarray(hs_scan), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hf), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ck), np.asarray(cf), atol=1e-5)


def test_kernel_gradients_match_scan(lstm_setup):
    cell, params, x, firsts, carry0 = lstm_setup

    def loss(params, x, carry0, mode):
        cells.set_pallas_mode(mode)
        try:
            (hN, cN), hs = _unroll(cell, params, x, carry0, firsts, True)
        finally:
            cells.set_pallas_mode("auto")
        # touch everything: per-step outputs and both finals
        return (hs**2).sum() + (hN * 0.5).sum() + (cN * 0.25).sum()

    g_scan = jax.grad(loss, argnums=(0, 1, 2))(params, x, carry0, "off")
    g_kern = jax.grad(loss, argnums=(0, 1, 2))(params, x, carry0, "interpret")
    flat_s = jax.tree_util.tree_leaves(g_scan)
    flat_k = jax.tree_util.tree_leaves(g_kern)
    assert len(flat_s) == len(flat_k)
    for a, b in zip(flat_k, flat_s, strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_full_train_step_with_kernel(rng):
    """End-to-end: the PPO train step runs with the kernel active and matches
    the scan path numerically."""
    from tests.conftest import small_config
    from tpu_rl.algos.registry import get_algo
    from tpu_rl.types import Batch

    cfg = small_config()
    _fam, state, train_step = get_algo("PPO").build(cfg, jax.random.key(0))
    zb = Batch.zeros(
        cfg.batch_size, cfg.seq_len, cfg.obs_shape, cfg.action_space,
        cfg.hidden_size,
    )
    batch = zb.replace(
        obs=jnp.asarray(
            rng.normal(size=zb.obs.shape).astype(np.float32)
        ),
        act=jnp.asarray(
            rng.integers(0, 2, size=zb.act.shape).astype(np.float32)
        ),
        log_prob=jnp.full(zb.log_prob.shape, -0.69),
    )
    key = jax.random.key(1)
    cells.set_pallas_mode("off")
    try:
        s1, m1 = train_step(state, batch, key)
        cells.set_pallas_mode("interpret")
        s2, m2 = train_step(state, batch, key)
    finally:
        cells.set_pallas_mode("auto")
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(s1.params),
        jax.tree_util.tree_leaves(s2.params),
        strict=True,
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_dp_mesh_shard_map_island(devices, rng):
    """The kernel runs as a shard_map island inside the data-parallel jitted
    train step (8-device mesh, interpret mode) and matches the scan path."""
    from tests.conftest import small_config
    from tests.test_parallel import _fake_batch
    from tpu_rl.algos.registry import get_algo
    from tpu_rl.parallel import make_mesh, make_parallel_train_step, replicate, shard_batch

    cfg = small_config(batch_size=16)
    fam, state0, train_step = get_algo("PPO").build(cfg, jax.random.key(0))
    batch = _fake_batch(cfg, fam)
    key = jax.random.key(1)

    cells.set_pallas_mode("off")
    try:
        s_ref, m_ref = jax.jit(train_step)(state0, batch, key)

        cells.set_pallas_mode("interpret")
        mesh = make_mesh(8)
        pstep = make_parallel_train_step(train_step, mesh, cfg)
        state = replicate(state0, mesh)
        s_mesh, m_mesh = pstep(state, shard_batch(batch, mesh), replicate(key, mesh))
    finally:
        cells.set_pallas_mode("auto")
        cells.set_data_mesh(None)
    np.testing.assert_allclose(
        float(m_ref["loss"]), float(m_mesh["loss"]), rtol=1e-5
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(s_ref.params),
        jax.tree_util.tree_leaves(s_mesh.params),
        strict=True,
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_batch_tiled_grid_matches_scan(rng, monkeypatch):
    """With a VMEM budget too small for the whole batch, the kernel must run
    as a multi-tile Pallas grid and still match the scan exactly."""
    import tpu_rl.ops.pallas_lstm as pk

    B, S, IN, H = 32, 6, 5, 16
    cell = LSTMCell(H)
    x = jnp.asarray(rng.normal(size=(B, S, IN)).astype(np.float32))
    firsts = np.zeros((B, S, 1), np.float32)
    firsts[:, 0] = 1.0
    firsts[1, 3] = 1.0
    firsts = jnp.asarray(firsts)
    carry0 = (
        jnp.asarray(rng.normal(size=(B, H)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(B, H)).astype(np.float32)),
    )
    params = cell.init(jax.random.key(0), carry0, x[:, 0])
    # Budget fits an 8-row tile but not 16 or the whole batch -> grid of 4,
    # for BOTH the forward kernel and the fused backward kernel.
    monkeypatch.setattr(pk, "_VMEM_BUDGET_BYTES", 480000)
    assert pk.batch_tile(B, S, H) == 8
    assert pk.bwd_batch_tile(B, S, H) == 8

    def loss(params, x, carry0, mode):
        cells.set_pallas_mode(mode)
        try:
            (hN, cN), hs = _unroll(cell, params, x, carry0, firsts, True)
        finally:
            cells.set_pallas_mode("auto")
        return (hs**2).sum() + (hN * 0.5).sum() + (cN * 0.25).sum()

    v_scan, g_scan = jax.value_and_grad(loss, argnums=(0, 1))(
        params, x, carry0, "off"
    )
    v_kern, g_kern = jax.value_and_grad(loss, argnums=(0, 1))(
        params, x, carry0, "interpret"
    )
    np.testing.assert_allclose(float(v_kern), float(v_scan), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_kern), jax.tree_util.tree_leaves(g_scan),
        strict=True,
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_vmem_budget_fallback():
    from tpu_rl.ops.pallas_lstm import batch_tile, fits_vmem

    assert fits_vmem(128, 5, 64)
    assert not fits_vmem(128, 4096, 256)  # long-context: transformer's job
    # The wide bench workload tiles instead of falling back...
    bt = batch_tile(1024, 16, 1024)
    assert bt is not None and 1024 % bt == 0 and bt % 8 == 0
    # ...but a long-context shape whose only fitting tiles are degenerate
    # (< 8 rows: serialized over the grid, worse than the scan) must refuse,
    assert batch_tile(128, 4096, 256) is None
    # ...as must a workload whose weights alone bust VMEM.
    assert batch_tile(8, 4096, 2048) is None


def test_mixed_dot_rejects_non_matrix_operands():
    """mixed_dot's custom VJP transposes residuals with .T — valid for
    matrices only. Batched or 1-D operands must fail loudly at the primal
    (a silent wrong-gradient contraction is the failure mode)."""
    from tpu_rl.ops.pallas_lstm import mixed_dot

    a2 = jnp.ones((4, 8))
    b2 = jnp.ones((8, 3))
    out = mixed_dot(a2, b2)  # the supported shape still works
    assert out.shape == (4, 3) and out.dtype == jnp.float32
    # gradients flow through the 2-D path
    g = jax.grad(lambda a: mixed_dot(a, b2).sum())(a2)
    assert g.shape == a2.shape
    with pytest.raises(ValueError, match="2-D"):
        mixed_dot(jnp.ones((2, 4, 8)), jnp.ones((8, 3)))  # batched lhs
    with pytest.raises(ValueError, match="2-D"):
        mixed_dot(jnp.ones((8,)), b2)  # vector lhs
    with pytest.raises(ValueError, match="2-D"):
        jax.jit(mixed_dot)(a2, jnp.ones((2, 8, 3)))  # under tracing too
