"""Mesh data-parallel learner tests on the virtual 8-device CPU mesh
(SURVEY.md §4: substitutes for the reference's test-on-a-real-cluster
non-strategy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import small_config
from tpu_rl.algos.registry import get_algo
from tpu_rl.parallel import (
    make_mesh,
    make_parallel_train_step,
    replicate,
    shard_batch,
    shard_chained_batch,
)
from tpu_rl.types import Batch


def _fake_batch(cfg, family, seed=0):
    rng = np.random.default_rng(seed)
    b = Batch.zeros(
        cfg.batch_size,
        cfg.seq_len,
        cfg.obs_shape,
        cfg.action_space,
        cfg.hidden_size,
        continuous=family.continuous,
    )
    def noise(x):
        return jnp.asarray(rng.normal(size=x.shape).astype(np.float32))
    obs = noise(b.obs)
    if family.continuous:
        act = jnp.tanh(noise(b.act))
        log_prob = -jnp.ones_like(b.log_prob)
    else:
        act = jnp.asarray(
            rng.integers(0, cfg.action_space, size=b.act.shape).astype(np.float32)
        )
        log_prob = jnp.full_like(b.log_prob, -np.log(cfg.action_space))
    return b.replace(obs=obs, act=act, rew=noise(b.rew) * 0.1, log_prob=log_prob)


@pytest.mark.parametrize(
    "algo", ["PPO", "PPO-Continuous", "IMPALA", "V-MPO", "SAC", "SAC-Continuous"]
)
def test_dp_step_runs_on_8dev_mesh(algo):
    cfg = small_config(algo=algo, batch_size=8)
    family, state, train_step = get_algo(algo).build(cfg, jax.random.key(0))
    mesh = make_mesh(8)
    pstep = make_parallel_train_step(train_step, mesh, cfg)
    batch = shard_batch(_fake_batch(cfg, family), mesh)
    state = replicate(state, mesh)
    state, metrics = pstep(state, batch, replicate(jax.random.key(1), mesh))
    assert int(state.step) == 1
    for v in jax.tree_util.tree_leaves(metrics):
        assert np.isfinite(np.asarray(v)).all()


@pytest.mark.parametrize("algo", ["PPO", "V-MPO", "SAC"])
def test_dp_matches_single_device(algo):
    """Sharded-over-8 must be numerically equivalent (fp tolerance) to the
    unsharded step: GSPMD only changes layout, not math. V-MPO is the hard
    case — its top-half advantage selection reduces over the GLOBAL batch
    (reference ``v_mpo/learning.py:60-64``), so GSPMD must insert cross-chip
    exchanges for the sort; SAC exercises the separate-state flavor."""
    cfg = small_config(algo=algo, batch_size=8)
    family, state, train_step = get_algo(algo).build(cfg, jax.random.key(0))
    batch = _fake_batch(cfg, family)
    key = jax.random.key(1)

    ref_state, ref_metrics = jax.jit(train_step)(state, batch, key)

    mesh = make_mesh(8)
    _, state2, _ = get_algo(algo).build(cfg, jax.random.key(0))
    pstep = make_parallel_train_step(train_step, mesh, cfg)
    dp_state, dp_metrics = pstep(
        replicate(state2, mesh), shard_batch(batch, mesh), replicate(key, mesh)
    )

    np.testing.assert_allclose(
        float(ref_metrics["loss"]), float(dp_metrics["loss"]), rtol=2e-4, atol=2e-5
    )
    def leaves(s):
        return jax.tree_util.tree_leaves(
            s.params
            if hasattr(s, "params")
            else (s.actor_params, s.critic_params, s.target_critic_params,
                  s.log_alpha)
        )

    for a, b in zip(leaves(ref_state), leaves(dp_state), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5)


def test_host_local_batch_to_global_single_process(devices):
    """On one host, host-local placement must equal plain shard_batch."""
    from tpu_rl.parallel.multihost import host_local_batch_to_global, is_multihost
    from tpu_rl.parallel.mesh import batch_sharding

    assert not is_multihost()
    cfg = small_config(algo="PPO", batch_size=16)
    family, _, _ = get_algo("PPO").build(cfg, jax.random.key(0))
    batch = _fake_batch(cfg, family)
    mesh = make_mesh(8)
    sharding = batch_sharding(mesh)
    host_np = {"obs": np.asarray(batch.obs), "rew": np.asarray(batch.rew)}
    placed = host_local_batch_to_global(host_np, sharding)
    want = shard_batch(batch, mesh)
    np.testing.assert_array_equal(np.asarray(placed["obs"]), np.asarray(want.obs))
    np.testing.assert_array_equal(np.asarray(placed["rew"]), np.asarray(want.rew))
    assert placed["obs"].sharding.is_equivalent_to(want.obs.sharding, 3)


@pytest.mark.parametrize("algo", ["IMPALA", "SAC"])
def test_chained_step_matches_sequential(algo):
    """chain=K compiles K updates per dispatch (dp.py
    make_parallel_train_step): the result must equal K sequential
    unchained updates run on the per-update batches with the same folded
    keys — chaining changes dispatch granularity, never math."""
    K = 3
    cfg = small_config(algo=algo, batch_size=8)
    family, state, train_step = get_algo(algo).build(cfg, jax.random.key(0))
    batches = [_fake_batch(cfg, family, seed=s) for s in range(K)]
    key = jax.random.key(7)

    ref_state = state
    step1 = jax.jit(train_step)
    last_metrics = None
    for i, b in enumerate(batches):
        ref_state, last_metrics = step1(ref_state, b, jax.random.fold_in(key, i))

    mesh = make_mesh(4)
    _, state2, _ = get_algo(algo).build(cfg, jax.random.key(0))
    cstep = make_parallel_train_step(train_step, mesh, cfg, chain=K)
    c_state, c_metrics = cstep(
        replicate(state2, mesh),
        shard_chained_batch(batches, mesh),
        replicate(key, mesh),
    )

    np.testing.assert_allclose(
        float(last_metrics["loss"]), float(c_metrics["loss"]), rtol=2e-4, atol=2e-5
    )
    def leaves(s):
        return jax.tree_util.tree_leaves(
            s.params
            if hasattr(s, "params")
            else (s.actor_params, s.critic_params, s.target_critic_params,
                  s.log_alpha)
        )

    for a, b in zip(leaves(ref_state), leaves(c_state), strict=True):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
        )


def test_batch_not_divisible_raises():
    cfg = small_config(batch_size=6)
    mesh = make_mesh(4)
    with pytest.raises(ValueError, match="not divisible"):
        make_parallel_train_step(lambda s, b, k: (s, {}), mesh, cfg)
