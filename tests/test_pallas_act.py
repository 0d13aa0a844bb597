"""Fused act-step Pallas kernel (ISSUE 16): numerical pin against the XLA
act path in interpreter mode on CPU, plus the dispatch contract —
``make_act_fn`` must hand back the fused path only when asked AND in scope,
and the fallback must be the literal ``family.act``. Real-TPU execution is
covered by chip_smoke.py's ``kernels`` phase."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import small_config
from tpu_rl.models import cells
from tpu_rl.models.families import build_family
from tpu_rl.models.quant import make_act_fn
from tpu_rl.ops.pallas_act import (
    act_fits_vmem,
    fused_act_step,
    make_fused_act,
)


@pytest.fixture
def act_setup(rng):
    cfg = small_config(hidden_size=32, obs_shape=(6,), action_space=3)
    family = build_family(cfg)
    params = family.init_params(jax.random.key(0), seq_len=cfg.seq_len)
    B = 16
    obs = jnp.asarray(rng.normal(size=(B, 6)).astype(np.float32))
    hw, cw = family.carry_widths
    h = jnp.asarray(rng.normal(size=(B, hw)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(B, cw)).astype(np.float32))
    return cfg, family, params, obs, h, c


class TestFusedActParity:
    def test_kernel_matches_xla_act(self, act_setup):
        cfg, family, params, obs, h, c = act_setup
        key = jax.random.key(11)
        a_x, logits_x, lp_x, h2_x, c2_x = family.act(params, obs, h, c, key)
        cells.set_pallas_mode("interpret")
        try:
            fused = make_fused_act(family)
            assert fused is not None
            a_k, logits_k, lp_k, h2_k, c2_k = fused(params, obs, h, c, key)
        finally:
            cells.set_pallas_mode("auto")
        np.testing.assert_allclose(
            np.asarray(logits_k), np.asarray(logits_x), atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(h2_k), np.asarray(h2_x), atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(c2_k), np.asarray(c2_x), atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(lp_k), np.asarray(lp_x), atol=1e-5
        )
        # identical PRNG key + pinned logits => the SAME sampled actions
        np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_x))
        assert a_k.shape == a_x.shape and a_k.dtype == a_x.dtype

    def test_logits_are_normalized(self, act_setup):
        _, family, params, obs, h, c = act_setup
        logits, _h2, _c2 = fused_act_step(
            params["actor"], obs, h, c, interpret=True
        )
        np.testing.assert_allclose(
            np.exp(np.asarray(logits)).sum(-1), 1.0, atol=1e-5
        )

    def test_kernel_under_jit(self, act_setup):
        """The serving step jits the fused act; the interpret-mode kernel
        must survive tracing (shape-polymorphic failures would surface at
        warmup, inside the recompile ratchet's window)."""
        _, family, params, obs, h, c = act_setup
        cells.set_pallas_mode("interpret")
        try:
            fused = jax.jit(make_fused_act(family))
            a, logits, lp, h2, c2 = fused(
                params, obs, h, c, jax.random.key(0)
            )
            jax.block_until_ready(logits)
        finally:
            cells.set_pallas_mode("auto")
        assert logits.shape == (obs.shape[0], family.n_actions)


class TestDispatch:
    def test_make_act_fn_xla_is_family_act(self, act_setup):
        cfg, family, *_ = act_setup
        assert make_act_fn(cfg, family) is family.act

    def test_make_act_fn_pallas_wraps(self, act_setup):
        cfg, family, *_ = act_setup
        act = make_act_fn(cfg.replace(act_kernel="pallas"), family)
        assert act is not family.act

    def test_out_of_scope_family_falls_back(self):
        cfg = small_config(
            algo="PPO-Continuous", is_continuous=True, action_space=2
        )
        family = build_family(cfg)
        assert make_fused_act(family) is None
        assert make_act_fn(cfg.replace(act_kernel="pallas"), family) \
            is family.act

    def test_cpu_auto_mode_falls_back_to_xla_numerics(self, act_setup):
        """On a CPU backend in auto mode the wrapper must route through
        family.act (no interpret-mode slowness in production), still
        producing identical outputs."""
        cfg, family, params, obs, h, c = act_setup
        act = make_act_fn(cfg.replace(act_kernel="pallas"), family)
        key = jax.random.key(5)
        got = act(params, obs, h, c, key)
        want = family.act(params, obs, h, c, key)
        for g, w in zip(got, want, strict=True):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_vmem_gate(self):
        assert act_fits_vmem(256, 4, 256, 2)
        assert not act_fits_vmem(100_000, 4, 2048, 2)
