"""Fused act-step kernel (Pallas/TPU) — the serving fast path's compute layer.

The serving hot path (``InferenceService._step_fn`` and the worker-local act)
runs ``DiscreteActorCritic.act`` as four separate XLA ops per flush: torso
Dense+relu, LSTM-cell step, logits head, log-softmax — each a kernel launch
that round-trips its (rows, H) activations through HBM. At serving batch
sizes (a bucket of 8..256 rows) those intermediates are tiny and the
launches + HBM hops dominate. This kernel fuses the whole act step into ONE
Pallas program: every weight matrix and every intermediate lives in VMEM,
the three matmuls feed the MXU back to back, and only (obs, h, c) in and
(log-softmax logits, h', c') out touch HBM.

Scope: the discrete LSTM actor-critic family only (PPO/IMPALA/V-MPO with the
MLP backbone) at float32 compute — exactly the family whose act step the
fleet benches. Everything else falls back to ``family.act``
(:func:`make_fused_act` returns None); the value head is skipped entirely
because the act contract discards it.

Dispatch honors :func:`tpu_rl.models.cells.set_pallas_mode`: ``"interpret"``
runs the kernel in the Pallas interpreter (CPU equivalence tests — the
parity pin in tests/test_pallas_act.py), ``"off"`` disables it, ``"auto"``/
``"force"`` use the compiled kernel on a TPU backend when the working set
fits VMEM. The act programs that take this path are single-device jits;
multi-device GSPMD programs (``InferenceReplica`` with
``inference_mesh_data > 1``) never ask for it (``models.quant.make_act_fn``):
the Mosaic custom call has no automatic SPMD partitioning rule.

Sampling and the carry-reset mask stay OUTSIDE the kernel, shared with the
XLA path, so a given (params, obs, key) produces the identical action from
either implementation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tpu_rl.ops.pallas_lstm import (
    _VMEM_BUDGET_BYTES,
    _block_bytes,
    _compiler_params,
)


def act_fits_vmem(rows: int, obs_dim: int, hidden: int, n_actions: int) -> bool:
    """Whole act step in one VMEM-resident program? (No grid: the serving
    batch is one tile, every operand is resident once.) Padded weight and
    activation blocks against the scoped-VMEM limit ``_compiler_params``
    requests, as in the LSTM kernel."""
    h4 = 4 * hidden
    weights = (
        _block_bytes(obs_dim, hidden)
        + 2 * _block_bytes(hidden, h4)
        + _block_bytes(hidden, n_actions)
        + 2 * _block_bytes(1, hidden)
        + _block_bytes(1, h4)
    )
    acts = (
        _block_bytes(rows, obs_dim)
        + 4 * _block_bytes(rows, hidden)  # h, c in; h2, c2 out
        + 2 * _block_bytes(rows, n_actions)  # raw + log-softmax
        + 4 * _block_bytes(rows, h4)  # x, z and gate temporaries
    )
    return weights + acts <= _VMEM_BUDGET_BYTES


def _act_kernel(
    obs_ref, wb_ref, bb_ref, wx_ref, bx_ref, wh_ref, wl_ref, bl_ref,
    h_ref, c_ref, logits_ref, h2_ref, c2_ref,
):
    """obs (B,D); torso wb (D,H) + bb (1,H); LSTM wx (H,4H) + bx (1,4H) +
    wh (H,4H); logits head wl (H,A) + bl (1,A); carry h/c (B,H).
    Outputs: log-softmax logits (B,A), h2/c2 (B,H). Biases are 2-D (1,·):
    sublane/lane-shaped operands, broadcast over rows inside the kernel."""
    H = wh_ref.shape[0]
    x = jnp.maximum(
        jnp.dot(obs_ref[:], wb_ref[:], preferred_element_type=jnp.float32)
        + bb_ref[:],
        0.0,
    )
    z = (
        jnp.dot(x, wx_ref[:], preferred_element_type=jnp.float32)
        + bx_ref[:]
        + jnp.dot(h_ref[:], wh_ref[:], preferred_element_type=jnp.float32)
    )
    i = jax.nn.sigmoid(z[:, :H])
    f = jax.nn.sigmoid(z[:, H : 2 * H])
    g = jnp.tanh(z[:, 2 * H : 3 * H])
    o = jax.nn.sigmoid(z[:, 3 * H :])
    c2 = f * c_ref[:] + i * g
    h2 = o * jnp.tanh(c2)
    raw = (
        jnp.dot(h2, wl_ref[:], preferred_element_type=jnp.float32) + bl_ref[:]
    )
    # log-softmax, fused: one max + one exp-sum per row, all in VMEM.
    m = jnp.max(raw, axis=-1, keepdims=True)
    logits_ref[:] = raw - (m + jnp.log(jnp.sum(jnp.exp(raw - m), axis=-1, keepdims=True)))
    h2_ref[:] = h2
    c2_ref[:] = c2


@jax.named_scope("act_pallas")  # read back by utils.platform.program_paths
def fused_act_step(actor_params, obs, h, c, interpret: bool):
    """Run the fused kernel on an (already dequantized, f32) actor param
    tree. Returns (log-softmax logits, h2, c2) — the same triple
    ``DiscreteActorCritic.act`` produces, minus the discarded value."""
    p = actor_params["params"]
    wb, bb = p["body"]["kernel"], p["body"]["bias"]
    wx, bx = p["cell"]["x_proj"]["kernel"], p["cell"]["x_proj"]["bias"]
    wh = p["cell"]["recurrent_kernel"]
    wl, bl = p["logits"]["kernel"], p["logits"]["bias"]
    B = obs.shape[0]
    H = wh.shape[0]
    A = wl.shape[1]
    out_shape = (
        jax.ShapeDtypeStruct((B, A), jnp.float32),  # log-softmax logits
        jax.ShapeDtypeStruct((B, H), jnp.float32),  # h2
        jax.ShapeDtypeStruct((B, H), jnp.float32),  # c2
    )
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return pl.pallas_call(
        _act_kernel,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(
        f32(obs), f32(wb), f32(bb)[None, :], f32(wx), f32(bx)[None, :],
        f32(wh), f32(wl), f32(bl)[None, :], f32(h), f32(c),
    )


def _kernel_choice(rows: int, obs_dim: int, hidden: int, n_actions: int):
    """-> (use_kernel, interpret), read at TRACE time (the serving step is
    traced once per bucket at warmup, after any set_pallas_mode call)."""
    from tpu_rl.models.cells import _PALLAS_MODE

    if _PALLAS_MODE == "off":
        return False, False
    if _PALLAS_MODE == "interpret":
        return True, True
    if jax.default_backend() != "tpu":
        return False, False
    if not act_fits_vmem(rows, obs_dim, hidden, n_actions):
        return False, False
    return True, False


def make_fused_act(family):
    """Fused replacement for ``family.act`` with the identical signature and
    return contract, or None when the family is out of scope (non-discrete,
    transformer, bf16-compute LSTM — the fused kernel is f32-only, like the
    pallas_lstm unroll)."""
    from tpu_rl.models.policies import DiscreteActorCritic
    from tpu_rl.ops import distributions as D

    actor = family.actor
    if not isinstance(actor, DiscreteActorCritic) or actor.dtype is not None:
        return None

    def act(params, obs, h, c, key):
        use, interpret = _kernel_choice(
            obs.shape[0], obs.shape[1], family.hidden, family.n_actions
        )
        if not use:
            return family.act(params, obs, h, c, key)
        logits, h2, c2 = fused_act_step(params["actor"], obs, h, c, interpret)
        a = D.categorical_sample(key, logits)
        log_prob = D.categorical_log_prob(logits, a)
        return a[..., None].astype(jnp.float32), logits, log_prob[..., None], h2, c2

    return act
