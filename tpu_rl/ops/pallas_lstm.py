"""Fused LSTM sequence kernel (Pallas/TPU).

The hot op of the reference model zoo is the LSTM unroll — a Python loop of
``nn.LSTMCell`` launches in torch (``/root/reference/networks/models.py:71-75``),
a ``lax.scan`` here. This kernel fuses the whole sequence into ONE Pallas
program per batch tile: the recurrent weights live in VMEM for the entire
sequence (zero re-fetch from HBM between timesteps), the per-step work is a
single (Bt, H) x (H, 4H) MXU matmul plus VPU gate math, and the input
projection for all timesteps is one big batched matmul done OUTSIDE the
kernel where the MXU is happiest.

Differentiation: ``lstm_unroll`` is a ``jax.custom_vjp`` — forward runs the
Pallas kernel and saves the gate activations + cell states; backward is the
analytic LSTM backprop as a reverse ``lax.scan`` (elementwise + two small
matmuls per step), no recomputation.

Episode resets: the carry is multiplied by ``keep = 1 - firsts[t]`` before
each step, matching ``models.policies.scan_lstm`` semantics exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM requested for one kernel call, and the budget the tile pickers
# size against. A TPU v5e core reports 128 MiB of VMEM
# (``pltpu.get_tpu_info().vmem_capacity_bytes``, printed by chip_smoke.py);
# Mosaic's default scoped limit is 16 MiB, below one wide-hidden tile's
# working set (wh alone is 16 MiB at H=1024), so the limit is raised and the
# rest is left to the compiler. Wide-hidden workloads tile their batch via
# ``batch_tile`` instead of falling back to the scan.
_VMEM_BUDGET_BYTES = 106 * 1024 * 1024

_LANES, _SUBLANES = 128, 8


def _block_bytes(*shape: int) -> int:
    """f32 bytes one block occupies in VMEM: the minor dim pads to 128
    lanes and the second-minor to 8 sublanes — a ``(S, bt, 1)`` keep block
    costs as much as ``(S, bt, 128)``, and H=64 rows cost H=128."""
    *lead, rows, cols = shape
    n = 4 * (-(-rows // _SUBLANES) * _SUBLANES) * (-(-cols // _LANES) * _LANES)
    for d in lead:
        n *= d
    return n


def _compiler_params(interpret: bool):
    """Mosaic params shared by the forward, backward and act kernels."""
    if interpret:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BUDGET_BYTES)


def _make_kernel(save_acts: bool):
    def kernel(xp_ref, wh_ref, h0_ref, c0_ref, keep_ref, hs_ref, cs_ref, *rest):
        """One batch tile, full sequence, TIME-MAJOR layouts (the sequence
        index is the untiled leading axis, so the dynamic per-step index never
        touches a tiled sublane/lane dimension — a Mosaic requirement).

        xp   : (S, Bt, 4H) precomputed input projection (+bias)
        wh   : (H, 4H) recurrent weights (VMEM-resident all S steps)
        h0,c0: (Bt, H) initial carry
        keep : (S, Bt, 1) carry-keep mask (0 at episode-first steps)
        hs,cs: (S, Bt, H) per-step hidden / cell states (outputs)
        acts : (S, Bt, 4H) post-activation gates i,f,g,o — only in the
               differentiated path (VJP residuals); the primal skips the
               stores entirely (XLA cannot DCE an opaque custom call).
        """
        acts_ref = rest[0] if save_acts else None
        S = xp_ref.shape[0]
        H = wh_ref.shape[0]
        wh = wh_ref[:]

        def step(t, carry):
            h, c = carry
            keep = keep_ref[t]  # (Bt, 1)
            h = h * keep
            c = c * keep
            z = xp_ref[t] + jnp.dot(h, wh, preferred_element_type=jnp.float32)
            i = jax.nn.sigmoid(z[:, :H])
            f = jax.nn.sigmoid(z[:, H : 2 * H])
            g = jnp.tanh(z[:, 2 * H : 3 * H])
            o = jax.nn.sigmoid(z[:, 3 * H :])
            c2 = f * c + i * g
            h2 = o * jnp.tanh(c2)
            hs_ref[t] = h2
            cs_ref[t] = c2
            if acts_ref is not None:
                # one full-width store (no partial-lane writes)
                acts_ref[t] = jnp.concatenate([i, f, g, o], axis=-1)
            return h2, c2

        jax.lax.fori_loop(0, S, step, (h0_ref[:], c0_ref[:]))

    return kernel


@jax.named_scope("lstm_pallas")  # read back by utils.platform.program_paths
def _pallas_forward(xp, wh, h0, c0, keep, interpret: bool, save_acts: bool):
    """xp (B,S,4H), keep (B,S) -> (hs, cs[, acts]) in batch-major layout
    (the kernel runs time-major internally).

    The batch dimension is tiled over a 1-D Pallas grid: each grid step
    unrolls the full sequence for one VMEM-sized batch tile while Mosaic
    streams the next tile's input projection HBM->VMEM behind it. The
    recurrent weights block is the same for every tile (index_map pins it),
    so it stays VMEM-resident across the whole grid."""
    B, S, H4 = xp.shape
    H = H4 // 4
    bt = batch_tile(B, S, H)
    if bt is None:
        raise ValueError(
            f"no VMEM-fitting batch tile for (B={B}, S={S}, H={H}); "
            "caller should use the scan path"
        )
    grid = (B // bt,)
    out_shapes = [
        jax.ShapeDtypeStruct((S, B, H), jnp.float32),  # hs
        jax.ShapeDtypeStruct((S, B, H), jnp.float32),  # cs
    ]
    out_specs = [
        pl.BlockSpec((S, bt, H), lambda b: (0, b, 0)),
        pl.BlockSpec((S, bt, H), lambda b: (0, b, 0)),
    ]
    if save_acts:
        out_shapes.append(jax.ShapeDtypeStruct((S, B, H4), jnp.float32))
        out_specs.append(pl.BlockSpec((S, bt, H4), lambda b: (0, b, 0)))
    in_specs = [
        pl.BlockSpec((S, bt, H4), lambda b: (0, b, 0)),  # xp
        pl.BlockSpec((H, H4), lambda b: (0, 0)),  # wh (every tile)
        pl.BlockSpec((bt, H), lambda b: (b, 0)),  # h0
        pl.BlockSpec((bt, H), lambda b: (b, 0)),  # c0
        pl.BlockSpec((S, bt, 1), lambda b: (0, b, 0)),  # keep
    ]
    outs = pl.pallas_call(
        _make_kernel(save_acts),
        grid=grid,
        out_shape=tuple(out_shapes),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(
        jnp.moveaxis(xp, 1, 0),
        wh,
        h0,
        c0,
        jnp.moveaxis(keep, 1, 0)[..., None],
    )
    return tuple(jnp.moveaxis(o, 0, 1) for o in outs)


def _fits(blocks: int, batch: int, hidden: int) -> bool:
    """``blocks`` = padded bytes of every pipelined block of one grid step.
    Mosaic double-buffers each of them (the grid-invariant wh block too),
    and the step body keeps a few (Bt, 4H) gate temporaries live."""
    return 2 * blocks + 6 * _block_bytes(batch, 4 * hidden) <= _VMEM_BUDGET_BYTES


def fits_vmem(batch: int, seq: int, hidden: int) -> bool:
    """Does ONE forward batch tile of this size fit the VMEM budget?"""
    h4 = 4 * hidden
    blocks = (
        2 * _block_bytes(seq, batch, h4)  # xp, acts
        + 2 * _block_bytes(seq, batch, hidden)  # hs, cs
        + _block_bytes(seq, batch, 1)  # keep
        + 2 * _block_bytes(batch, hidden)  # h0, c0
        + _block_bytes(hidden, h4)  # wh
    )
    return _fits(blocks, batch, hidden)


def _best_tile(batch: int, fits) -> int | None:
    """Largest divisor of ``batch`` accepted by ``fits``, restricted to
    sublane multiples of 8 (or the whole batch when it both fits and is
    small): a degenerate few-row tile would serialize the batch over the grid
    at a fraction of VPU width — strictly worse than the ``lax.scan``
    fallback — so shapes with only tiny fitting divisors return None."""
    divs = [d for d in range(1, batch + 1) if batch % d == 0 and fits(d)]
    if not divs:
        return None
    mult8 = [d for d in divs if d % 8 == 0]
    if mult8:
        return max(mult8)
    return batch if batch in divs else None


def batch_tile(batch: int, seq: int, hidden: int) -> int | None:
    """Forward-kernel batch tile, or None when no tiling fits VMEM (very
    long seq x wide hidden: the caller falls back to the scan; long-context
    training is the transformer's job)."""
    return _best_tile(batch, lambda d: fits_vmem(d, seq, hidden))


def bwd_batch_tile(batch: int, seq: int, hidden: int) -> int | None:
    """Backward-kernel batch tile (acts, cs, dhs, dcs in; dxp out)."""
    h4 = 4 * hidden

    def fits(d: int) -> bool:
        blocks = (
            2 * _block_bytes(seq, d, h4)  # acts, dxp
            + 3 * _block_bytes(seq, d, hidden)  # cs, dhs, dcs
            + _block_bytes(seq, d, 1)  # keep
            + 4 * _block_bytes(d, hidden)  # h0, c0, dh0, dc0
            + _block_bytes(hidden, h4)  # wh
        )
        return _fits(blocks, d, hidden)

    return _best_tile(batch, fits)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def mixed_dot(a, b, dtype=jnp.bfloat16):
    """``a @ b`` with BOTH passes at reduced-precision MXU rate, f32 out.

    A plain ``dot(a.astype(bf16), b.astype(bf16), preferred f32)`` only
    accelerates the FORWARD: its AD transpose receives an f32 cotangent, so
    both backward matmuls are mixed f32 x bf16 dots that XLA runs at f32
    rate — measured as the round-4 "bf16 gave nothing" wide-LSTM row
    (10.25 ms bf16 vs 10.16 f32; the backward holds ~2/3 of the matmul
    FLOPs). This VJP casts the cotangent to ``dtype`` too — standard
    mixed-precision practice; gradients pick up one bf16 rounding, while
    accumulation (``preferred_element_type``) and all results stay f32.

    2-D operands only: the backward's ``.T``-transposed dots assume plain
    matrices, and batched/1-D operands would silently compute the wrong
    gradient contraction rather than fail. Reshape to 2-D at the call site
    (every LSTM use is ``(rows, features) @ (features, cols)``)."""
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(
            "mixed_dot requires 2-D operands (its custom VJP transposes "
            f"with .T); got a.ndim={a.ndim}, b.ndim={b.ndim}. Reshape to "
            "matrices before calling."
        )
    return jnp.dot(
        a.astype(dtype), b.astype(dtype), preferred_element_type=jnp.float32
    )


def _mixed_dot_fwd(a, b, dtype):
    # Residuals saved PRE-cast to ``dtype``: identical backward numerics
    # (the cast is idempotent), half the stacked-residual bytes under a
    # scan, and no per-step re-cast of the loop-invariant weights.
    return mixed_dot(a, b, dtype), (a.astype(dtype), b.astype(dtype))


def _mixed_dot_bwd(dtype, res, g):
    ad, bd = res
    gd = g.astype(dtype)
    da = jnp.dot(gd, bd.T, preferred_element_type=jnp.float32)
    db = jnp.dot(ad.T, gd, preferred_element_type=jnp.float32)
    return da, db


mixed_dot.defvjp(_mixed_dot_fwd, _mixed_dot_bwd)


@jax.named_scope("lstm_scan")
def _scan_forward(xp, wh, h0, c0, keep, matmul_dtype=None, want_cs=False):
    """Plain ``lax.scan`` forward over the precomputed input projection —
    the measured winner for UNdifferentiated unrolls at single-tile shapes
    (the fused kernel is 0.89-0.91x the scan on forward-only there,
    bench_lstm_kernel.json, a host-clocked record no cell has re-measured,
    ROADMAP W4; it wins only when the fused backward is in play).

    ``matmul_dtype`` (e.g. ``jnp.bfloat16``) runs the recurrent matmul
    through :func:`mixed_dot` — MXU-rate compute in BOTH passes with f32
    accumulation; the carry, gate math, and outputs stay float32.
    None = pure float32 (bit-identical to the fused kernel).

    Returns ``(hs, (h_last, c_last))`` by default; ``want_cs=True`` stacks
    the full per-step cell state and returns ``(hs, cs)`` instead — only
    the ``lstm_unroll`` primal needs that (its custom_vjp output contract
    is (B,S,H) pairs); every other caller consumes just the final carry,
    and stacking cs for them would write an extra (B,S,H) buffer per
    forward (~64 MB at B1024 S16 H1024)."""
    def step(carry, xs):
        h, c = carry
        xp_t, keep_t = xs
        kp = keep_t[:, None]
        h = h * kp
        c = c * kp
        rec = (
            jnp.dot(h, wh, preferred_element_type=jnp.float32)
            if matmul_dtype is None
            else mixed_dot(h, wh, matmul_dtype)
        )
        z = xp_t.astype(jnp.float32) + rec
        H = wh.shape[0]
        i = jax.nn.sigmoid(z[:, :H])
        f = jax.nn.sigmoid(z[:, H : 2 * H])
        g = jnp.tanh(z[:, 2 * H : 3 * H])
        o = jax.nn.sigmoid(z[:, 3 * H :])
        c2 = f * c + i * g
        h2 = o * jnp.tanh(c2)
        return (h2, c2), ((h2, c2) if want_cs else h2)

    (h_last, c_last), out = jax.lax.scan(
        step, (h0, c0), (jnp.moveaxis(xp, 1, 0), jnp.moveaxis(keep, 1, 0))
    )
    if want_cs:
        hs, cs = out
        return jnp.moveaxis(hs, 0, 1), jnp.moveaxis(cs, 0, 1)
    return jnp.moveaxis(out, 0, 1), (h_last, c_last)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def lstm_unroll(xp, wh, h0, c0, keep, interpret=False):
    """Fused LSTM over a sequence.

    xp (B,S,4H) input projection incl. bias; wh (H,4H); h0/c0 (B,H);
    keep (B,S) carry-keep mask. Returns (hs, cs), each (B,S,H).

    Measured-win dispatch (bench_lstm_kernel.json): this primal body runs
    only when the call is NOT differentiated (custom_vjp routes traced-for-AD
    calls through ``_fwd``), and forward-only is where the kernel loses
    (0.89-0.91x the scan at the single-tile shapes) — so the undifferentiated
    path always scans. ``interpret`` (CPU equivalence tests) and the cells
    module's "force" benchmark mode still run the kernel so tests and the
    gate-deriving benchmark can never silently degrade into scan-vs-scan."""
    from tpu_rl.models.cells import _PALLAS_MODE

    if interpret or _PALLAS_MODE == "force":
        hs, cs = _pallas_forward(
            xp, wh, h0, c0, keep, interpret, save_acts=False
        )
        return hs, cs
    return _scan_forward(xp, wh, h0, c0, keep, want_cs=True)


def _fwd(xp, wh, h0, c0, keep, interpret):
    hs, cs, acts = _pallas_forward(
        xp, wh, h0, c0, keep, interpret, save_acts=True
    )
    return (hs, cs), (xp, wh, h0, c0, keep, hs, cs, acts)


def _bwd_kernel(
    acts_ref, cs_ref, h0_ref, c0_ref, keep_ref, dhs_ref, dcs_ref,
    wh_ref, dxp_ref, dh0_ref, dc0_ref,
):
    """Analytic LSTM backprop for one batch tile, full sequence, reverse
    time — the fused mirror of the forward kernel: per step, the elementwise
    gate-gradient math plus ONE (Bt, 4H) x (4H, H) MXU matmul for the carry
    gradient, with wh VMEM-resident across the grid. The weight gradient is
    NOT accumulated here: dwh = sum_t h_prev_used[t]^T dz[t] contracts over
    batch x time, so it is one big MXU matmul over the kernel's dxp output,
    done outside where the contraction is (B*S)-deep instead of Bt-deep."""
    S = acts_ref.shape[0]
    H = wh_ref.shape[0]
    wh = wh_ref[:]

    def step(idx, carry):
        dh, dc = carry
        t = S - 1 - idx
        act = acts_ref[t]
        i = act[:, :H]
        f = act[:, H : 2 * H]
        g = act[:, 2 * H : 3 * H]
        o = act[:, 3 * H :]
        kp = keep_ref[t]  # (Bt, 1)
        tm1 = jnp.maximum(t - 1, 0)
        cp = jnp.where(t > 0, cs_ref[tm1], c0_ref[:])
        cp_used = cp * kp
        dh_t = dhs_ref[t] + dh
        t_c2 = jnp.tanh(cs_ref[t])
        do = dh_t * t_c2
        dc_t = dcs_ref[t] + dc + dh_t * o * (1.0 - t_c2 * t_c2)
        di = dc_t * g
        dg = dc_t * i
        df = dc_t * cp_used
        dz = jnp.concatenate(
            [
                di * i * (1.0 - i),
                df * f * (1.0 - f),
                dg * (1.0 - g * g),
                do * o * (1.0 - o),
            ],
            axis=-1,
        )  # (Bt, 4H)
        dxp_ref[t] = dz
        dh_prev = jnp.dot(dz, wh.T, preferred_element_type=jnp.float32) * kp
        dc_prev = dc_t * f * kp
        return dh_prev, dc_prev

    dh, dc = jax.lax.fori_loop(
        0, S, step, (jnp.zeros_like(h0_ref[:]), jnp.zeros_like(c0_ref[:]))
    )
    dh0_ref[:] = dh
    dc0_ref[:] = dc


@jax.named_scope("lstm_pallas")
def _pallas_backward(wh, h0, c0, keep, hs, cs, acts, dhs, dcs, interpret):
    """Batch-tiled fused backward; same grid scheme as the forward. Returns
    (dxp, dh0, dc0); the weight gradient is computed by the caller from dxp
    (one batch*time-deep MXU matmul)."""
    B, S, H = hs.shape
    H4 = 4 * H
    # The interpreter has no VMEM: an untileable shape still runs (whole
    # batch, grid 1) so tests always exercise the kernel.
    bt = bwd_batch_tile(B, S, H) or (B if interpret else None)
    assert bt is not None  # caller gates on bwd_batch_tile
    grid = (B // bt,)
    tm = lambda a: jnp.moveaxis(a, 1, 0)
    seq_spec = lambda w: pl.BlockSpec((S, bt, w), lambda b: (0, b, 0))
    row_spec = pl.BlockSpec((bt, H), lambda b: (b, 0))
    wh_spec = pl.BlockSpec((H, H4), lambda b: (0, 0))
    dxp, dh0, dc0 = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        out_shape=(
            jax.ShapeDtypeStruct((S, B, H4), jnp.float32),  # dxp (= dz)
            jax.ShapeDtypeStruct((B, H), jnp.float32),  # dh0
            jax.ShapeDtypeStruct((B, H), jnp.float32),  # dc0
        ),
        in_specs=[
            seq_spec(H4),  # acts
            seq_spec(H),  # cs
            row_spec,  # h0
            row_spec,  # c0
            seq_spec(1),  # keep
            seq_spec(H),  # dhs
            seq_spec(H),  # dcs
            wh_spec,  # wh
        ],
        out_specs=(seq_spec(H4), row_spec, row_spec),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(
        tm(acts), tm(cs), h0, c0, tm(keep)[..., None], tm(dhs),
        tm(dcs), wh,
    )
    return jnp.moveaxis(dxp, 0, 1), dh0, dc0


def _bwd(interpret, res, ct):
    xp, wh, h0, c0, keep, hs, cs, acts = res
    dhs, dcs = ct
    B, S, H = hs.shape

    # Fused backward kernel only when the WHOLE batch fits one tile: with a
    # multi-tile grid each sequential step's carry matmul contracts over just
    # Bt rows, starving the MXU — measured 0.73x the scan at B1024/H1024 —
    # while at grid 1 the fusion wins (1.2x at the reference quantum). Wide
    # multi-tile shapes keep the scan backward, whose per-step matmuls see
    # the full batch. (lstm_unroll is only reached when the cell chose the
    # kernel for the forward.) The cells "force" benchmark mode overrides
    # this gate too (any fitting tile), so force-mode fwd+grad rows time the
    # genuinely fused kernel pair, not kernel-fwd + scan-bwd.
    from tpu_rl.models.cells import _PALLAS_MODE

    # (A compiled forward kernel ran to get here, so the device is a TPU.)
    bwd_tile = bwd_batch_tile(B, S, H)
    if (
        interpret
        or bwd_tile == B
        or (_PALLAS_MODE == "force" and bwd_tile is not None)
    ):
        dxp, dh0, dc0 = _pallas_backward(
            wh, h0, c0, keep, hs, cs, acts, dhs, dcs, interpret
        )
        # Weight gradient as one (H, B*S) x (B*S, 4H) MXU matmul — the
        # batch*time-deep contraction the per-tile kernel cannot express
        # efficiently (a Bt-deep contraction starves the systolic array).
        h_prev = jnp.concatenate([h0[:, None], hs[:, :-1]], axis=1)
        dwh = jnp.einsum(
            "bth,btz->hz",
            h_prev * keep[..., None],
            dxp,
            preferred_element_type=jnp.float32,
        )
        return dxp, dwh, dh0, dc0, None

    h_prev = jnp.concatenate([h0[:, None], hs[:, :-1]], axis=1)  # (B,S,H)
    c_prev = jnp.concatenate([c0[:, None], cs[:, :-1]], axis=1)

    def step(carry, xs):
        dh, dc, dwh = carry
        # per-step slices, time-reversed
        dh_out, dc_out, act, hp, cp, c_t, kp = xs
        kp = kp[:, None]
        i, f, g, o = jnp.split(act, 4, axis=-1)
        hp_used = hp * kp
        cp_used = cp * kp
        dh_t = dh_out + dh
        t_c2 = jnp.tanh(c_t)  # tanh of the saved cell state
        do = dh_t * t_c2
        dc_t = dc_out + dc + dh_t * o * (1.0 - t_c2 * t_c2)
        di = dc_t * g
        dg = dc_t * i
        df = dc_t * cp_used
        dz = jnp.concatenate(
            [
                di * i * (1.0 - i),
                df * f * (1.0 - f),
                dg * (1.0 - g * g),
                do * o * (1.0 - o),
            ],
            axis=-1,
        )  # (B, 4H)
        dwh = dwh + hp_used.T @ dz
        dh_prev = (dz @ wh.T) * kp
        dc_prev = dc_t * f * kp
        return (dh_prev, dc_prev, dwh), dz

    xs = (
        jnp.moveaxis(dhs, 1, 0)[::-1],
        jnp.moveaxis(dcs, 1, 0)[::-1],
        jnp.moveaxis(acts, 1, 0)[::-1],
        jnp.moveaxis(h_prev, 1, 0)[::-1],
        jnp.moveaxis(c_prev, 1, 0)[::-1],
        jnp.moveaxis(cs, 1, 0)[::-1],
        jnp.moveaxis(keep, 1, 0)[::-1],
    )
    zero = jnp.zeros((B, H), jnp.float32)
    with jax.named_scope("lstm_scan"):
        (dh0, dc0, dwh), dz_rev = jax.lax.scan(
            step, (zero, zero, jnp.zeros_like(wh)), xs
        )
    dxp = jnp.moveaxis(dz_rev[::-1], 0, 1)  # (B, S, 4H)
    return dxp, dwh, dh0, dc0, None


lstm_unroll.defvjp(_fwd, _bwd)
