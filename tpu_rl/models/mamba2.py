"""The Mamba-2 mixer (SSD, arXiv:2405.21060) as granite_hybrid and nemotron_h
build it, widths as fields: ``[z, xBC, dt] = W_in u``; a causal depthwise
convolution and SiLU over ``xBC``; ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x)
B_t``, ``y_t = h_t C_t + D x_t``; ``W_out RMSNorm(y * silu(z))``.

Episode seams: ``is_fir[t]`` zeroes the state and the convolution's taps
before ``t``. Training runs the chunked form of the recurrence, in which a
seam is a same-segment mask on every decay factor (never ``-inf`` inside a
cumulative sum, whose differences are NaN); acting runs the one-step form and
relies on the worker zeroing the carry at episode starts.

Which form of the chunked recurrence trains where (``ssd_chunked``): on a TPU,
at widths that tile (chunk and state multiples of 128, as published), one
Pallas kernel per pass (``ops/pallas_ssd.py``, scope ``ssd_pallas`` inside
``ssd_scan``), under a registered data mesh as a ``shard_map`` island over its
``"data"`` axis; everywhere else — the CPU, the tests' 8-step chunks, a batch
that does not tile the mesh — the ``jnp``/``einsum`` body ``_ssd_jnp``, which
is also the kernels' oracle.
``models.cells.set_pallas_mode`` overrides as for the LSTM: ``"interpret"``
runs the kernels in the interpreter, ``"off"`` forces the ``jnp`` body.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tpu_rl.models import cells
from tpu_rl.models.layers import _rms_norm, seam_conv
from tpu_rl.ops import pallas_ssd


def _ssd_kernel_block(b: int, h: int, p: int, g: int, n: int, Q: int) -> tuple[int | None, bool]:
    """(heads per grid step of the Pallas scan, interpret), or (None, False)
    for the ``jnp`` body: the gate of ``models/cells.py`` (``set_pallas_mode``,
    the platform of the program being traced) applied to the scan. The CPU,
    widths that are no lane multiples and a batch that does not tile a
    registered data mesh (init and act traces: a Mosaic call has no SPMD
    rule outside its island) keep the ``jnp`` form."""
    mode = cells._PALLAS_MODE
    if mode == "off":
        return None, False
    if mode == "interpret":  # any width: whole windows of every head where none tiles
        return pallas_ssd.head_block(h, p, g, n, Q) or h, True
    platform, n_data = cells._program_devices()
    if platform != "tpu" or b % n_data:
        return None, False
    return pallas_ssd.head_block(h, p, g, n, Q), False


def _ssd_kernels(x, dt, A, B, C, D, seg, state0, chunk, dtype, hb, interpret):
    """The Pallas pair (``ops/pallas_ssd.py``); under a registered data mesh
    whose width the batch tiles, as a ``shard_map`` island over the
    ``"data"`` axis, as the LSTM kernel and the flash kernel run there."""
    scan = functools.partial(
        pallas_ssd.scan_window, chunk=chunk, dtype=dtype, hb=hb, interpret=interpret)
    mesh = cells._DATA_MESH
    if mesh is not None and x.shape[0] % cells._program_devices()[1] == 0:
        from jax.sharding import PartitionSpec as P

        from tpu_rl.parallel.mesh import DATA_AXIS

        rows = P(DATA_AXIS)  # every operand but A and D: its leading (batch) dim
        # no collectives inside; pallas out_shapes carry no vma annotations
        scan = jax.shard_map(
            scan, mesh=mesh, in_specs=(rows, rows, P(), rows, rows, P(), rows, rows),
            out_specs=(rows, rows), check_vma=False)
    with jax.named_scope("ssd_pallas"):  # the backward's ops carry it too
        return scan(x, dt, A, B, C, D, seg, state0)


@jax.named_scope("ssd_scan")
def ssd_chunked(x, dt, A, B, C, D, seg, state0, chunk: int, dtype, kernel=None):
    """The SSD recurrence over a whole window in matmul form.

    ``x`` (b, T, h, p); ``dt`` (b, T, h) float32, after softplus; ``A`` (h,)
    negative; ``B``, ``C`` (b, T, g, n); ``seg`` (b, T) int, 0 = the episode
    ``state0`` (b, h, p, n) belongs to. Returns ``y`` (b, T, h, p) float32
    and the state after the last step. Matmul operands in ``dtype``; decays,
    cumulative sums and the carried state in float32. ``kernel``: ``(heads a
    grid step of the Pallas pair or None for the jnp body, interpret)``
    where the caller and not the gate chooses (tests, ``chip_smoke.py``)."""
    b, T, h, p = x.shape
    g, n = B.shape[2:]
    pad = (-T) % chunk
    if pad:  # dt = 0: the state passes through, nothing is added
        x, dt, B, C = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (x, dt, B, C)
        )
        seg = jnp.concatenate([seg, jnp.repeat(seg[:, -1:], pad, axis=1)], axis=1)
    hb, interpret = kernel or _ssd_kernel_block(b, h, p, g, n, chunk)
    if hb is None:
        y, last = _ssd_jnp(x, dt, A, B, C, D, seg, state0, chunk, dtype)
    else:
        y, last = _ssd_kernels(x, dt, A, B, C, D, seg, state0, chunk, dtype, hb, interpret)
    return y[:, :T], last


def _ssd_jnp(x, dt, A, B, C, D, seg, state0, Q: int, dtype):
    """``ssd_chunked`` on a window of whole chunks as ``einsum``s and one
    ``lax.scan`` over the chunks: the CPU's path and the kernels' oracle."""
    b, T, h, p = x.shape
    g, n = B.shape[2:]
    r, nc = h // g, T // Q
    cd = dtype or jnp.float32
    f32 = jnp.float32
    xc = x.reshape(b, nc, Q, h, p)
    dtc = dt.reshape(b, nc, Q, h)
    Bc = B.reshape(b, nc, Q, g, n).astype(cd)
    Cc = C.reshape(b, nc, Q, g, n).astype(cd)
    segc = seg.reshape(b, nc, Q)
    # the segment a chunk is entered in: that of the step before it
    seg_in = jnp.concatenate([jnp.zeros_like(segc[:, :1, 0]), segc[:, :-1, -1]], axis=1)

    acum = jnp.cumsum((dtc * A).transpose(0, 1, 3, 2), axis=-1)  # (b, nc, h, Q)
    dtx = xc.astype(f32) * dtc[..., None]  # (b, nc, Q, h, p)

    def decay(exponent, keep):
        return jnp.exp(jnp.where(keep, exponent, -jnp.inf))

    # inside a chunk: step s reaches step l >= s of the same segment
    reach = (segc[:, :, :, None] == segc[:, :, None, :]) & jnp.tril(jnp.ones((Q, Q), bool))
    L = decay(acum[..., :, None] - acum[..., None, :], reach[:, :, None])  # (b,nc,h,l,s)
    CB = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc, preferred_element_type=f32)
    M = (CB[:, :, :, None] * L.reshape(b, nc, g, r, Q, Q)).reshape(b, nc, h, Q, Q)
    y = jnp.einsum(
        "bchls,bcshp->bclhp", M.astype(cd), dtx.astype(cd), preferred_element_type=f32
    )

    # what each chunk adds to the state at its end
    to_end = decay(acum[..., -1:] - acum, (segc == segc[:, :, -1:])[:, :, None])  # (b,nc,h,Q)
    xw = (dtx * to_end.transpose(0, 1, 3, 2)[..., None]).astype(cd)
    S = jnp.einsum(
        "bcsgrp,bcsgn->bcgrpn", xw.reshape(b, nc, Q, g, r, p), Bc,
        preferred_element_type=f32,
    ).reshape(b, nc, h, p, n)
    # what a chunk keeps of the state it is entered with: nothing past a seam
    through = decay(acum[..., -1], (segc[:, :, -1] == seg_in)[:, :, None])  # (b, nc, h)

    def across(state, c):
        S_c, through_c = c
        return through_c[..., None, None] * state + S_c, state

    last, entered = jax.lax.scan(
        across, state0.astype(f32),
        (S.transpose(1, 0, 2, 3, 4), through.transpose(1, 0, 2)),
    )
    entered = entered.transpose(1, 0, 2, 3, 4)  # (b, nc, h, p, n): state before chunk c
    into = decay(acum, (segc == seg_in[:, :, None])[:, :, None])  # (b, nc, h, Q)
    y_in = jnp.einsum(
        "bclgn,bcgrpn->bclgrp", Cc, entered.astype(cd).reshape(b, nc, g, r, p, n),
        preferred_element_type=f32,
    ).reshape(b, nc, Q, h, p)
    y = y + y_in * into.transpose(0, 1, 3, 2)[..., None]
    y = y + xc.astype(f32) * D[:, None]
    return y.reshape(b, T, h, p), last


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of step sizes log-uniform in [1e-3, 1e-1] (Mamba-2)."""
    dt = jnp.exp(
        jax.random.uniform(key, shape, dtype) * (np.log(0.1) - np.log(0.001))
        + np.log(0.001)
    )
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2Mixer(nn.Module):
    """``__call__`` (training) runs ``ssd_chunked``: the Pallas kernels on a
    TPU, the ``jnp`` body elsewhere; ``step`` (acting) is the one-step form.
    The widths are fields, so that a family whose ``config.json`` names them
    otherwise (``models/nemotron_h.py``) builds the same mixer."""

    hidden: int
    heads: int
    d_head: int
    groups: int
    d_state: int
    d_conv: int
    chunk: int
    eps: float
    conv_bias: bool = True
    proj_bias: bool = False
    dtype: Any = None

    def setup(self):
        self.inner = self.heads * self.d_head
        self.conv_ch = self.inner + 2 * self.groups * self.d_state
        proj = dict(use_bias=self.proj_bias, dtype=self.dtype)
        self.in_proj = nn.Dense(self.inner + self.conv_ch + self.heads, name="in_proj", **proj)
        self.out_proj = nn.Dense(self.hidden, name="out_proj", **proj)
        self.conv_weight = self.param(
            "conv_weight", nn.initializers.variance_scaling(1.0, "fan_in", "uniform", in_axis=0),
            (self.d_conv, self.conv_ch),
        )
        self.conv_b = (
            self.param("conv_bias", nn.initializers.zeros, (self.conv_ch,))
            if self.conv_bias else jnp.zeros((self.conv_ch,))
        )
        self.dt_bias = self.param("dt_bias", _dt_bias_init, (self.heads,))
        self.A_log = self.param("A_log", _a_log_init, (self.heads,))
        self.D = self.param("D", nn.initializers.ones, (self.heads,))
        self.norm_scale = self.param("norm_scale", nn.initializers.ones, (self.inner,))

    def _split(self, u):
        zxbcdt = self.in_proj(u)
        z, xbc, dt = jnp.split(zxbcdt, [self.inner, self.inner + self.conv_ch], axis=-1)
        return z, xbc, jax.nn.softplus(dt.astype(jnp.float32) + self.dt_bias)

    def _heads(self, xbc):
        """Convolved, activated ``xBC`` -> x (..., h, p), B and C (..., g, n)."""
        gn = self.groups * self.d_state
        x, B, C = jnp.split(jax.nn.silu(xbc), [self.inner, self.inner + gn], axis=-1)
        lead = xbc.shape[:-1]
        return (
            x.reshape(*lead, self.heads, self.d_head),
            B.reshape(*lead, self.groups, self.d_state),
            C.reshape(*lead, self.groups, self.d_state),
        )

    def _out(self, y, z):
        """Gated RMSNorm over each group's channels, then the output
        projection. ``y`` float32 (..., inner)."""
        lead = y.shape[:-1]
        gated = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(*lead, self.groups, -1)
        normed = _rms_norm(gated, 1.0, self.eps).reshape(*lead, self.inner)
        return self.out_proj((normed * self.norm_scale).astype(self.dtype or jnp.float32))

    def __call__(self, u, seg, state0, tail0):
        """``u`` (B, T, d); ``state0`` (B, h, p, n), ``tail0`` (B, K-1, C):
        the carry the window starts from. Returns the output and the carry
        after the last step."""
        z, xbc, dt = self._split(u)
        x, B, C = self._heads(seam_conv(xbc, tail0, seg, self.conv_weight, self.conv_b))
        y, state = ssd_chunked(
            x, dt, -jnp.exp(self.A_log), B, C, self.D, seg, state0, self.chunk, self.dtype,
        )
        K = self.conv_weight.shape[0]
        keep = (seg[:, -(K - 1):] == seg[:, -1:])[..., None]  # taps of the last episode only
        tail = jnp.where(keep, xbc[:, -(K - 1):].astype(jnp.float32), 0.0)
        return self._out(y.reshape(*y.shape[:2], self.inner), z), state, tail

    def step(self, u, state, tail):
        """One acting step: ``u`` (B, d)."""
        z, xbc, dt = self._split(u)
        window = jnp.concatenate([tail, xbc[:, None].astype(jnp.float32)], axis=1)
        conv = jnp.einsum("bkc,kc->bc", window, self.conv_weight) + self.conv_b
        x, B, C = self._heads(conv)
        r = self.heads // self.groups
        x = x.astype(jnp.float32)
        Bh, Ch = (jnp.repeat(a.astype(jnp.float32), r, axis=1) for a in (B, C))
        keep = jnp.exp(dt * -jnp.exp(self.A_log))  # (B, h)
        state = keep[..., None, None] * state + (dt[..., None] * x)[..., None] * Bh[:, :, None]
        y = jnp.einsum("bhpn,bhn->bhp", state, Ch) + x * self.D[:, None]
        return self._out(y.reshape(-1, self.inner), z), state, window[:, 1:]
