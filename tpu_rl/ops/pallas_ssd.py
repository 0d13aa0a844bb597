"""Chunked Mamba-2 scan (SSD) as one Pallas TPU kernel per pass.

``models/mamba2.ssd_chunked`` in its ``jnp`` form builds every
token x inner intermediate of the chunked recurrence as an HLO result of its
own (``dtx``, ``xw``, ``y``, ``y_in`` in float32, their bf16 copies, two layout
copies, the per-chunk states in both dtypes): ~2.2 GB through HBM per layer
and forward at the published widths, against ~0.35 GB the algorithm has to
move. Here one kernel walks the chunks of a window *in order* — grid
``(rows, head blocks, chunks)``, the chunk axis sequential — with the carried
state of a head block in a VMEM scratch; the decay matrix ``L``, ``CB`` and
``M = CB * L`` of a chunk never leave VMEM. The backward kernel walks the
chunks in reverse with the state's cotangent in the scratch and recomputes
``CB``, ``L`` and the chunk's products from the kernel's inputs and the state
each chunk was entered with (the forward's one residual output).

What stays outside, in ``jnp`` under the caller's autodiff: the cumulative sum
of ``dt * A`` and the three per-step decay vectors (``to_end``, ``into``,
``through``: a few MB), under the same same-segment masks as the ``jnp`` form.

Precision is the ``jnp`` form's: operands of every matmul in ``dtype``
(bf16 in the registered cell) with float32 accumulation; decays, masks, sums
and the carried state in float32; the masked exponent is a ``where(keep, e,
-inf)`` before ``exp``. The backward casts the cotangent ``dy`` to ``dtype``
at its matmuls, as ``pallas_lstm.mixed_dot`` does.

Layout: time runs along the lanes. A tile is ``(head_block * d_head, chunk)``:
the heads' channels stacked on the rows, a chunk's steps on the lanes — the
layout XLA keeps the convolution's output and the gate in, so the window
enters and leaves the call without a layout copy. Then every per-head,
per-step factor (``dt``, the decays) is a ``(1, chunk)`` row that broadcasts
over a head's rows for free, a head is a row slice, the state keeps its own
``(head_block * d_head, d_state)`` shape, and a head's products with the
decay matrix are ``(d_head, chunk) x (chunk, chunk)``, the full width of the
MXU. The one column a head needs, its cumulative decay down the rows of the
decay matrix, is a ``(chunk, 1)`` slice of a second, transposed copy of that
small operand; ``B`` and ``C`` come in both orientations too (2 MB each). The
backward builds the transposed decay matrix from the negated exponent rather
than transpose a ``(chunk, chunk)`` matrix a head, and finds the gradient of
the exponent without it: its row sums are ``sum_p dy * (M dtx)`` and its
column sums ``dt * sum_p d(dtx) * x``. On a v5e both kernels wait on their
DMAs (~500 GB/s of 1 KB rows), not on the MXU or the VPU (``PERF.md``, PR 29).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_LANES = 128


def _vmem_limit() -> int:
    """Scoped VMEM a call asks for: three quarters of a core's, on the chip
    the program is traced for; the rest stays XLA's, which keeps arrays there
    between ops. On a v5e (128 MiB) the 96 MiB the cell was measured with:
    asked for no more than its tiles need (55 MiB), XLA placed the
    surrounding program differently and the cell's update read 455.9 ms
    against 450.6 (PERF.md, PR 29). Where Pallas' table does not know the
    device (a CPU host compiling for a described chip, the interpreter), the
    16 MiB of the smallest TPU generation count."""
    try:
        capacity = pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:
        capacity = 16 * 1024 * 1024
    return 3 * capacity // 4


def _vmem_bytes(hb: int, p: int, n: int, Q: int, gb: int) -> int:
    """VMEM the kernels need with ``hb`` heads a grid step: the blocks of the
    backward (the larger pass) double-buffered plus its scratch — at the
    cell's widths 27.6 MiB here, 26.95 MiB by Mosaic's own count — and as
    much again for what a kernel keeps between its matmuls."""
    W = hb * p
    blocks = 4 * W * (4 * Q + 3 * n + _LANES)  # x, D, dy, dx; entered, dlast, dstate0; dD
    blocks += 4 * Q * gb * n * 6  # B and C in both orientations; dB, dC
    blocks += 4 * Q * (9 * hb + 2 * _LANES)  # per-step rows and sums; acum and seg columns
    return 2 * (2 * blocks + 4 * W * n)


def head_block(heads: int, d_head: int, groups: int, d_state: int, chunk: int) -> int | None:
    """Heads per grid step of a compiled call, or None when no block fits
    the kernels. A block holds whole groups or lies inside one; chunk and
    state are lane multiples, a head's rows fill bf16 sublane groups, a
    block's rows are a multiple of 128, and the kernels' need
    (``_vmem_bytes``) is inside what the call asks for (``_vmem_limit``).
    The most rows that fit: on a v5e 2048, where the three calls of a layer
    took 7% less time than at 1024 rows and 12% less at 1024 than at 512
    (PERF.md, PR 29) — the kernels wait on their DMAs, not on the units."""
    if chunk % _LANES or d_state % _LANES or d_head % 16:
        return None
    r, limit = heads // groups, _vmem_limit()
    for hb in range(heads, 0, -1):
        if (heads % hb == 0 and (hb % r == 0 or r % hb == 0) and (hb * d_head) % _LANES == 0
                and _vmem_bytes(hb, d_head, d_state, chunk, max(1, hb // r)) <= limit):
            return hb
    return None


def _decay(exponent, keep):
    return jnp.exp(jnp.where(keep, exponent, -jnp.inf))


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _nt(a, b):  # a (m, k), b (n, k) -> (m, n)
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=_F32)


def _tn(a, b):  # a (k, m), b (k, n) -> (m, n)
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=_F32)


def _reach(sc_ref, sr_ref):
    """(Q, Q) bool at [l, s]: step s reaches step l >= s of the same
    segment; and its transpose."""
    Q = sc_ref.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    same = sc_ref[...] == sr_ref[...]
    return same & (row >= col), same & (col >= row)


def _fold(a, width):
    """(rows, Q) -> (rows, width): the sum of its lane tiles (what a later
    sum over time does not need apart)."""
    return sum(a[:, i:i + width] for i in range(0, a.shape[1], width))


def _fwd_kernel(p, n, r, cd,
                x_ref, rows_ref, ac_ref, ar_ref, th_ref, sc_ref, sr_ref,
                b_ref, ct_ref, d_ref, s0_ref, y_ref, ent_ref, last_ref, h_scr):
    """One chunk of one head block, time along the lanes: x, y (W, Q) f32,
    W = hb * p rows; rows (4, hb, Q) f32: dt, dt * to_end, into (and to_end,
    the backward's); acum (Q, hb) and (hb, Q); through (hb, n), one value a
    row; seg (Q, 1) and (1, Q) int; B (Q, gb * n) and C transposed
    (gb * n, Q) in ``cd``; D (W, Q); the state (W, n) f32: ``s0`` in,
    ``entered`` per chunk and ``last`` out, ``h`` the scratch that carries it
    along the chunk axis."""
    c = pl.program_id(2)
    hb = ar_ref.shape[0]
    rb = min(r, hb)  # heads of one group inside this block

    @pl.when(c == 0)
    def _():
        h_scr[...] = s0_ref[...]

    H = h_scr[...]
    ent_ref[...] = H
    Hb = H.astype(cd)
    _, reach_t = _reach(sc_ref, sr_ref)
    for gi in range(hb // rb):
        rows = slice(gi * rb * p, (gi + 1) * rb * p)
        Bg, Ctg = b_ref[:, gi * n:(gi + 1) * n], ct_ref[gi * n:(gi + 1) * n, :]
        CBt = _nn(Bg, Ctg)  # (Q, Q) at [s, l], shared by the group's heads
        y2 = _nn(Hb[rows], Ctg)  # the entered state's share of every step
        xw = []
        for j in range(gi * rb, (gi + 1) * rb):
            R = slice(j * p, (j + 1) * p)
            x = x_ref[R, :]
            xw.append((x * rows_ref[1, j:j + 1, :]).astype(cd))
            Lt = _decay(ar_ref[j:j + 1, :] - ac_ref[:, j:j + 1], reach_t)  # a_l - a_s at [s, l]
            y1 = _nn((x * rows_ref[0, j:j + 1, :]).astype(cd), (CBt * Lt).astype(cd))
            lo = R.start - rows.start
            y_ref[R, :] = y1 + y2[lo:lo + p] * rows_ref[2, j:j + 1, :] + x * d_ref[R, :]
        S = _nn(jnp.concatenate(xw, axis=0), Bg)  # what the chunk adds to the state
        for j in range(gi * rb, (gi + 1) * rb):  # and what it keeps of it
            R = slice(j * p, (j + 1) * p)
            lo = R.start - rows.start
            h_scr[R, :] = th_ref[j:j + 1, :] * H[R] + S[lo:lo + p]

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = h_scr[...]


def _bwd_kernel(p, n, r, cd,
                x_ref, rows_ref, ac_ref, ar_ref, th_ref, sc_ref, sr_ref,
                b_ref, bt_ref, c_ref, ct_ref, d_ref, ent_ref, dy_ref, dlast_ref,
                dx_ref, sums_ref, dth_ref, dbt_ref, dct_ref, dd_ref, ds0_ref, dh_scr):
    """The same chunk, walked from the last to the first: ``dh`` carries the
    cotangent of the state the chunk leaves; ``ent`` is the state it was
    entered with. ``sums``
    (4, hb, Q): per head and step, the sums over the head's rows of
    ``d(M dtx) * x``, ``d(xw) * x``, ``dy * (M dtx)``, ``dy * (C H)`` — from
    which the caller folds d dt, d acum, d to_end, d into. dB, dC (transposed)
    are summed over the heads of the block, ``dy * x`` (for dD) over the
    chunks in one resident block."""
    i = pl.program_id(2)  # chunk nc - 1 - i
    hb = ar_ref.shape[0]
    rb = min(r, hb)

    @pl.when(i == 0)
    def _():
        dh_scr[...] = dlast_ref[...]
        dd_ref[...] = jnp.zeros_like(dd_ref)

    H, dHp = ent_ref[...], dh_scr[...]
    Hb, dHpb = H.astype(cd), dHp.astype(cd)
    reach, reach_t = _reach(sc_ref, sr_ref)
    rowsum = lambda a: jnp.sum(a, axis=0, keepdims=True)  # noqa: E731
    for gi in range(hb // rb):
        rows = slice(gi * rb * p, (gi + 1) * rb * p)
        gn = slice(gi * n, (gi + 1) * n)
        Bg, Btg, Cg, Ctg = b_ref[:, gn], bt_ref[gn, :], c_ref[:, gn], ct_ref[gn, :]
        CB, CBt = _nn(Cg, Btg), _nn(Bg, Ctg)  # at [l, s] and at [s, l]
        y2 = _nn(Hb[rows], Ctg)
        dxw = _nn(dHpb[rows], Btg)  # (rb * p, Q): through what the chunk adds
        dCBt = jnp.zeros_like(CBt)
        xw, dY2 = [], []
        for j in range(gi * rb, (gi + 1) * rb):
            R = slice(j * p, (j + 1) * p)
            lo = R.start - rows.start
            x, dy = x_ref[R, :], dy_ref[R, :]
            dt, dtte, into, te = (rows_ref[q, j:j + 1, :] for q in range(4))
            dyb, dtxb = dy.astype(cd), (x * dt).astype(cd)
            xw.append((x * dtte).astype(cd))
            dY2.append((dy * into).astype(cd))
            e = ac_ref[:, j:j + 1] - ar_ref[j:j + 1, :]  # a_l - a_s at [l, s]
            L, Lt = _decay(e, reach), _decay(-e, reach_t)
            y1 = _nn(dtxb, (CBt * Lt).astype(cd))  # M dtx again
            ddtx1 = _nn(dyb, (CB * L).astype(cd))  # d of dtx through M dtx
            dCBt = dCBt + _tn(dtxb, dyb) * Lt
            dxw_j = dxw[lo:lo + p]
            dx_ref[R, :] = (ddtx1 + dxw_j * te) * dt + dy * d_ref[R, :]
            sums_ref[0, j:j + 1, :] = rowsum(ddtx1 * x)
            sums_ref[1, j:j + 1, :] = rowsum(dxw_j * x)
            sums_ref[2, j:j + 1, :] = rowsum(dy * y1)
            sums_ref[3, j:j + 1, :] = rowsum(dy * y2[lo:lo + p])
            dd_ref[R, :] += _fold(dy * x, dd_ref.shape[1])
        xw, dY2 = jnp.concatenate(xw, axis=0), jnp.concatenate(dY2, axis=0)
        dH = _nn(dY2, Cg)  # through the entered state's share of every step
        for j in range(gi * rb, (gi + 1) * rb):
            R = slice(j * p, (j + 1) * p)
            lo = R.start - rows.start
            dth_ref[j:j + 1, :] = rowsum(dHp[R] * H[R])
            dh_scr[R, :] = th_ref[j:j + 1, :] * dHp[R] + dH[lo:lo + p]
        dCBtb = dCBt.astype(cd)
        dct_ref[gn, :] = _tn(Hb[rows], dY2) + _nn(Btg, dCBtb)
        dbt_ref[gn, :] = _tn(dHpb[rows], xw) + _nt(Ctg, dCBtb)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        ds0_ref[...] = dh_scr[...]


class _Call:
    """One call's static shapes, the operands in the kernels' layouts and the
    block specs of both passes. ``chunk_of`` maps the grid's third index to a
    chunk: the identity forward, reversed backward. In a block shape None is
    a squeezed axis."""

    def __init__(self, cfg, x, dt, acum, to_end, into, through, B, C, D, seg):
        self.cd, self.hb, self.interpret = cfg
        self.b, self.nc, self.Q, self.h = dt.shape
        self.p = x.shape[-1]
        self.g, self.n = B.shape[2:]
        self.nhb, self.r = self.h // self.hb, self.h // self.g
        self.gb = max(1, self.hb // self.r)  # groups a block holds
        b, nc, Q, hb, p, cd = self.b, self.nc, self.Q, self.hb, self.p, self.cd
        self.W, self.HP, self.T = hb * p, self.h * p, nc * Q
        self.fold = min(Q, _LANES)

        def blocks(a):  # (b, nc, Q, h) -> (b, nc, nhb, Q, hb)
            return a.reshape(b, nc, Q, self.nhb, hb).transpose(0, 1, 3, 2, 4)

        ac = blocks(acum)
        Bf, Cf = (a.reshape(b, self.T, -1).astype(cd) for a in (B, C))
        self.rows = jnp.stack(  # (b, nc, nhb, 4, hb, Q)
            [blocks(a).swapaxes(-1, -2) for a in (dt, dt * to_end, into, to_end)], axis=3)
        self.ops = dict(
            ac=ac, ar=ac.swapaxes(-1, -2),
            th=jnp.broadcast_to(through.astype(_F32).reshape(b, nc, self.nhb, hb, 1),
                                (b, nc, self.nhb, hb, self.n)),
            sc=seg[..., None].astype(jnp.int32), sr=seg[:, :, None].astype(jnp.int32),
            B=Bf, Bt=Bf.swapaxes(1, 2), C=Cf, Ct=Cf.swapaxes(1, 2),
            d=jnp.broadcast_to(jnp.repeat(D.astype(_F32), p)[:, None], (self.HP, Q)),
        )
        self.grid = (b, self.nhb, nc)

    def time_minor(self, a):  # (b, T, h, p) -> (b, h * p, T): where XLA keeps time anyway
        return a.reshape(self.b, self.T, self.HP).swapaxes(1, 2).astype(_F32)

    def time_major(self, a_t, like):  # and back, to the shape of ``like``
        return a_t.swapaxes(1, 2).reshape(like.shape)

    def specs(self, chunk_of):
        Q, hb, W, n, gb = self.Q, self.hb, self.W, self.n, self.gb
        g_of = lambda k: (k * hb // self.r) // gb  # noqa: E731 — the block's groups

        def spec(shape, index):
            return pl.BlockSpec(shape, lambda i, k, c: index(i, k, chunk_of(c)))

        small = lambda *shape: spec(  # noqa: E731
            (None, None, None, *shape), lambda i, k, c: (i, c, k) + (0,) * len(shape))
        return dict(
            x=spec((None, W, Q), lambda i, k, c: (i, k, c)),
            rows=small(4, hb, Q), ac=small(Q, hb), ar=small(hb, Q), th=small(hb, n),
            sc=spec((None, None, Q, 1), lambda i, k, c: (i, c, 0, 0)),
            sr=spec((None, None, 1, Q), lambda i, k, c: (i, c, 0, 0)),
            B=spec((None, Q, gb * n), lambda i, k, c: (i, c, g_of(k))),
            Bt=spec((None, gb * n, Q), lambda i, k, c: (i, g_of(k), c)),
            d=spec((W, Q), lambda i, k, c: (k, 0)),
            state=spec((None, W, n), lambda i, k, c: (i, k, 0)),
            entered=spec((None, None, W, n), lambda i, k, c: (i, c, k, 0)),
            sums=small(4, hb, Q),
            part=spec((None, None, gb * n, Q), lambda i, k, c: (i, k, 0, c)),
            dd=spec((None, W, self.fold), lambda i, k, c: (i, k, 0)),
        )

    def call(self, kernel, names, operands, out_names, out_shape, chunk_of, name):
        s = self.specs(chunk_of)
        return pl.pallas_call(
            functools.partial(kernel, self.p, self.n, self.r, self.cd),
            grid=self.grid,
            in_specs=[s[k] for k in names],
            out_specs=[s[k] for k in out_names],
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((self.W, self.n), _F32)],
            interpret=self.interpret,
            compiler_params=None if self.interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(),
            ),
            name=name,
        )(*operands)


# Both passes are jitted: the layers of a model (and every later trace of its
# train step in the process) then share one trace and one lowering of each
# kernel. Unrolled over up to 32 heads, a kernel costs ~0.4 s to trace and
# lower; 27 of them added 12 s to every trace of the cell's update program and
# 75 s to its set-up (PERF.md, PR 29).
@functools.partial(jax.jit, static_argnums=(0,))
def _forward(cfg, x, dt, acum, to_end, into, through, B, C, D, state0, seg):
    """``(y, last, entered)``: the state each chunk was entered with is the
    backward's residual, written by the one forward kernel either way (0.1 ms
    of the cell's 450 ms an update where nothing reads it)."""
    k = _Call(cfg, x, dt, acum, to_end, into, through, B, C, D, seg)
    o = k.ops
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, _F32)  # noqa: E731
    y_t, entered, last = k.call(
        _fwd_kernel,
        ["x", "rows", "ac", "ar", "th", "sc", "sr", "B", "Bt", "d", "state"],
        (k.time_minor(x), k.rows, o["ac"], o["ar"], o["th"], o["sc"], o["sr"],
         o["B"], o["Ct"], o["d"], state0.astype(_F32).reshape(k.b, k.HP, k.n)),
        ["x", "entered", "state"],
        [f32(k.b, k.HP, k.T), f32(k.b, k.nc, k.HP, k.n), f32(k.b, k.HP, k.n)],
        lambda c: c, "ssd_fwd",
    )
    return k.time_major(y_t, x), last.reshape(state0.shape), entered


@functools.partial(jax.jit, static_argnums=(0,))
def _backward(cfg, res, dy, dlast):
    x, dt, acum, to_end, into, through, B, C, D, seg, entered = res
    k = _Call(cfg, x, dt, acum, to_end, into, through, B, C, D, seg)
    o = k.ops
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, _F32)  # noqa: E731
    b, nc, nhb, Q, hb = k.b, k.nc, k.nhb, k.Q, k.hb
    part = f32(b, nhb, k.gb * k.n, k.T)
    dx_t, sums, dth, dbt_part, dct_part, dd, ds0 = k.call(
        _bwd_kernel,
        ["x", "rows", "ac", "ar", "th", "sc", "sr", "B", "Bt", "B", "Bt",
         "d", "entered", "x", "state"],
        (k.time_minor(x), k.rows, o["ac"], o["ar"], o["th"], o["sc"], o["sr"],
         o["B"], o["Bt"], o["C"], o["Ct"], o["d"], entered,
         k.time_minor(dy), dlast.astype(_F32).reshape(b, k.HP, k.n)),
        ["x", "sums", "th", "part", "part", "dd", "state"],
        [f32(b, k.HP, k.T), f32(b, nc, nhb, 4, hb, Q), f32(b, nc, nhb, hb, k.n), part, part,
         f32(b, k.HP, k.fold), f32(b, k.HP, k.n)],
        lambda c: nc - 1 - c, "ssd_bwd",
    )
    # (b, nc, nhb, 4, hb, Q) -> four (b, nc, Q, h)
    s_ddtx1, s_dxw, s_y1, s_y2 = (
        sums[:, :, :, q].transpose(0, 1, 4, 2, 3).reshape(b, nc, Q, k.h) for q in range(4))
    ddt = s_ddtx1 + to_end * s_dxw  # dtx = x dt, xw = dtx to_end
    dte = dt * s_dxw
    dacum = s_y1 - dt * s_ddtx1  # row sums of d(exponent) less its column sums
    dthrough = dth.sum(-1).reshape(b, nc, k.h)

    def groups(part):  # (b, nhb, gb * n, T): summed over the head blocks of a group
        if hb % k.r:  # several blocks inside one group
            part = part.reshape(b, k.g, nhb // k.g, k.n, k.T).sum(2)
        return part.reshape(b, k.g * k.n, k.T).swapaxes(1, 2).reshape(B.shape).astype(B.dtype)

    dD = dd.reshape(b, k.h, -1).sum((0, 2)).astype(D.dtype)
    return (k.time_major(dx_t, x).astype(x.dtype), ddt, dacum, dte, s_y2, dthrough,
            groups(dbt_part), groups(dct_part), dD, ds0.reshape(dlast.shape), None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunks(cfg, x, dt, acum, to_end, into, through, B, C, D, state0, seg):
    """The kernel pair. ``cfg = (matmul dtype, heads a grid step, interpret)``;
    ``x`` (b, T, h, p); ``dt``, ``acum``, ``to_end``, ``into`` (b, nc, Q, h);
    ``through`` (b, nc, h); ``B``, ``C`` (b, T, g, n); ``seg`` (b, nc, Q).
    Returns ``(y, last)``."""
    return _forward(cfg, x, dt, acum, to_end, into, through, B, C, D, state0, seg)[:2]


def _chunks_fwd(cfg, x, dt, acum, to_end, into, through, B, C, D, state0, seg):
    y, last, entered = _forward(cfg, x, dt, acum, to_end, into, through, B, C, D, state0, seg)
    return (y, last), (x, dt, acum, to_end, into, through, B, C, D, seg, entered)


def _chunks_bwd(cfg, res, ct):
    return _backward(cfg, res, *ct)


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def scan_window(x, dt, A, B, C, D, seg, state0, chunk: int, dtype, hb: int, interpret: bool):
    """``ssd_chunked``'s contract (a window that is a multiple of the chunk;
    ``x`` (b, T, h, p), ``dt`` (b, T, h), ``B``, ``C`` (b, T, g, n), ``seg``
    (b, T), ``state0`` (b, h, p, n)) on the kernel pair, ``hb`` heads a grid
    step. Returns ``y`` (b, T, h, p) float32 and the last state. (Not named
    after a path scope: ``utils.platform.program_paths`` reads a lowered
    module's text, and that holds the function names of cached traces.)"""
    b, T, h, _ = x.shape
    Q, nc = chunk, T // chunk
    segc = seg.reshape(b, nc, Q)
    # the segment a chunk is entered in: that of the step before it
    seg_in = jnp.concatenate([jnp.zeros_like(segc[:, :1, 0]), segc[:, :-1, -1]], axis=1)
    dtc = dt.astype(_F32).reshape(b, nc, Q, h)
    acum = jnp.cumsum(dtc * A, axis=2)  # (b, nc, Q, h)
    # each step's share of what the chunk adds to the state at its end
    to_end = _decay(acum[:, :, -1:] - acum, (segc == segc[:, :, -1:])[..., None])
    # what a step still sees of the state the chunk was entered with
    into = _decay(acum, (segc == seg_in[:, :, None])[..., None])
    # what a chunk keeps of that state: nothing past a seam
    through = _decay(acum[:, :, -1], (segc[:, :, -1] == seg_in)[..., None])  # (b, nc, h)
    return _chunks((dtype or _F32, hb, interpret), x, dtc, acum, to_end, into, through,
                   B, C, D, state0, segc)
