"""Chunked gated delta rule as one Pallas TPU kernel per pass.

``ops/gated_delta.gated_delta_chunked`` in its ``jax.numpy`` form writes every
chunk's ``D``, ``A``, ``U``, ``W``, entering state and ``V'`` to HBM as HLO
results of their own, stacks them with two ``lax.scan``s and runs a span's
forward a third time for its ``jax.checkpoint``. Here one kernel walks the
chunks of a window *in order* — grid ``(rows, head blocks, chunks)``, the chunk
axis sequential — with the carried state of a head block (``d_k x d_v`` float32
a value head) in a VMEM scratch; ``D``, ``K̂K̂ᵀ``, ``Q̂K̂ᵀ``, the triangle's
inverse ``A``, ``U``, ``W`` and ``V'`` of a chunk never leave VMEM. The backward
kernel walks the chunks in reverse, ``_GROUP`` of them a grid step, with the
state's cotangent in a scratch, and recomputes ``D``, ``U``, ``W`` and ``V'``
from the kernel's inputs and the differentiated forward's two residuals: the
state every ``_GROUP``-th chunk was entered with (from which a step's other
entering states are computed again, into a second scratch) and each chunk's
``A`` (16 KB a chunk and head beside a state's 64 KB; the inverse is most of
what a chunk costs, so the backward reads it instead of inverting again). The
un-differentiated forward (a layer's first pass under ``nn.remat``) writes
neither.

The L2 norms of q and k are the kernels' too (float32, a key head's tile at a
time): in ``jax.numpy`` they were taken on the ``(b, T, h, d)`` view, which XLA
lays out apart from the ``(b, T, h * d)`` one the projections and the
convolution leave, a copy of q, k and each cotangent a pass. What stays
outside, under the caller's autodiff: the cumulative sum of ``g`` per chunk
and the per-step decay vectors (``into``, ``to_end``, ``through``) under the
same same-segment masks as the ``jax.numpy`` form.

Precision is that form's: the operands of every product in ``dtype`` (bf16 in
the registered cell) with float32 accumulation, except the triangle's inverse
and its transpose rule, whose products are float32 at float32 precision;
decays, masks, sums and the carried state in float32; a seam is a
``where(keep, e, -inf)`` before ``exp``. The inverse is by blocks
(``_unit_lower_inverses``): forward substitution inside diagonal blocks of 16
rows, then two doublings, each two float32 products. The backward casts
cotangents to ``dtype`` at its products, as ``pallas_lstm.mixed_dot`` does.

Layout: a chunk's steps lie on the sublanes and a head's features on the
lanes, so a tile is ``(chunk, heads a step * d)`` of the ``(b, T, h * d)`` view
of a ``(b, T, h, d)`` window, which q, k, v and their cotangents enter and leave
by (XLA re-lays dq, dk and dv for the convolution's backward: 2.4 ms an update
at the cell's widths). ``o`` and its cotangent are float32 and keep the
``(b, T, h, d)`` tiles the gated norm reads them in — a step's eight heads on
the sublanes of one tile, a head's rows read and written with a stride: 4% more
bundles a pass, against 256 MiB re-laid a layer and pass. Per-step
factors come in both orientations (``(chunk, 1)`` columns that scale rows, the
four of a head block side by side on the lanes of one tile; ``(1, chunk)``
rows that scale columns), a few KB a chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_rl.ops.pallas_ssd import _decay, _nn, _nt, _tn, _vmem_limit

_F32 = jnp.float32
L2_EPS = 1e-6  # ``gated_delta.L2_EPS``
_LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def _vmem_bytes(hb: int, dk: int, dv: int, r: int, Q: int, itemsize: int = 4) -> int:
    """VMEM the kernels need with ``hb`` value heads a grid step: the blocks
    of the backward (the larger pass: ``_GROUP`` chunks a step) double-buffered
    plus its scratch — at the cell's widths and 8 heads 13.5 MiB here, which
    is Mosaic's own count of them, 17.5 MiB with what it spills — and as much
    again for what a kernel keeps between its products."""
    G = _GROUP
    tiles = lambda rows, lanes: 4 * -(-rows // 8) * 8 * -(-lanes // _LANES) * _LANES  # noqa: E731
    state = 4 * hb * dk * dv
    qkv = itemsize * G * Q * (2 * (hb // r) * dk + hb * dv)  # and their cotangents
    # columns, rows, through; and their cotangents
    steps = G * (tiles(Q, 4 * hb) + 3 * tiles(hb, Q) + tiles(hb, dv))
    seg = G * (tiles(Q, 1) + tiles(1, Q))
    read = qkv + steps + seg + 2 * state + 4 * G * Q * hb * (Q + dv)  # entered, dlast; A, do
    return 2 * (2 * (read + qkv + steps + state) + (G + 1) * state)


def head_block(hv: int, hk: int, dk: int, dv: int, chunk: int) -> int | None:
    """Value heads per grid step of a compiled call, or None when no block
    fits the kernels: key and value sizes are lane multiples, the chunk fills
    bf16 sublane groups, a block holds whole groups of the value heads one key
    head serves and whole tiles of ``o`` (eight heads a step, or every head),
    and the kernels' need (``_vmem_bytes``) is inside what the call asks for
    (``_vmem_limit``). The most heads that fit, up to ``_MAX_HEADS``: the
    kernels are unrolled over a block's heads."""
    if dk % _LANES or dv % _LANES or chunk % 16 or hv % hk:
        return None
    r, limit = hv // hk, _vmem_limit()
    for hb in range(min(hv, _MAX_HEADS), 0, -1):
        tiles = hb % 8 == 0 or hb == hv
        if hv % hb == 0 and hb % r == 0 and tiles and _vmem_bytes(hb, dk, dv, r, chunk) <= limit:
            return hb
    return None


# Heads a grid step at most: a step's products are independent chains the
# scheduler interleaves over the matrix units, and eight of them fill the four.
_MAX_HEADS = 8


def _f32_dot(a, b, dims):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=_HIGHEST, preferred_element_type=_F32)


# Rows of the diagonal blocks that are inverted by substitution on the vector
# unit; from there a block doubles by two float32 products.
_BASE = 16


def _unit_lower_inverses(ns, n_scr):
    """``(I + n)^-1`` for each strictly lower-triangular ``n`` (Q, Q) float32 of
    a list, by blocks: the diagonal blocks of ``_BASE`` rows by forward
    substitution (``X <- X - n[:, s] X[s, :]`` a column ``s``, all blocks of a
    head at once, on the vector unit: ``n_scr`` (heads * Q, Q) holds ``n`` so
    that a column can be read as one), then ``[[X1, 0], [-X2 n21 X1, X2]]`` a
    doubling: ``X <- X - X (n_off X)`` with ``n_off`` the blocks that join two
    inverted ones, float32 products at float32 precision (two a doubling: four
    for a chunk of 64 against the ten of ``gated_delta._unit_lower_inverse``'s
    repeated squaring, which cost the matrix unit most of a chunk). The heads
    of a block in lockstep: a product waits for the one before it in its own
    chain alone, so the matrix units run the block's chains side by side."""
    Q = ns[0].shape[0]
    base = min(_BASE, Q)
    assert Q % base == 0 and base & (base - 1) == 0 and (Q // base) & (Q // base - 1) == 0, Q
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    for j, n in enumerate(ns):
        n_scr[j * Q:(j + 1) * Q, :] = n
    blocks = range(Q // base)
    eye = (row == col).astype(_F32)
    X = [[eye[b * base:(b + 1) * base] for b in blocks] for _ in ns]
    for s in range(base - 1):
        X = [[x - n_scr[j * Q + b * base:j * Q + (b + 1) * base, b * base + s:b * base + s + 1]
              * x[s:s + 1, :] for b, x in zip(blocks, Xj)] for j, Xj in enumerate(X)]
    X = [jnp.concatenate(Xj, axis=0) if len(Xj) > 1 else Xj[0] for Xj in X]
    size = base
    while size < Q:
        shift = size.bit_length() - 1
        joins = ((row >> (shift + 1)) == (col >> (shift + 1))) & ((row >> shift) != (col >> shift))
        inner = [_f32_dot(jnp.where(joins, n, 0.0), x, ((1,), (0,))) for n, x in zip(ns, X)]
        X = [x - _f32_dot(x, y, ((1,), (0,))) for x, y in zip(X, inner)]
        size *= 2
    return X


def _unit(x, scale: float = 1.0):
    """``gated_delta.l2norm(x) * scale`` over the lanes, float32, and the
    factor each row was multiplied by."""
    x = x.astype(_F32)
    factor = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + L2_EPS) * scale
    return x * factor, factor


def _unit_bwd(y, factor, dy, scale: float = 1.0):
    """The cotangent of ``x`` from that of ``y = _unit(x, scale)[0]``: with
    ``u = y / scale``, ``factor (dy - u (u . dy))``."""
    return factor * (dy - y * (jnp.sum(y * dy, axis=1, keepdims=True) * scale ** -2))


def _masks(sc, sr):
    """(Q, Q) bool at [i, j]: step j reaches step i >= j of the same segment;
    and j < i alone."""
    Q = sc.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return (sc == sr) & (row >= col), row > col


def _each(f, *lists):
    return [f(*xs) for xs in zip(*lists)]


def _columns(cols_ref, c, hb):
    """Chunk ``c``'s (Q, 1) columns of a (G, Q, 4 * hb) block: gamma, beta,
    into and to_end of each head (the four side by side on the lanes: a (4, Q,
    hb) block would be padded to four times the tiles)."""
    return [[cols_ref[c, :, x * hb + j:x * hb + j + 1] for x in range(4)] for j in range(hb)]


def _chunk_inputs(dk, r, cd, c, at, q_ref, k_ref, cols_ref, rows_ref, sc_ref, sr_ref):
    """What both passes compute first of chunk ``c`` of a block (its steps
    the rows ``at``): a list entry a key head of the block (its lanes;
    normalised q and k with their factors, and in ``cd``) and a value head (q,
    k and their two products as its key head has them; its per-step columns
    and rows; its decay matrix)."""
    heads = range(rows_ref.shape[2])
    reach, strict = _masks(sc_ref[c], sr_ref[c])
    keys = [slice(g * dk, (g + 1) * dk) for g in range(len(heads) // r)]
    q = [_unit(q_ref[at, K], dk ** -0.5) for K in keys]
    k = [_unit(k_ref[at, K]) for K in keys]
    q_b, k_b = [y.astype(cd) for y, _ in q], [y.astype(cd) for y, _ in k]
    of = lambda per_key: [per_key[j // r] for j in heads]  # noqa: E731
    KK, QK = _each(_nt, k_b, k_b), _each(_nt, q_b, k_b)  # shared by a key head's value heads
    cols = _columns(cols_ref, c, len(heads))
    rows = [[rows_ref[c, x, j:j + 1, :] for x in range(3)] for j in heads]
    D = [_decay(col[0] - row[0], reach) for col, row in zip(cols, rows)]
    return (keys, q, k, q_b, k_b), (of(q_b), of(k_b), of(KK), of(QK), cols, rows, D, strict)


def _deltas(cd, A, rows, kh, vh, S0b):
    """From a chunk's inverse on, a list entry a value head: ``A diag(beta)``
    and ``A diag(beta D_.0)`` in ``cd``, ``W`` in ``cd`` and ``V' = U - W S0``,
    every delta of the chunk."""
    Ab = [a * w[1] for a, w in zip(A, rows)]
    Abb = [x.astype(cd) for x in Ab]
    Abib = [(a * w[2]).astype(cd) for a, w in zip(Ab, rows)]
    Wb = [x.astype(cd) for x in _each(_nn, Abib, kh)]
    Vp = [u - ws for u, ws in zip(_each(_nn, Abb, vh), _each(_nn, Wb, S0b))]
    return Abb, Abib, Wb, Vp


def _leaves(cd, th, cols, kh, Vp, S0):
    """The state a chunk leaves: ``D_C0 S0 + (K D_C.)^T V'``, a value head."""
    written = _each(_tn, kh, [(c[3] * x).astype(cd) for c, x in zip(cols, Vp)])
    return [t * s + w for t, s, w in zip(th, S0, written)]


def _fwd_kernel(group, dk, dv, r, cd,
                q_ref, k_ref, v_ref, cols_ref, rows_ref, th_ref, sc_ref, sr_ref, s0_ref,
                o_ref, last_ref, *rest):
    """One chunk of one head block: q, k (Q, key heads * dk) as projected and
    v (Q, hb * dv) in ``cd``; cols (Q, 4 * hb) f32: gamma, beta, into, to_end
    as columns; rows (3, hb, Q): gamma, beta, into as rows; through (hb, dv),
    one value a row; seg (Q, 1) and (1, Q) int; the state (hb * dk, dv) f32:
    ``s0`` in, ``last`` out, ``s`` the scratch that carries it along the chunk
    axis; o (Q, hb, dv) f32. The per-chunk operands carry a leading axis of
    one chunk (the backward's blocks hold ``group``). With ``group`` (the
    differentiated forward) also the backward's residuals: ``entered``, the
    state every ``group``-th chunk was entered with, and each chunk's ``A``
    (Q, hb * Q). Every line is the block's heads in lockstep
    (``_unit_lower_inverses``)."""
    ent_ref, a_ref, s_scr, n_scr = rest if group else (None, None, *rest)
    c = pl.program_id(2)
    hb, Q = rows_ref.shape[2:]

    @pl.when(c == 0)
    def _():
        s_scr[...] = s0_ref[...]

    if group:
        @pl.when(c % group == 0)
        def _():
            ent_ref[...] = s_scr[...]
    bf = lambda xs: [x.astype(cd) for x in xs]  # noqa: E731
    heads = range(hb)
    _, (qh, kh, KK, QK, cols, rows, D, strict) = _chunk_inputs(
        dk, r, cd, 0, slice(None), q_ref, k_ref, cols_ref, rows_ref, sc_ref, sr_ref)
    S0 = [s_scr[j * dk:(j + 1) * dk, :] for j in heads]
    S0b = bf(S0)
    A = _unit_lower_inverses(
        [jnp.where(strict, c[1] * kk * d, 0.0) for c, kk, d in zip(cols, KK, D)], n_scr)
    vh = [v_ref[:, j * dv:(j + 1) * dv] for j in heads]
    _, _, _, Vp = _deltas(cd, A, rows, kh, vh, S0b)
    o_in = _each(_nn, qh, S0b)
    o_own = _each(_nn, bf([qk * d for qk, d in zip(QK, D)]), bf(Vp))
    left = _leaves(cd, [th_ref[0, j:j + 1, :] for j in heads], cols, kh, Vp, S0)
    for j in heads:
        o_ref[:, j, :] = cols[j][2] * o_in[j] + o_own[j]
        s_scr[j * dk:(j + 1) * dk, :] = left[j]
        if group:
            a_ref[0, :, j * Q:(j + 1) * Q] = A[j]

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = s_scr[...]


def _bwd_kernel(dk, dv, r, cd,
                q_ref, k_ref, v_ref, cols_ref, rows_ref, th_ref, sc_ref, sr_ref,
                ent_ref, a_ref, do_ref, dlast_ref,
                dq_ref, dk_ref, dv_ref, dcols_ref, drows_ref, dth_ref, ds0_ref, dh_scr, s_scr):
    """A group of G chunks a grid step, the groups walked from the last to the
    first: the per-chunk blocks hold G chunks (q, k, v, do and their
    cotangents G * Q rows; the small operands and ``a`` a leading axis of G).
    ``ent`` is the state the group was entered with: the states its other
    chunks were entered with are computed again into ``s_scr`` (G, hb * dk,
    dv), then the chunks are walked in reverse with ``dh`` carrying the
    cotangent of the state a chunk leaves. dcols (Q, 4 * hb) a chunk: the
    cotangents of gamma (its row sums), beta, into (their shares that scale
    rows) and to_end; drows (3, hb, Q): gamma's column sums (to subtract), and
    beta's and into's shares that scale columns; dth (hb, dv): through's, to
    sum over a row. dq and dk are summed over the value heads a key head
    serves, then taken through the norm. The heads in lockstep, as the
    forward."""
    i = pl.program_id(2)  # group ng - 1 - i
    G, hb, Q = rows_ref.shape[0], *rows_ref.shape[2:]
    heads = range(hb)
    bf = lambda xs: [x.astype(cd) for x in xs]  # noqa: E731

    @pl.when(i == 0)
    def _():
        dh_scr[...] = dlast_ref[...]

    def steps(c):  # the rows of chunk ``c`` in a block of G * Q
        return pl.ds(pl.multiple_of(c * Q, Q), Q)

    def enter(c, _):
        """``s_scr[c + 1]`` from ``s_scr[c]``."""
        k_b = [_unit(k_ref[steps(c), g * dk:(g + 1) * dk])[0].astype(cd)
               for g in range(hb // r)]
        kh = [k_b[j // r] for j in heads]
        cols = _columns(cols_ref, c, hb)
        rows = [[rows_ref[c, x, j:j + 1, :] for x in range(3)] for j in heads]
        S0 = [s_scr[c, j * dk:(j + 1) * dk, :] for j in heads]
        A = [a_ref[c, :, j * Q:(j + 1) * Q] for j in heads]
        vh = [v_ref[steps(c), j * dv:(j + 1) * dv] for j in heads]
        Vp = _deltas(cd, A, rows, kh, vh, bf(S0))[3]
        th = [th_ref[c, j:j + 1, :] for j in heads]
        for j, left in enumerate(_leaves(cd, th, cols, kh, Vp, S0)):
            s_scr[c + 1, j * dk:(j + 1) * dk, :] = left

    s_scr[0] = ent_ref[...]
    if G > 1:
        jax.lax.fori_loop(0, G - 1, enter, None)

    rowsum = lambda a: jnp.sum(a, axis=1, keepdims=True)  # noqa: E731
    colsum = lambda a: jnp.sum(a, axis=0, keepdims=True)  # noqa: E731

    def back(n, _):
        c = G - 1 - n
        at = steps(c)
        (keys, q, k, q_b, k_b), (qh, kh, KK, QK, cols, rows, D, strict) = _chunk_inputs(
            dk, r, cd, c, at, q_ref, k_ref, cols_ref, rows_ref, sc_ref, sr_ref)
        _, bc, inc, tec = zip(*cols)
        _, br, inr = zip(*rows)
        vh = [v_ref[at, j * dv:(j + 1) * dv] for j in heads]
        do = [do_ref[at, j, :] for j in heads]
        S0 = [s_scr[c, j * dk:(j + 1) * dk, :] for j in heads]
        dS = [dh_scr[j * dk:(j + 1) * dk, :] for j in heads]
        A = [a_ref[c, :, j * Q:(j + 1) * Q] for j in heads]
        th = [th_ref[c, j:j + 1, :] for j in heads]
        dob, S0b, dSb = bf(do), bf(S0), bf(dS)
        # the chunk again, from its inverse on
        Abb, Abib, Wb, Vp = _deltas(cd, A, rows, kh, vh, S0b)
        o_in = _each(_nn, qh, S0b)
        P2b = bf([m * d for m, d in zip(QK, D)])
        # through the state the chunk leaves, and the output
        dZ = _each(_nn, kh, dSb)
        dk_written = _each(_nt, bf([t * x for t, x in zip(tec, Vp)]), dSb)
        dVp = [t * z + p for t, z, p in zip(tec, dZ, _each(_tn, P2b, dob))]
        d_o_in = bf([w * x for w, x in zip(inc, do)])
        dq_in = _each(_nt, d_o_in, S0b)
        dP2 = _each(_nt, dob, bf(Vp))
        # through V' = U - W S0
        dVpb = bf(dVp)
        dWb = bf([-x for x in _each(_nt, dVpb, S0b)])
        dS0 = [t * s + a - w for t, s, a, w in zip(
            th, dS, _each(_tn, qh, d_o_in), _each(_tn, Wb, dVpb))]
        dvh = _each(_tn, Abb, dVpb)
        dk_w = _each(_tn, Abib, dWb)
        dAbi = _each(_nt, dWb, kh)
        dAb = [x + y * w for x, y, w in zip(_each(_nt, dVpb, vh), dAbi, inr)]
        # through the inverse: d n = -A^T dA A^T, strictly lower
        dN = [_f32_dot(a, x * w, ((0,), (0,))) for a, x, w in zip(A, dAb, br)]
        dN = [jnp.where(strict, -_f32_dot(x, a, ((1,), (1,))), 0.0) for x, a in zip(dN, A)]
        dNb = [x * w for x, w in zip(dN, bc)]
        dE = [(p * m + x * kk) * d for p, m, x, kk, d in zip(dP2, QK, dNb, KK, D)]  # the exponent's
        for j in heads:
            dh_scr[j * dk:(j + 1) * dk, :] = dS0[j]
            dv_ref[at, j * dv:(j + 1) * dv] = dvh[j].astype(dv_ref.dtype)
            dth_ref[c, j:j + 1, :] = colsum(dS[j] * S0[j])
            for x, d in enumerate((dE[j], dN[j] * KK[j] * D[j], do[j] * o_in[j], dZ[j] * Vp[j])):
                dcols_ref[c, :, x * hb + j:x * hb + j + 1] = rowsum(d)
            drows_ref[c, 0, j:j + 1, :] = colsum(dE[j])
            drows_ref[c, 1, j:j + 1, :] = colsum(dAb[j] * A[j])
            drows_ref[c, 2, j:j + 1, :] = colsum(dAbi[j] * A[j]) * br[j]
        for g, K in enumerate(keys):
            mine = range(g * r, (g + 1) * r)
            dKKb = sum(dNb[j] * D[j] for j in mine).astype(cd)
            dQKb = sum(dP2[j] * D[j] for j in mine).astype(cd)
            dq = sum(dq_in[j] for j in mine) + _nn(dQKb, k_b[g])
            dkk = (sum(dk_written[j] + dk_w[j] for j in mine)
                   + _nn(dKKb, k_b[g]) + _tn(dKKb, k_b[g]) + _tn(dQKb, q_b[g]))
            dq_ref[at, K] = _unit_bwd(*q[g], dq, dk ** -0.5).astype(dq_ref.dtype)
            dk_ref[at, K] = _unit_bwd(*k[g], dkk).astype(dk_ref.dtype)

    jax.lax.fori_loop(0, G, back, None)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        ds0_ref[...] = dh_scr[...]


# Chunks a grid step of the backward: the differentiated forward keeps the
# state every ``_GROUP``-th chunk was entered with (64 KB a chunk and head:
# every chunk's of an 8,192-step window at the cell's widths is 512 MiB a
# layer, which the update program's peak does not have), and the backward
# computes the others again, one product more a chunk.
_GROUP = 4


class _Call:
    """One call's static shapes, the operands in the kernels' layouts and the
    block specs of both passes. ``group``: the chunks that share an entered
    state. ``specs(chunk_of, G)``: blocks of ``G`` chunks, ``chunk_of`` mapping
    the grid's third index to a block of chunks: the identity forward,
    reversed backward. In a block shape None is a squeezed axis."""

    def __init__(self, cfg, q, k, v, gamma, beta, into, to_end, through, seg):
        self.cd, self.hb, self.interpret = cfg
        self.b, self.T, self.hk, self.dk = q.shape
        self.hv, self.dv = v.shape[2:]
        self.nc, self.Q = seg.shape[1:]
        self.r, self.nhb = self.hv // self.hk, self.hv // self.hb
        self.group = max(g for g in range(1, _GROUP + 1) if self.nc % g == 0)
        b, nc, Q, hb, nhb = self.b, self.nc, self.Q, self.hb, self.nhb

        def blocks(a):  # (b, nc, Q, hv) -> (b, nc, nhb, Q, hb)
            return a.astype(_F32).reshape(b, nc, Q, nhb, hb).transpose(0, 1, 3, 2, 4)

        flat = lambda a: a.reshape(b, self.T, -1)  # noqa: E731 — (b, T, h, d) -> (b, T, h * d)
        self.ops = (
            flat(q), flat(k), flat(v),
            jnp.concatenate([blocks(a) for a in (gamma, beta, into, to_end)], axis=-1),
            jnp.stack([blocks(a).swapaxes(-1, -2) for a in (gamma, beta, into)], axis=3),
            jnp.broadcast_to(through.astype(_F32).reshape(b, nc, nhb, hb, 1),
                             (b, nc, nhb, hb, self.dv)),
            seg[..., None].astype(jnp.int32), seg[:, :, None].astype(jnp.int32),
        )

    def specs(self, chunk_of, G):
        Q, hb, dk, dv = self.Q, self.hb, self.dk, self.dv
        kb = hb // self.r  # key heads a block

        def spec(shape, index):
            return pl.BlockSpec(shape, lambda i, h, c: index(i, h, chunk_of(c)))

        small = lambda *shape: spec(  # noqa: E731
            (None, G, None, *shape), lambda i, h, c: (i, c, h) + (0,) * len(shape))
        return dict(
            qk=spec((None, G * Q, kb * dk), lambda i, h, c: (i, c, h)),
            v=spec((None, G * Q, hb * dv), lambda i, h, c: (i, c, h)),
            cols=small(Q, 4 * hb), rows=small(3, hb, Q), th=small(hb, dv),
            sc=spec((None, G, Q, 1), lambda i, h, c: (i, c, 0, 0)),
            sr=spec((None, G, 1, Q), lambda i, h, c: (i, c, 0, 0)),
            state=spec((None, hb * dk, dv), lambda i, h, c: (i, h, 0)),
            entered=spec((None, None, hb * dk, dv), lambda i, h, c: (i, c * G // self.group, h, 0)),
            a=small(Q, hb * Q),
            o=spec((None, G * Q, hb, dv), lambda i, h, c: (i, c, h, 0)),
        )

    def call(self, kernel, G, names, operands, out_names, out_shape, chunk_of, name, scratch):
        s = self.specs(chunk_of, G)
        return pl.pallas_call(
            functools.partial(kernel, self.dk, self.dv, self.r, self.cd),
            grid=(self.b, self.nhb, self.nc // G),
            in_specs=[s[k] for k in names],
            out_specs=[s[k] for k in out_names],
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((self.hb * self.dk, self.dv), _F32), *scratch],
            interpret=self.interpret,
            compiler_params=None if self.interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(),
            ),
            name=name,
        )(*operands)


_INPUTS = ["qk", "qk", "v", "cols", "rows", "th", "sc", "sr"]


# Both passes are jitted: the layers of a model (and every later trace of its
# train step in the process) then share one trace and one lowering of each
# kernel (PERF.md, PR 29: un-jitted, the scan's kernels added 12 s to every
# trace of an update program).
@functools.partial(jax.jit, static_argnums=(0, 1))
def _forward(cfg, residuals, q, k, v, gamma, beta, into, to_end, through, state0, seg):
    """``(o, last)``, and with ``residuals`` ``(o, last, entered, A)``: what
    the backward reads beside the kernel's inputs."""
    c = _Call(cfg, q, k, v, gamma, beta, into, to_end, through, seg)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, _F32)  # noqa: E731
    b, nc, HK = c.b, c.nc, c.hv * c.dk
    out_names, out_shape = ["o", "state"], [f32(b, c.T, c.hv, c.dv), f32(b, HK, c.dv)]
    if residuals:
        out_names += ["entered", "a"]
        out_shape += [f32(b, nc // c.group, HK, c.dv), f32(b, nc, c.nhb, c.Q, c.hb * c.Q)]

    out, last, *res = c.call(
        functools.partial(_fwd_kernel, c.group if residuals else 0), 1,
        [*_INPUTS, "state"], (*c.ops, state0.astype(_F32).reshape(b, HK, c.dv)),
        out_names, out_shape, lambda ch: ch, "gdn_fwd_res" if residuals else "gdn_fwd",
        scratch=[pltpu.VMEM((c.hb * c.Q, c.Q), _F32)],
    )
    return (out, last.reshape(state0.shape), *res)


@functools.partial(jax.jit, static_argnums=(0,))
def _backward(cfg, res, do, dlast):
    q, k, v, gamma, beta, into, to_end, through, seg, entered, A = res
    c = _Call(cfg, q, k, v, gamma, beta, into, to_end, through, seg)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, _F32)  # noqa: E731
    b, nc, nhb, Q, hb, HK, G = c.b, c.nc, c.nhb, c.Q, c.hb, c.hv * c.dk, c.group
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    dq, dk, dv, dcols, drows, dth, ds0 = c.call(
        _bwd_kernel, G, [*_INPUTS, "entered", "a", "o", "state"],
        (*c.ops, entered, A, do.astype(_F32),
         dlast.astype(_F32).reshape(b, HK, c.dv)),
        ["qk", "qk", "v", "cols", "rows", "th", "state"],
        [*map(like, c.ops[:3]), f32(b, nc, nhb, Q, 4 * hb),
         f32(b, nc, nhb, 3, hb, Q), f32(b, nc, nhb, hb, c.dv), f32(b, HK, c.dv)],
        lambda g: nc // G - 1 - g, "gdn_bwd",
        scratch=[pltpu.VMEM((G, hb * c.dk, c.dv), _F32)],
    )
    # (b, nc, nhb, Q, hb) and (b, nc, nhb, hb, Q) -> (b, nc, Q, hv)
    col = lambda x: dcols[..., x * hb:(x + 1) * hb].transpose(0, 1, 3, 2, 4).reshape(  # noqa: E731
        b, nc, Q, c.hv)
    row = lambda x: drows[:, :, :, x].transpose(0, 1, 4, 2, 3).reshape(b, nc, Q, c.hv)  # noqa: E731
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            col(0) - row(0), col(1) + row(1), col(2) + row(2), col(3),
            dth.sum(-1).reshape(b, nc, c.hv), ds0.reshape(dlast.shape), None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunks(cfg, q, k, v, gamma, beta, into, to_end, through, state0, seg):
    """The kernel pair. ``cfg = (product dtype, value heads a grid step,
    interpret)``; ``q``, ``k`` (b, T, h_k, d_k) as projected, in any float
    dtype, and ``v`` (b, T, h_v, d_v) in the product dtype; ``gamma``, ``beta``, ``into``,
    ``to_end`` (b, nc, Q, h_v) and ``through`` (b, nc, h_v) float32; ``seg``
    (b, nc, Q). Returns ``(o, last)``."""
    return _forward(cfg, False, q, k, v, gamma, beta, into, to_end, through, state0, seg)


def _chunks_fwd(cfg, q, k, v, gamma, beta, into, to_end, through, state0, seg):
    o, last, entered, A = _forward(
        cfg, True, q, k, v, gamma, beta, into, to_end, through, state0, seg)
    return (o, last), (q, k, v, gamma, beta, into, to_end, through, seg, entered, A)


def _chunks_bwd(cfg, res, ct):
    return _backward(cfg, res, *ct)


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def delta_window(q, k, v, g, beta, seg, state0, chunk: int, dtype, hb: int, interpret: bool):
    """``gated_delta_chunked``'s contract (a window that is a multiple of the
    chunk; ``q`` and ``k`` as projected: the kernels normalise them) on the
    pair, ``hb`` value heads a grid step. Returns ``o`` (b, T, h_v, d_v) float32 and the
    last state. (Not named after a path scope: ``utils.platform.program_paths``
    reads a lowered module's text, and that holds the function names of
    cached traces.)"""
    b, T = q.shape[:2]
    hv = v.shape[2]
    Q, nc = chunk, T // chunk
    cd = dtype or _F32
    segc = seg.reshape(b, nc, Q)
    # the segment a chunk is entered in: that of the step before it
    seg_in = jnp.concatenate([jnp.zeros_like(segc[:, :1, 0]), segc[:, :-1, -1]], axis=1)
    gamma = jnp.cumsum(g.astype(_F32).reshape(b, nc, Q, hv), axis=2)
    # each step's share of what the chunk writes to the state at its end
    to_end = _decay(gamma[:, :, -1:] - gamma, (segc == segc[:, :, -1:])[..., None])
    # what a step still sees of the state the chunk was entered with
    into = _decay(gamma, (segc == seg_in[:, :, None])[..., None])
    return _chunks(
        (cd, hb, interpret), q, k, v.astype(cd), gamma, beta.astype(_F32).reshape(b, nc, Q, hv),
        into, to_end, into[:, :, -1], state0, segc)
