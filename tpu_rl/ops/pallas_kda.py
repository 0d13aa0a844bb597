"""Kimi Delta Attention's chunked rule as one Pallas TPU kernel per pass.

``ops/kda.kda_chunked`` in its ``jax.numpy`` form writes every chunk's ``Γ``,
``K⁺``, ``Q⁺``, ``P``, ``R``, ``A``, ``U``, ``W``, entering state and ``Δ`` to
HBM as HLO results of their own (each ``(b, nc, h, Q, d_k)`` float32 array is
268 MB a layer at the cell's widths), stacks them with two ``lax.scan``s and
runs a span's forward a third time for its ``jax.checkpoint``. Here one kernel
walks the chunks of a window *in order* — grid ``(rows, head blocks, chunks)``,
the chunk axis sequential — with the carried state of a head block in a VMEM
scratch, and everything ``ops/kda.py``'s header lists of a chunk is computed
from the kernel's inputs (q, k, v, the log decay ``g``, ``beta``, the segment
ids) and never leaves VMEM. The design is ``ops/pallas_gdn.py``'s (read its
header first): the backward kernel walks the chunks in reverse, ``_GROUP`` of
them a grid step, with the state's cotangent in a scratch, and recomputes a
chunk from the inputs and the differentiated forward's two residuals — the
state every ``_GROUP``-th chunk was entered with and each chunk's ``A``. The
un-differentiated forward writes neither. The L2 norms of q and k are the
kernels' too.

What a decay per key *channel* changes, and why this is a file of its own and
no flag on that one: the per-step factors ``pallas_gdn`` carries as ``(chunk,
1)`` columns are ``(chunk, d_k)`` float32 tiles here (``Γ``, ``e^Γ`` against
the entering state, ``e^(Γ_C - Γ)`` towards the leaving one), the cumulative
sum of ``g`` is the kernels' (``_cumsum``: log2 Q shifted adds down the
sublanes; its transpose, a suffix sum, gives ``dg``), and ``P`` / ``R`` are
factored products in sub-blocks of ``sub`` steps, each row block against its
own first step (``_pairs``: one product a row block, k̂'s and q̂'s rows
stacked, against every step up to the block's last; the later steps' rows are
zeros, not ``e^(+large)``). The pair of a step with itself is summed exactly
(``sum_c q̂_c k̂_c``) and selected out of the factored products in the forward
*and* in the backward — the cotangent of ``R`` is masked to strictly under the
diagonal before it enters the factoring's transpose and its diagonal goes to
q̂ and k̂ directly — because through the factoring its two gradients by ``Γ``
cancel only to the operands' rounding (``ops/kda.py``; PR 51 read ``dg`` 14%
off with bf16 operands at the bound).

The state is carried *transposed*, ``(d_v, d_k)`` a head: the decay through a
chunk, ``e^Γ_C``, is then a ``(1, d_k)`` row that scales the state's columns,
and every product with the state contracts its lanes (``_nt``) or yields it
whole (``_tn``). ``state0``, the entered states and ``dstate0`` are transposed
outside, 2 MB a row.

Precision is the ``jax.numpy`` form's: decays, cumulative sums, masks, the
inverse (``pallas_gdn._unit_lower_inverses``: float32 products at float32
precision) and the carried state in float32; the operands of every other
product in ``dtype`` (bf16 in the cell) with float32 accumulation; a seam is a
``where(keep, e, -inf)`` before ``exp`` on every factor and resets the state
mid-chunk. The backward casts cotangents to ``dtype`` at its products, as
``pallas_lstm.mixed_dot`` does.

Layout: ``pallas_gdn``'s. A chunk's steps on the sublanes, a head's features
on the lanes: q, k, v, ``g`` and their cotangents are tiles ``(chunk, heads a
step * d)`` of the ``(b, T, h * d)`` view; ``o`` and its cotangent are float32
in the ``(b, T, h, d)`` tiles the per-head norm reads; ``beta`` comes as
``(chunk, heads)`` columns and ``(heads, chunk)`` rows.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_rl.ops.pallas_gdn import (
    _each, _f32_dot, _masks, _unit, _unit_bwd, _unit_lower_inverses,
)
from tpu_rl.ops.pallas_ssd import _decay, _nn, _nt, _tn, _vmem_limit

_F32 = jnp.float32
_LANES = 128

# Chunks a grid step of the backward (``pallas_gdn._GROUP``'s reasons: a state
# entered is 64 KB a chunk and head, every chunk's of an 8,192-step window 256
# MiB a layer and row).
_GROUP = 4

# Heads a grid step at most: a step's products are independent chains the
# scheduler interleaves over the matrix units; ``o``'s tiles hold eight.
_MAX_HEADS = 8


def _vmem_bytes(hb: int, dk: int, dv: int, Q: int, itemsize: int = 4) -> int:
    """VMEM the kernels need with ``hb`` heads a grid step: the blocks of the
    backward (the larger pass: ``_GROUP`` chunks a step) double-buffered plus
    its scratch, and as much again for what a kernel keeps between its
    products (a head's ``(Q, d_k)`` float32 factors do not fit the vector
    registers: Mosaic holds them in VMEM). At the cell's widths and 8 heads
    39.2 MiB with bf16 operands and 51.2 at float32 ones, which is what the
    gate counts; Mosaic's own allocation there, spills included, is 22.60
    MiB (bf16; compiled for a described v5e at falling limits)."""
    G = _GROUP
    tiles = lambda rows, lanes: 4 * -(-rows // 8) * 8 * -(-lanes // _LANES) * _LANES  # noqa: E731
    state = 4 * hb * dk * dv
    qkv = itemsize * G * Q * hb * (2 * dk + dv)  # and their cotangents
    g = 4 * G * Q * hb * dk  # and dg
    steps = G * (tiles(Q, hb) + tiles(hb, Q))  # beta as columns and rows; their cotangents
    seg = G * (tiles(Q, 2) + tiles(1, Q))
    read = qkv + g + steps + seg + 2 * state + 4 * G * Q * hb * (Q + dv)  # entered, dlast; A, do
    scratch = (G + 1) * state + tiles(hb * Q, Q)
    return 2 * (2 * (read + qkv + g + steps + state) + scratch)


def head_block(h: int, dk: int, dv: int, chunk: int, sub: int) -> int | None:
    """Heads per grid step of a compiled call, or None when no block fits the
    kernels: key and value sizes are lane multiples, the chunk and its
    sub-blocks fill bf16 sublane groups, a block holds whole tiles of ``o``
    (eight heads a step, or every head), and the kernels' need
    (``_vmem_bytes``) is inside what the call asks for (``_vmem_limit``). The
    most heads that fit, up to ``_MAX_HEADS``: the kernels are unrolled over a
    block's heads."""
    if dk % _LANES or dv % _LANES or chunk % 16 or sub % 16 or chunk % sub:
        return None
    limit = _vmem_limit()
    for hb in range(min(h, _MAX_HEADS), 0, -1):
        if h % hb == 0 and (hb % 8 == 0 or hb == h) and _vmem_bytes(hb, dk, dv, chunk) <= limit:
            return hb
    return None


def _cumsum(x, reverse: bool = False):
    """Running sums down the rows of a float32 tile (up them with
    ``reverse``: the transpose), ``log2`` of the rows shifted adds on the
    sublanes; a rotation's wrapped rows are selected away."""
    Q = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
    shift = 1
    while shift < Q:
        if reverse:
            x = x + jnp.where(row < Q - shift, pltpu.roll(x, Q - shift, 0), 0.0)
        else:
            x = x + jnp.where(row >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


def _rows(parts, Q):
    """Row blocks (the last may stop short of ``Q`` rows: zeros follow) as one tile."""
    short = Q - sum(p.shape[0] for p in parts)
    if short:
        parts = [*parts, jnp.zeros((short, parts[0].shape[1]), parts[0].dtype)]
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]


def _pairs(cd, sub, qn, kn, gamma):
    """``ops/kda._pairs`` for one head: the factored ``P`` and ``R`` (Q, Q)
    float32, right on and under the diagonal (above it finite or 0 and
    meaning nothing), and per row block what the transpose needs: the block's
    left factor ``e^(Γ_i - Γ_ref)`` (sub, d_k) and right factor ``e^(Γ_ref -
    Γ_j)`` over the steps up to its last, float32, and the two operands in
    ``cd`` (k̂'s rows over q̂'s, (2 sub, d_k); the right one (Q, d_k), zeros
    from the block's end on)."""
    Q = gamma.shape[0]
    P, R, kept = [], [], []
    for a in range(Q // sub):
        at, upto = slice(a * sub, (a + 1) * sub), (a + 1) * sub
        ref = gamma[a * sub:a * sub + 1]
        el, er = jnp.exp(gamma[at] - ref), jnp.exp(ref - gamma[:upto])
        left = jnp.concatenate([kn[at] * el, qn[at] * el], axis=0).astype(cd)
        right = _rows([kn[:upto] * er], Q).astype(cd)
        both = _nt(left, right)  # (2 sub, Q)
        P.append(both[:sub])
        R.append(both[sub:])
        kept.append((el, er, left, right))
    return _rows(P, Q), _rows(R, Q), kept


def _pairs_bwd(cd, sub, qn, kn, kept, dP, dR):
    """The transpose of ``_pairs``: from the cotangents of ``P`` and ``R``
    (masked to what the forward kept of them) those of q̂, k̂ and ``Γ``."""
    Q = qn.shape[0]
    colsum = lambda x: jnp.sum(x, axis=0, keepdims=True)  # noqa: E731
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
    dq_at, dk_at, dg_at = [], [], []
    dk_upto = dg_upto = dg_ref = 0.0
    for a, (el, er, left, right) in enumerate(kept):
        at, upto = slice(a * sub, (a + 1) * sub), (a + 1) * sub
        both = jnp.concatenate([dP[at], dR[at]], axis=0).astype(cd)  # (2 sub, Q)
        dleft = _nn(both, right)  # (2 sub, d_k)
        dright = _tn(both, left)[:upto]  # (upto, d_k)
        dkl, dql = dleft[:sub], dleft[sub:]
        dq_at.append(dql * el)
        dk_at.append(dkl * el)
        t_el = (dkl * kn[at] + dql * qn[at]) * el
        t_er = dright * kn[:upto] * er
        dg_at.append(t_el)
        dk_upto = dk_upto + _rows([dright * er], Q)
        dg_upto = dg_upto + _rows([t_er], Q)
        # the block's reference step: Γ at its first row
        dg_ref = dg_ref + jnp.where(row == a * sub, colsum(t_er) - colsum(t_el), 0.0)
    return _rows(dq_at, Q), _rows(dk_at, Q) + dk_upto, _rows(dg_at, Q) - dg_upto + dg_ref


def _state_factors(dk, cd, c, at, k_ref, g_ref, bc_ref, br_ref, sc_ref):
    """What of chunk ``c`` of a block (its steps the rows ``at``) stands
    between two states, a list entry a head: normalised k with its factor;
    ``Γ``; the factors against the entering state (``into``) and towards the
    leaving one (``to_end``); ``K⁺`` and ``k̂ e^(Γ_C - Γ)`` in ``cd``; beta as
    columns and rows; each step's segment as a column."""
    hb, Q = br_ref.shape[1:]
    heads = range(hb)
    seg = sc_ref[c]  # (Q, 2): each step's segment; the one the chunk is entered in
    sc = seg[:, 0:1]
    entered_in, ends_in = sc == seg[:, 1:2], sc == sc[Q - 1:Q]
    keys = [slice(j * dk, (j + 1) * dk) for j in heads]
    k = [_unit(k_ref[at, K]) for K in keys]
    kn = [y for y, _ in k]
    gamma = [_cumsum(g_ref[at, K]) for K in keys]
    into = [_decay(x, entered_in) for x in gamma]
    to_end = [_decay(x[Q - 1:Q] - x, ends_in) for x in gamma]
    return types.SimpleNamespace(
        keys=keys, sc=sc, k=k, kn=kn, gamma=gamma, into=into, to_end=to_end,
        Kp=[(x * w).astype(cd) for x, w in zip(kn, into)],
        Ke=[(x * w).astype(cd) for x, w in zip(kn, to_end)],
        bc=[bc_ref[c, :, j:j + 1] for j in heads], br=[br_ref[c, j:j + 1, :] for j in heads],
    )


def _chunk_inputs(dk, sub, cd, c, at, q_ref, k_ref, g_ref, bc_ref, br_ref, sc_ref, sr_ref):
    """What both passes compute first of chunk ``c``: ``_state_factors`` and,
    a list entry a head, normalised q with its factor, ``Q⁺`` in ``cd``, ``P``
    and ``R`` as the forward keeps them with what ``_pairs`` hands its
    transpose (``kept``), and the chunk's masks."""
    x = _state_factors(dk, cd, c, at, k_ref, g_ref, bc_ref, br_ref, sc_ref)
    Q = br_ref.shape[2]
    reach, strict = _masks(x.sc, sr_ref[c])
    x.under = reach & strict
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    x.eye = rows == jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    x.q = [_unit(q_ref[at, K], dk ** -0.5) for K in x.keys]
    x.qn = [y for y, _ in x.q]
    x.Qp = [(y * w).astype(cd) for y, w in zip(x.qn, x.into)]
    P, R, x.kept = zip(*_each(functools.partial(_pairs, cd, sub), x.qn, x.kn, x.gamma))
    x.P = [jnp.where(x.under, p, 0.0) for p in P]
    # a step's pair with itself carries no decay and is summed exactly
    x.R = [jnp.where(x.under, r, 0.0) + jnp.where(x.eye, jnp.sum(y * z, axis=1, keepdims=True), 0.0)
           for r, y, z in zip(R, x.qn, x.kn)]
    return x


def _deltas(cd, A, br, vh, Kp, S0b):
    """From a chunk's inverse on, a list entry a head: ``A diag(beta)`` and
    ``W`` in ``cd``, and ``Δ = U - W S0``, every delta of the chunk."""
    Ab = [(a * w).astype(cd) for a, w in zip(A, br)]
    Wb = [x.astype(cd) for x in _each(_nn, Ab, Kp)]
    D = [u - ws for u, ws in zip(_each(_nn, Ab, vh), _each(_nt, Wb, S0b))]
    return Ab, Wb, D


def _leaves(cd, into, Ke, D, S0):
    """The (transposed) state a chunk leaves: ``S0ᵀ Diag(e^Γ_C) + Δᵀ (k̂
    e^(Γ_C - Γ))``, a head."""
    Q = into[0].shape[0]
    written = _each(_tn, [x.astype(cd) for x in D], Ke)
    return [w[Q - 1:Q] * s + x for w, s, x in zip(into, S0, written)]


def _fwd_kernel(group, dk, dv, sub, cd,
                q_ref, k_ref, v_ref, g_ref, bc_ref, br_ref, sc_ref, sr_ref, s0_ref,
                o_ref, last_ref, *rest):
    """One chunk of one head block: q, k (Q, hb * dk) as projected, v (Q, hb
    * dv) in ``cd`` and g (Q, hb * dk) float32; beta (Q, hb) as columns and
    (hb, Q) as rows; seg (Q, 2) (a step's segment; the segment the chunk is
    entered in) and (1, Q) int; the state transposed, (hb * dv, dk) f32:
    ``s0`` in, ``last`` out, ``s`` the scratch that carries it along the chunk
    axis; o (Q, hb, dv) f32. The per-chunk operands carry a leading axis of
    one chunk (the backward's blocks hold ``group``). With ``group`` (the
    differentiated forward) also the backward's residuals: ``entered``, the
    state every ``group``-th chunk was entered with, and each chunk's ``A``
    (Q, hb * Q). Every line is the block's heads in lockstep
    (``pallas_gdn._unit_lower_inverses``)."""
    ent_ref, a_ref, s_scr, n_scr = rest if group else (None, None, *rest)
    c = pl.program_id(2)
    hb, Q = br_ref.shape[1:]

    @pl.when(c == 0)
    def _():
        s_scr[...] = s0_ref[...]

    if group:
        @pl.when(c % group == 0)
        def _():
            ent_ref[...] = s_scr[...]
    bf = lambda xs: [x.astype(cd) for x in xs]  # noqa: E731
    heads = range(hb)
    ch = _chunk_inputs(
        dk, sub, cd, 0, slice(None), q_ref, k_ref, g_ref, bc_ref, br_ref, sc_ref, sr_ref)
    S0 = [s_scr[j * dv:(j + 1) * dv, :] for j in heads]
    S0b = bf(S0)
    A = _unit_lower_inverses([w * p for w, p in zip(ch.bc, ch.P)], n_scr)
    vh = [v_ref[:, j * dv:(j + 1) * dv] for j in heads]
    _, _, D = _deltas(cd, A, ch.br, vh, ch.Kp, S0b)
    o = [y + z for y, z in zip(_each(_nt, ch.Qp, S0b), _each(_nn, bf(ch.R), bf(D)))]
    left = _leaves(cd, ch.into, ch.Ke, D, S0)
    for j in heads:
        o_ref[:, j, :] = o[j]
        s_scr[j * dv:(j + 1) * dv, :] = left[j]
        if group:
            a_ref[0, :, j * Q:(j + 1) * Q] = A[j]

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = s_scr[...]


def _bwd_kernel(dk, dv, sub, cd,
                q_ref, k_ref, v_ref, g_ref, bc_ref, br_ref, sc_ref, sr_ref,
                ent_ref, a_ref, do_ref, dlast_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbc_ref, dbr_ref, ds0_ref, dh_scr, s_scr):
    """A group of G chunks a grid step, the groups walked from the last to the
    first: the per-chunk blocks hold G chunks (q, k, v, g, do and their
    cotangents G * Q rows; beta, seg and ``a`` a leading axis of G). ``ent``
    is the state the group was entered with: the states its other chunks were
    entered with are computed again into ``s_scr`` (G, hb * dv, dk), then the
    chunks are walked in reverse with ``dh`` carrying the cotangent of the
    state a chunk leaves. dg is the suffix sum of ``Γ``'s cotangent; beta's
    comes in two shares, dbc (Q, hb) from where it scales rows and dbr (hb,
    Q) from where it scales columns. The heads in lockstep, as the forward."""
    i = pl.program_id(2)  # group ng - 1 - i
    G, hb, Q = br_ref.shape
    heads = range(hb)
    bf = lambda xs: [x.astype(cd) for x in xs]  # noqa: E731

    @pl.when(i == 0)
    def _():
        dh_scr[...] = dlast_ref[...]

    def steps(c):  # the rows of chunk ``c`` in a block of G * Q
        return pl.ds(pl.multiple_of(c * Q, Q), Q)

    def enter(c, _):
        """``s_scr[c + 1]`` from ``s_scr[c]``."""
        at = steps(c)
        ch = _state_factors(dk, cd, c, at, k_ref, g_ref, bc_ref, br_ref, sc_ref)
        S0 = [s_scr[c, j * dv:(j + 1) * dv, :] for j in heads]
        A = [a_ref[c, :, j * Q:(j + 1) * Q] for j in heads]
        vh = [v_ref[at, j * dv:(j + 1) * dv] for j in heads]
        D = _deltas(cd, A, ch.br, vh, ch.Kp, bf(S0))[2]
        for j, left in enumerate(_leaves(cd, ch.into, ch.Ke, D, S0)):
            s_scr[c + 1, j * dv:(j + 1) * dv, :] = left

    s_scr[0] = ent_ref[...]
    if G > 1:
        jax.lax.fori_loop(0, G - 1, enter, None)

    rowsum = lambda a: jnp.sum(a, axis=1, keepdims=True)  # noqa: E731
    colsum = lambda a: jnp.sum(a, axis=0, keepdims=True)  # noqa: E731
    last_row = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1

    def back(n, _):
        c = G - 1 - n
        at = steps(c)
        ch = _chunk_inputs(dk, sub, cd, c, at, q_ref, k_ref, g_ref, bc_ref, br_ref, sc_ref, sr_ref)
        q, k, qn, kn, into, to_end = ch.q, ch.k, ch.qn, ch.kn, ch.into, ch.to_end
        Kp, Qp, Ke, P, R, bc, br = ch.Kp, ch.Qp, ch.Ke, ch.P, ch.R, ch.bc, ch.br
        under, eye = ch.under, ch.eye
        vh = [v_ref[at, j * dv:(j + 1) * dv] for j in heads]
        do = [do_ref[at, j, :] for j in heads]
        S0 = [s_scr[c, j * dv:(j + 1) * dv, :] for j in heads]
        dS = [dh_scr[j * dv:(j + 1) * dv, :] for j in heads]
        A = [a_ref[c, :, j * Q:(j + 1) * Q] for j in heads]
        dob, S0b, dSb = bf(do), bf(S0), bf(dS)
        # the chunk again, from its inverse on
        Ab, Wb, D = _deltas(cd, A, br, vh, Kp, S0b)
        Db = bf(D)
        # through the state the chunk leaves, and the output
        dKe = _each(_nn, Db, dSb)
        dQp = _each(_nn, dob, S0b)
        dR = _each(_nt, dob, Db)
        dD = [x + y for x, y in zip(_each(_nt, Ke, dSb), _each(_tn, bf(R), dob))]
        # through Δ = U - W S0
        dDb = bf(dD)
        dWb = bf([-x for x in _each(_nn, dDb, S0b)])
        dS0 = [w[Q - 1:Q] * s + x - y for w, s, x, y in zip(
            into, dS, _each(_tn, dob, Qp), _each(_tn, dDb, Wb))]
        dvh = _each(_tn, Ab, dDb)
        dKp = _each(_tn, Ab, dWb)
        dAb = [x + y for x, y in zip(_each(_nt, dDb, vh), _each(_nt, dWb, Kp))]
        # through the inverse: d n = -A^T dA A^T, strictly lower
        dN = [_f32_dot(a, x * w, ((0,), (0,))) for a, x, w in zip(A, dAb, br)]
        dN = [jnp.where(under, -_f32_dot(x, a, ((1,), (1,))), 0.0) for x, a in zip(dN, A)]
        # through the pairs: the diagonal of R apart, the rest through the factoring
        dP = [x * w for x, w in zip(dN, bc)]
        dd = [rowsum(jnp.where(eye, x, 0.0)) for x in dR]
        dR = [jnp.where(under, x, 0.0) for x in dR]
        dqn, dkn, dgamma = zip(*[
            _pairs_bwd(cd, sub, *xs) for xs in zip(qn, kn, ch.kept, dP, dR)])
        # through the factors against the two states: d into, d to_end (times the factor)
        t_in = [(x * y + z * w) * f for x, y, z, w, f in zip(dKp, kn, dQp, qn, into)]
        t_end = [x * y * f for x, y, f in zip(dKe, kn, to_end)]
        through = [colsum(x * y) * w[Q - 1:Q] for x, y, w in zip(dS, S0, into)]
        for j, K in enumerate(ch.keys):
            dgam = dgamma[j] + t_in[j] - t_end[j] + jnp.where(
                last_row, through[j] + colsum(t_end[j]), 0.0)
            dq = dqn[j] + dQp[j] * into[j] + dd[j] * kn[j]
            dkk = dkn[j] + dKp[j] * into[j] + dKe[j] * to_end[j] + dd[j] * qn[j]
            dh_scr[j * dv:(j + 1) * dv, :] = dS0[j]
            dv_ref[at, j * dv:(j + 1) * dv] = dvh[j].astype(dv_ref.dtype)
            dg_ref[at, K] = _cumsum(dgam, reverse=True)
            dq_ref[at, K] = _unit_bwd(*q[j], dq, dk ** -0.5).astype(dq_ref.dtype)
            dk_ref[at, K] = _unit_bwd(*k[j], dkk).astype(dk_ref.dtype)
            dbc_ref[c, :, j:j + 1] = rowsum(dN[j] * P[j])
            dbr_ref[c, j:j + 1, :] = colsum(dAb[j] * A[j])

    jax.lax.fori_loop(0, G, back, None)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        ds0_ref[...] = dh_scr[...]


class _Call:
    """One call's static shapes, the operands in the kernels' layouts and the
    block specs of both passes. ``group``: the chunks that share an entered
    state. ``specs(chunk_of, G)``: blocks of ``G`` chunks, ``chunk_of`` mapping
    the grid's third index to a block of chunks: the identity forward,
    reversed backward. In a block shape None is a squeezed axis."""

    def __init__(self, cfg, q, k, v, g, beta, seg):
        self.cd, self.hb, self.sub, self.Q, self.interpret = cfg
        self.b, self.T, self.h, self.dk = q.shape
        self.dv = v.shape[-1]
        self.nc, self.nhb = self.T // self.Q, self.h // self.hb
        self.group = max(n for n in range(1, _GROUP + 1) if self.nc % n == 0)
        b, nc, Q = self.b, self.nc, self.Q
        flat = lambda a: a.reshape(b, self.T, -1)  # noqa: E731 — (b, T, h, d) -> (b, T, h * d)
        bc = beta.astype(_F32).reshape(b, nc, Q, self.nhb, self.hb).transpose(0, 1, 3, 2, 4)
        segc = seg.astype(jnp.int32).reshape(b, nc, Q)
        # the segment a chunk is entered in: that of the step before it
        seg_in = jnp.concatenate([jnp.zeros_like(segc[:, :1, -1]), segc[:, :-1, -1]], axis=1)
        self.ops = (
            flat(q), flat(k), flat(v), flat(g.astype(_F32)), bc, bc.swapaxes(-1, -2),
            jnp.stack([segc, jnp.broadcast_to(seg_in[..., None], segc.shape)], axis=-1),
            segc[:, :, None],
        )

    def specs(self, chunk_of, G):
        Q, hb, dk, dv = self.Q, self.hb, self.dk, self.dv

        def spec(shape, index):
            return pl.BlockSpec(shape, lambda i, h, c: index(i, h, chunk_of(c)))

        small = lambda *shape: spec(  # noqa: E731
            (None, G, None, *shape), lambda i, h, c: (i, c, h) + (0,) * len(shape))
        return dict(
            qk=spec((None, G * Q, hb * dk), lambda i, h, c: (i, c, h)),
            v=spec((None, G * Q, hb * dv), lambda i, h, c: (i, c, h)),
            bc=small(Q, hb), br=small(hb, Q),
            sc=spec((None, G, Q, 2), lambda i, h, c: (i, c, 0, 0)),
            sr=spec((None, G, 1, Q), lambda i, h, c: (i, c, 0, 0)),
            state=spec((None, hb * dv, dk), lambda i, h, c: (i, h, 0)),
            entered=spec((None, None, hb * dv, dk), lambda i, h, c: (i, c * G // self.group, h, 0)),
            a=small(Q, hb * Q),
            o=spec((None, G * Q, hb, dv), lambda i, h, c: (i, c, h, 0)),
        )

    def call(self, kernel, G, names, operands, out_names, out_shape, chunk_of, name, scratch):
        s = self.specs(chunk_of, G)
        return pl.pallas_call(
            functools.partial(kernel, self.dk, self.dv, self.sub, self.cd),
            grid=(self.b, self.nhb, self.nc // G),
            in_specs=[s[k] for k in names],
            out_specs=[s[k] for k in out_names],
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((self.hb * self.dv, self.dk), _F32), *scratch],
            interpret=self.interpret,
            compiler_params=None if self.interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(),
            ),
            name=name,
        )(*operands)

    def transposed(self, state):  # (b, h, d_k, d_v) <-> the kernels' (b, h * d_v, d_k)
        return state.astype(_F32).swapaxes(-1, -2).reshape(self.b, self.h * self.dv, self.dk)


_INPUTS = ["qk", "qk", "v", "qk", "bc", "br", "sc", "sr"]


# Both passes are jitted: the layers of a model (and every later trace of its
# train step in the process) then share one trace and one lowering of each
# kernel (``pallas_gdn._forward``).
@functools.partial(jax.jit, static_argnums=(0, 1))
def _forward(cfg, residuals, q, k, v, g, beta, state0, seg):
    """``(o, last)``, and with ``residuals`` ``(o, last, entered, A)``: what
    the backward reads beside the kernel's inputs."""
    c = _Call(cfg, q, k, v, g, beta, seg)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, _F32)  # noqa: E731
    b, nc, HV = c.b, c.nc, c.h * c.dv
    out_names, out_shape = ["o", "state"], [f32(b, c.T, c.h, c.dv), f32(b, HV, c.dk)]
    if residuals:
        out_names += ["entered", "a"]
        out_shape += [f32(b, nc // c.group, HV, c.dk), f32(b, nc, c.nhb, c.Q, c.hb * c.Q)]
    out, last, *res = c.call(
        functools.partial(_fwd_kernel, c.group if residuals else 0), 1,
        [*_INPUTS, "state"], (*c.ops, c.transposed(state0)),
        out_names, out_shape, lambda ch: ch, "kdelta_fwd_res" if residuals else "kdelta_fwd",
        scratch=[pltpu.VMEM((c.hb * c.Q, c.Q), _F32)],
    )
    return (out, last.reshape(b, c.h, c.dv, c.dk).swapaxes(-1, -2), *res)


@functools.partial(jax.jit, static_argnums=(0,))
def _backward(cfg, res, do, dlast):
    q, k, v, g, beta, seg, entered, A = res
    c = _Call(cfg, q, k, v, g, beta, seg)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, _F32)  # noqa: E731
    b, nc, nhb, Q, hb, HV, G = c.b, c.nc, c.nhb, c.Q, c.hb, c.h * c.dv, c.group
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    dq, dk, dv, dg, dbc, dbr, ds0 = c.call(
        _bwd_kernel, G, [*_INPUTS, "entered", "a", "o", "state"],
        (*c.ops, entered, A, do.astype(_F32), c.transposed(dlast)),
        ["qk", "qk", "v", "qk", "bc", "br", "state"],
        [*map(like, c.ops[:4]), f32(b, nc, nhb, Q, hb), f32(b, nc, nhb, hb, Q), f32(b, HV, c.dk)],
        lambda n: nc // G - 1 - n, "kdelta_bwd",
        scratch=[pltpu.VMEM((G, hb * c.dv, c.dk), _F32)],
    )
    dbeta = dbc.transpose(0, 1, 3, 2, 4) + dbr.transpose(0, 1, 4, 2, 3)  # (b, nc, Q, nhb, hb)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape).astype(g.dtype), dbeta.reshape(beta.shape).astype(beta.dtype),
            ds0.reshape(b, c.h, c.dv, c.dk).swapaxes(-1, -2).astype(dlast.dtype), None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunks(cfg, q, k, v, g, beta, state0, seg):
    """The kernel pair. ``cfg = (product dtype, heads a grid step, steps a
    sub-block, steps a chunk, interpret)``; ``q``, ``k`` (b, T, h, d_k) as
    projected, in any float dtype, and ``v`` (b, T, h, d_v) in the product
    dtype; ``g`` (b, T, h, d_k) and ``beta`` (b, T, h); ``state0`` (b, h, d_k,
    d_v) float32; ``seg`` (b, T). Returns ``(o, last)``, float32."""
    return _forward(cfg, False, q, k, v, g, beta, state0, seg)


def _chunks_fwd(cfg, q, k, v, g, beta, state0, seg):
    o, last, entered, A = _forward(cfg, True, q, k, v, g, beta, state0, seg)
    return (o, last), (q, k, v, g, beta, seg, entered, A)


def _chunks_bwd(cfg, res, ct):
    return _backward(cfg, res, *ct)


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def delta_window(q, k, v, g, beta, seg, state0, chunk: int, dtype, sub: int, hb: int,
                 interpret: bool):
    """``kda_chunked``'s contract (a window that is a multiple of the chunk;
    ``q`` and ``k`` as projected: the kernels normalise them) on the pair,
    ``hb`` heads a grid step, the pairs in sub-blocks of ``sub`` steps.
    Returns ``o`` (b, T, h, d_v) float32 and the last state. (Not named after
    a path scope: ``utils.platform.program_paths`` reads a lowered module's
    text, and that holds the function names of cached traces.)"""
    cd = dtype or _F32
    return _chunks((cd, hb, sub, chunk, interpret), q, k, v.astype(cd), g, beta,
                   state0.astype(_F32), seg)
