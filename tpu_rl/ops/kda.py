"""Kimi Delta Attention's recurrence (Kimi Linear, arXiv:2510.26692): the
delta rule of ``ops/gated_delta.py`` with a decay per key *channel*.

Per head, with ``S`` (key x value) the state, ``k̂ = k / |k|``,
``q̂ = q / |q| / sqrt(d_k)`` (``gated_delta.l2norm``), ``α_t = exp(g_t)`` a
vector of ``d_k`` decays and ``β_t`` the write strength:

    S~   = Diag(α_t) S_{t-1}           S_{t-1} := 0 where an episode starts at t
    δ_t  = β_t (v_t - S~^T k̂_t)
    S_t  = S~ + k̂_t δ_t^T
    o_t  = S_t^T q̂_t

As many key heads as value heads. ``kda_step`` is that recurrence for one step
(acting). ``kda_chunked`` (scope ``kda_scan``) is its chunked form (training):
inside a chunk of ``Q`` steps, with ``Γ_i = sum_{j<=i} g_j`` (a vector),
``K⁺ = k̂ * e^Γ`` and ``Q⁺ = q̂ * e^Γ`` (rows 0 from the chunk's first seam on:
they read the entering state ``S0``), and for steps ``j <= i`` of one episode

    P_ij = sum_c k̂_ic k̂_jc e^(Γ_ic - Γ_jc)      R_ij = sum_c q̂_ic k̂_jc e^(Γ_ic - Γ_jc)

(0 across a seam),

    A  = (I + tril(diag(β) P, -1))^-1
    U  = A diag(β) V,   W = A diag(β) K⁺
    Δ  = U - W S0                                      every δ of the chunk
    O  = Q⁺ S0 + tril(R) Δ                             diagonal kept
    S' = Diag(e^Γ_C) S0 + (k̂ * e^(Γ_C - Γ))^T Δ       C: the chunk's last step

which with ``Γ`` constant over the channels is ``ops/gated_delta.py``'s form.

**Keeping it finite.** ``P`` and ``R`` are matmuls only if the pair's decay
factors: ``(k̂_i * e^(Γ_i - Γ_r)) . (k̂_j * e^(Γ_r - Γ_j))`` for a reference
step ``r``. With ``r`` the chunk's start the second factor is ``e^(-Γ_j)``,
which overflows float32 after 18 steps of a gate at its bound of -5. So the
pairs are computed in sub-blocks of ``SUB`` (16) steps: for a pair of
sub-blocks ``a > b`` the reference is ``a``'s first step and both exponents
are <= 0; inside a diagonal sub-block it is the sub-block's own first step,
the left exponent is <= 0 and the right one at most ``|bound| (SUB - 1)`` = 75
(``e^75`` is finite in float32 and in bf16's exponent; ``config.
_check_ling_flash_arch`` refuses a bound the sub-block does not hold). Ten
block products a chunk of 64 in place of one: four diagonal ones in one batch,
and one product a sub-block row against everything before it. The entries
above the diagonal, where the true exponent is positive, are selected away,
and so is the diagonal itself: a step's pair with itself carries no decay and
``R``'s is summed exactly.

Decays, cumulative sums, the inverse and the state are float32; the operands of
every other product are ``dtype`` with float32 accumulation, as
``gated_delta``'s. A seam is a mask on every factor, never ``-inf`` inside a
cumulative sum.

Two forms compute it, and ``kda_chunked`` chooses by what it can observe
(``_kernel_block``, as ``gated_delta._kernel_block``: ``cells.set_pallas_mode``,
the platform of the program being traced, whether the batch tiles a registered
data mesh, lane multiples, VMEM): on a TPU at the published widths one Pallas
kernel per pass (``ops/pallas_kda.py``, scope ``kda_pallas`` inside
``kda_scan``; under a data mesh a ``shard_map`` island) — a file of its own,
because the per-step factors ``ops/pallas_gdn.py`` carries as ``(chunk, 1)``
columns are ``(chunk, d_k)`` tiles here — and everywhere else — the CPU, the
tests' small widths, init and act traces — the ``jax.numpy`` body below, which
is also the kernels' oracle: the window walked in ``gated_delta``'s spans
(``_chunked_jnp`` with this file's ``_span``), each rematerialised in the
backward pass, which is JAX's transpose of this program; the kernels' is their
own (``jax.custom_vjp``), at the same precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_rl.ops import pallas_kda
from tpu_rl.ops.gated_delta import _HIGHEST, _chunked_jnp, _decay, _unit_lower_inverse, l2norm

# Steps of a sub-block: the pairs inside one are computed against its first
# step, so a gate at its bound ``b`` raises an operand to ``e^(|b| (SUB - 1))``.
SUB = 16


def kda_step(q, k, v, g, beta, state):
    """One step: ``q``, ``k`` (B, h, d_k) as projected (normalised here); ``v``
    (B, h, d_v); ``g`` (B, h, d_k) the log decay of every key channel (<= 0);
    ``beta`` (B, h); ``state`` (B, h, d_k, d_v) float32. Returns ``o``
    (B, h, d_v) and the state after the step, float32."""
    q = l2norm(q) * q.shape[-1] ** -0.5
    k = l2norm(k)
    state = jnp.exp(g.astype(jnp.float32))[..., None] * state
    delta = beta[..., None] * (
        v.astype(jnp.float32) - jnp.einsum("bhdv,bhd->bhv", state, k, precision=_HIGHEST))
    state = state + k[..., :, None] * delta[..., None, :]
    return jnp.einsum("bhdv,bhd->bhv", state, q, precision=_HIGHEST), state


def _kernel_block(b: int, h: int, dk: int, dv: int, Q: int) -> tuple[int | None, bool]:
    """(heads per grid step of the Pallas pair, interpret), or (None, False)
    for the ``jax.numpy`` body: ``gated_delta._kernel_block``'s gate on this
    rule's kernels. The CPU, sizes that are no lane multiples and a batch that
    does not tile a registered data mesh (init and act traces: a Mosaic call
    has no SPMD rule outside its island) keep the ``jax.numpy`` form."""
    from tpu_rl.models import cells

    mode = cells._PALLAS_MODE
    if mode == "off":
        return None, False
    hb = pallas_kda.head_block(h, dk, dv, Q, min(SUB, Q))
    if mode == "interpret":  # any width: every head at once where no block tiles
        return hb or h, True
    platform, n_data = cells._program_devices()
    if platform != "tpu" or b % n_data:
        return None, False
    return hb, False


def _kernels(q, k, v, g, beta, seg, state0, chunk, dtype, hb, interpret):
    """The Pallas pair (``ops/pallas_kda.py``); under a registered data mesh
    whose width the batch tiles, as a ``shard_map`` island over the ``"data"``
    axis (``gated_delta._kernels``)."""
    from tpu_rl.models import cells

    sub = min(SUB, chunk)
    assert chunk % sub == 0, f"a chunk of {chunk} steps is no whole number of sub-blocks of {sub}"
    scan = functools.partial(
        pallas_kda.delta_window, chunk=chunk, dtype=dtype, sub=sub, hb=hb, interpret=interpret)
    mesh = cells._DATA_MESH
    if mesh is not None and q.shape[0] % cells._program_devices()[1] == 0:
        from jax.sharding import PartitionSpec as P

        from tpu_rl.parallel.mesh import DATA_AXIS

        rows = P(DATA_AXIS)  # every operand: its leading (batch) dim
        # no collectives inside; pallas out_shapes carry no vma annotations
        scan = jax.shard_map(
            scan, mesh=mesh, in_specs=(rows,) * 7, out_specs=(rows, rows), check_vma=False)
    with jax.named_scope("kda_pallas"):  # the backward's ops carry it too
        return scan(q, k, v, g, beta, seg, state0)


@jax.named_scope("kda_scan")
def kda_chunked(q, k, v, g, beta, seg, state0, chunk: int, dtype=None, kernel=None):
    """The rule over a whole window in matmul form.

    ``q``, ``k`` (b, T, h, d_k) as projected (normalised here); ``v``
    (b, T, h, d_v); ``g`` (b, T, h, d_k) float32, the log decay of every key
    channel (<= 0); ``beta`` (b, T, h) float32; ``seg`` (b, T) int, 0 = the
    episode ``state0`` (b, h, d_k, d_v) belongs to. Returns ``o``
    (b, T, h, d_v) float32 and the state after the last step. ``kernel``:
    ``(heads a grid step of the Pallas pair or None for the jax.numpy body,
    interpret)`` where the caller and not the gate chooses (tests,
    ``chip_smoke.py``)."""
    b, T, h, dk = q.shape
    hb, interpret = kernel or _kernel_block(b, h, dk, v.shape[-1], chunk)
    if hb is None:
        return _chunked_jnp(q, k, v, g, beta, seg, state0, chunk, dtype, span_fn=_span)
    pad = (-T) % chunk
    if pad:  # g = 0, beta = 0: the state passes through, nothing is written
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (q, k, v, g, beta)
        )
        seg = jnp.concatenate([seg, jnp.repeat(seg[:, -1:], pad, axis=1)], axis=1)
    o, last = _kernels(q, k, v, g, beta, seg, state0, chunk, dtype, hb, interpret)
    return o[:, :T], last


def _pairs(left, kn, gamma, sub: int, cd):
    """``sum_c left_ic k̂_jc e^(Γ_ic - Γ_jc)`` for every pair of a chunk's steps
    with ``j <= i`` (what lies above the diagonal is to be selected away: it
    is finite and means nothing). ``left`` (..., n, Q, d) holds ``n`` left
    operands (k̂ for ``P``, q̂ for ``R``) that share the right one; ``kn``,
    ``gamma`` (..., Q, d) float32. Returns (..., n, Q, Q) float32. Sub-block
    ``a``'s rows are computed against its own first step's ``Γ``: the left
    factor's exponent is <= 0 everywhere, the right one's <= 0 for the
    sub-blocks before ``a`` and at most ``|bound| (sub - 1)`` inside it."""
    *lead, n, Q, d = left.shape
    ns = Q // sub
    f32 = jnp.float32
    g_s = gamma.reshape(*lead, ns, sub, d)
    ref = g_s[..., :1, :]  # Γ at each sub-block's first step
    left_s = (left.reshape(*lead, n, ns, sub, d) * jnp.exp(g_s - ref)[..., None, :, :, :]).astype(cd)
    inside = (kn.reshape(*lead, ns, sub, d) * jnp.exp(ref - g_s)).astype(cd)
    diagonal = jnp.einsum("...naid,...ajd->...naij", left_s, inside, preferred_element_type=f32)
    rows = []
    for a in range(ns):
        before = a * sub
        parts = []
        if before:
            right = (kn[..., :before, :] * jnp.exp(ref[..., a, :, :] - gamma[..., :before, :])).astype(cd)
            parts.append(jnp.einsum(
                "...nid,...jd->...nij", left_s[..., a, :, :], right, preferred_element_type=f32))
        parts.append(diagonal[..., a, :, :])
        if Q - before - sub:
            parts.append(jnp.zeros((*lead, n, sub, Q - before - sub), f32))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _span(q, k, v, g, beta, seg, seg_before, state0, Q: int, dtype):
    """``kda_chunked`` on whole chunks computed at once; ``seg_before`` (b,):
    the segment of the step before the first."""
    b, T, h, dk = q.shape
    dv = v.shape[-1]
    nc = T // Q
    sub = min(SUB, Q)
    assert Q % sub == 0, f"a chunk of {Q} steps is no whole number of sub-blocks of {sub}"
    cd = dtype or jnp.float32
    f32 = jnp.float32

    def heads_first(a):  # (b, T, h, d) -> (b, nc, h, Q, d)
        return a.reshape(b, nc, Q, h, -1).transpose(0, 1, 3, 2, 4)

    qn = heads_first(l2norm(q) * dk ** -0.5)
    kn = heads_first(l2norm(k))
    vc = heads_first(v).astype(cd)
    gamma = jnp.cumsum(heads_first(g.astype(f32)), axis=-2)  # (b, nc, h, Q, dk)
    bc = beta.reshape(b, nc, Q, h).transpose(0, 1, 3, 2)  # (b, nc, h, Q)
    segc = seg.reshape(b, nc, Q)
    # the segment a chunk is entered in: that of the step before it
    seg_in = jnp.concatenate([seg_before[:, None], segc[:, :-1, -1]], axis=1)

    # step j reaches step i >= j of the same segment
    reach = (segc[:, :, :, None] == segc[:, :, None, :]) & jnp.tril(jnp.ones((Q, Q), bool))
    entered_in = (segc == seg_in[:, :, None])[:, :, None, :, None]  # still S0's episode
    ends_in = (segc == segc[:, :, -1:])[:, :, None, :, None]  # the last step's episode
    into = _decay(gamma, entered_in)  # e^Γ_i against S0, (b, nc, h, Q, dk)
    to_end = _decay(gamma[..., -1:, :] - gamma, ends_in)  # e^(Γ_C - Γ_j)
    through = into[..., -1, :]  # e^Γ_C, (b, nc, h, dk)

    # the factored pairs strictly under the diagonal; a step's pair with itself has no
    # decay (e^0) and is taken exactly: through the factoring its two halves' gradients by
    # Γ cancel only to the operands' rounding, which at a gate near its bound is more than
    # the whole of the true gradient
    eye = jnp.eye(Q, dtype=bool)
    pairs = _pairs(jnp.stack([kn, qn], axis=3), kn, gamma, sub, cd)  # (b, nc, h, 2, Q, Q)
    pairs = jnp.where((reach & ~eye)[:, :, None, None], pairs, 0.0)
    kk = pairs[..., 0, :, :]
    qk = pairs[..., 1, :, :] + jnp.where(eye, jnp.sum(qn * kn, axis=-1)[..., :, None], 0.0)
    A = _unit_lower_inverse(bc[..., :, None] * kk)
    a_beta = (A * bc[..., None, :]).astype(cd)  # A diag(beta)
    U = jnp.einsum("bchij,bchjv->bchiv", a_beta, vc, preferred_element_type=f32)
    W = jnp.einsum(
        "bchij,bchjd->bchid", a_beta, (kn * into).astype(cd), preferred_element_type=f32)

    def across(state, c):
        W_c, U_c, k_end, through_c = c
        fresh = U_c - jnp.einsum(
            "bhid,bhdv->bhiv", W_c, state.astype(cd), preferred_element_type=f32)
        written = jnp.einsum(
            "bhid,bhiv->bhdv", k_end, fresh.astype(cd), preferred_element_type=f32)
        return through_c[..., None] * state + written, (state, fresh)

    chunks_first = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    last, (entered, fresh) = jax.lax.scan(
        across, state0,
        tuple(chunks_first(a) for a in (W.astype(cd), U, (kn * to_end).astype(cd), through)),
    )
    entered, fresh = jnp.moveaxis(entered, 0, 1), jnp.moveaxis(fresh, 0, 1)

    o = jnp.einsum(
        "bchid,bchdv->bchiv", (qn * into).astype(cd), entered.astype(cd),
        preferred_element_type=f32,
    ) + jnp.einsum(
        "bchij,bchjv->bchiv", qk.astype(cd), fresh.astype(cd), preferred_element_type=f32)
    return o.transpose(0, 1, 3, 2, 4).reshape(b, T, h, dv), last
