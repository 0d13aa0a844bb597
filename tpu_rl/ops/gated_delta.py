"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): linear attention
whose state is a matrix per head and whose transition is not diagonal.

Per value head, with ``S`` (key x value) the state, ``k̂ = k / |k|``,
``q̂ = q / |q| / sqrt(d_k)`` (``l2norm``), ``α_t = exp(g_t)`` the decay and
``β_t`` the write strength:

    S~   = α_t S_{t-1}                 S_{t-1} := 0 where an episode starts at t
    δ_t  = β_t (v_t - S~^T k̂_t)        what the state does not yet say of v_t
    S_t  = S~ + k̂_t δ_t^T
    o_t  = S_t^T q̂_t

so ``S_t = α_t S_{t-1} (I - β_t k̂ k̂^T) + β_t k̂ v^T`` read from the key side.
Key head ``h // (value heads / key heads)`` serves value head ``h``.

``gated_delta_step`` is that recurrence for one step (acting).
``gated_delta_chunked`` (scope ``gdn_scan``) is its chunked form (training):
inside a chunk of ``Q`` steps, with ``γ_i = sum_{j<=i} g_j`` and
``D_ij = exp(γ_i - γ_j)`` where steps ``j <= i`` share an episode and 0 where
a seam lies between them (``D_i0`` against the entering state ``S0``: 0 from
the chunk's first seam on),

    A  = (I + tril(diag(β) (K̂ K̂^T * D), -1))^-1       the WY / UT transform
    U  = A diag(β) V,   W = A diag(β D_.0) K̂
    V' = U - W S0                                      every δ of the chunk
    O  = D_.0 * (Q̂ S0) + tril(Q̂ K̂^T * D) V'           diagonal kept
    S' = D_C0 S0 + (K̂ * D_C.)^T V'                     C: the chunk's last step

Two forms compute it, and ``gated_delta_chunked`` chooses by what it can
observe (``_kernel_block``: ``cells.set_pallas_mode``, the platform of the
program being traced, whether the batch tiles a registered data mesh, lane
multiples, VMEM): on a TPU at the published widths one Pallas kernel per pass
(``ops/pallas_gdn.py``, scope ``gdn_pallas`` inside ``gdn_scan``; under a data
mesh a ``shard_map`` island), everywhere else — the CPU, the tests' small
widths, init and act traces — the ``jax.numpy`` body below (``_chunked_jnp``),
which is also the kernels' oracle.

In the ``jax.numpy`` body ``A`` is the inverse of a unit lower-triangular
matrix: ``sum_k (-N)^k`` by
repeated squaring (``_unit_lower_inverse``: matmuls only, float32 at the
highest precision, its own transpose rule so that the backward keeps ``A``
alone). The window is walked in spans of ``SPAN_CHUNKS`` chunks. Within a span
everything that does not read ``S0`` is computed for all its chunks at once;
one ``lax.scan`` over the chunks carries the state and yields each chunk's
entering state and ``V'``; ``O`` is again computed for all chunks at once, and
the backward pass rematerialises the span. Decays, cumulative sums, the
inverse and the carried state are float32; the operands of every other product
are ``dtype`` with float32 accumulation, as ``models/mamba2._ssd_jnp``
does for Mamba-2. A seam is a mask on every decay factor (never ``-inf``
inside a cumulative sum). The backward is JAX's transpose of this program;
the kernels' is their own (``jax.custom_vjp``), at the same precision.

``ops/kda.py`` is the sibling whose decay is a vector (one factor a key
*channel*, Kimi Delta Attention). It borrows ``l2norm``, ``_decay``,
``_unit_lower_inverse`` and the walk over spans (``_chunked_jnp`` with its own
``span_fn``) from here; with a decay that is constant over the channels its
chunked form is this file's, line for line.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_rl.ops import pallas_gdn

L2_EPS = 1e-6
_HIGHEST = jax.lax.Precision.HIGHEST


def l2norm(x):
    """``x / sqrt(sum x^2 + 1e-6)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _decay(exponent, keep):
    return jnp.exp(jnp.where(keep, exponent, -jnp.inf))


@jax.custom_vjp
def _unit_lower_inverse(n):
    """``(I + n)^-1`` for strictly lower-triangular ``n`` (..., Q, Q) float32:
    with ``m = -n`` nilpotent, ``(I + m)(I + m^2)(I + m^4) ...`` up to the
    power ``Q``."""
    Q = n.shape[-1]
    power = -n
    inverse = jnp.eye(Q, dtype=n.dtype) + power
    for _ in range(max(0, (Q - 1).bit_length() - 1)):
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inverse = inverse + jnp.matmul(inverse, power, precision=_HIGHEST)
    return inverse


def _unit_lower_inverse_fwd(n):
    inverse = _unit_lower_inverse(n)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, g):
    """``d n = -A^T g A^T``, of which the strictly lower part is ``n``'s."""
    at = jnp.swapaxes(inverse, -1, -2)
    d = -jnp.matmul(jnp.matmul(at, g, precision=_HIGHEST), at, precision=_HIGHEST)
    return (jnp.tril(d, -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_step(q, k, v, g, beta, state):
    """One step: ``q``, ``k`` (B, h_k, d_k) as projected (normalised here);
    ``v`` (B, h_v, d_v); ``g`` (log decay, <= 0) and ``beta`` (B, h_v);
    ``state`` (B, h_v, d_k, d_v) float32. Returns ``o`` (B, h_v, d_v) and the
    state after the step, float32."""
    r = v.shape[1] // k.shape[1]
    q = jnp.repeat(l2norm(q) * q.shape[-1] ** -0.5, r, axis=1)
    k = jnp.repeat(l2norm(k), r, axis=1)
    v = v.astype(jnp.float32)
    state = jnp.exp(g)[..., None, None] * state
    delta = beta[..., None] * (v - jnp.einsum("bhdv,bhd->bhv", state, k, precision=_HIGHEST))
    state = state + k[..., :, None] * delta[..., None, :]
    return jnp.einsum("bhdv,bhd->bhv", state, q, precision=_HIGHEST), state


# Chunks computed at once. What does not read the carried state (the decays,
# the inverse, U and W) is computed for this many chunks of every row in one
# batch of products, and the backward pass holds one such span's
# intermediates at a time (~300 KB a step at the published widths: a whole
# 8,192-step window's would be 2.4 GB a row).
SPAN_CHUNKS = 16


def _kernel_block(b: int, hv: int, hk: int, dk: int, dv: int, Q: int) -> tuple[int | None, bool]:
    """(value heads per grid step of the Pallas pair, interpret), or (None,
    False) for the ``jax.numpy`` body: the gate of ``models/cells.py``
    (``set_pallas_mode``, the platform of the program being traced) applied to
    the scan, as ``models/mamba2._ssd_kernel_block`` applies it to
    Mamba-2's. The CPU, sizes that are no lane multiples and a batch that does
    not tile a registered data mesh (init and act traces: a Mosaic call has no
    SPMD rule outside its island) keep the ``jax.numpy`` form."""
    from tpu_rl.models import cells

    mode = cells._PALLAS_MODE
    if mode == "off":
        return None, False
    if mode == "interpret":  # any width: every head at once where no block tiles
        return pallas_gdn.head_block(hv, hk, dk, dv, Q) or hv, True
    platform, n_data = cells._program_devices()
    if platform != "tpu" or b % n_data:
        return None, False
    return pallas_gdn.head_block(hv, hk, dk, dv, Q), False


def _kernels(q, k, v, g, beta, seg, state0, chunk, dtype, hb, interpret):
    """The Pallas pair (``ops/pallas_gdn.py``); under a
    registered data mesh whose width the batch tiles, as a ``shard_map``
    island over the ``"data"`` axis, as Mamba-2's kernels run there."""
    from tpu_rl.models import cells

    scan = functools.partial(
        pallas_gdn.delta_window, chunk=chunk, dtype=dtype, hb=hb, interpret=interpret)
    mesh = cells._DATA_MESH
    if mesh is not None and q.shape[0] % cells._program_devices()[1] == 0:
        from jax.sharding import PartitionSpec as P

        from tpu_rl.parallel.mesh import DATA_AXIS

        rows = P(DATA_AXIS)  # every operand: its leading (batch) dim
        # no collectives inside; pallas out_shapes carry no vma annotations
        scan = jax.shard_map(
            scan, mesh=mesh, in_specs=(rows,) * 7, out_specs=(rows, rows), check_vma=False)
    with jax.named_scope("gdn_pallas"):  # the backward's ops carry it too
        return scan(q, k, v, g, beta, seg, state0)


@jax.named_scope("gdn_scan")
def gated_delta_chunked(q, k, v, g, beta, seg, state0, chunk: int, dtype=None, kernel=None):
    """The rule over a whole window in matmul form.

    ``q``, ``k`` (b, T, h_k, d_k) as projected (normalised here); ``v``
    (b, T, h_v, d_v); ``g`` (log decay, <= 0) and ``beta`` (b, T, h_v)
    float32; ``seg`` (b, T) int, 0 = the episode ``state0`` (b, h_v, d_k,
    d_v) belongs to. Returns ``o`` (b, T, h_v, d_v) float32 and the state
    after the last step. ``kernel``: ``(value heads a grid step of the Pallas
    pair or None for the jax.numpy body, interpret)`` where the caller and
    not the gate chooses (tests, ``chip_smoke.py``)."""
    b, T, hk, dk = q.shape
    hv, dv = v.shape[2:]
    hb, interpret = kernel or _kernel_block(b, hv, hk, dk, dv, chunk)
    if hb is None:
        return _chunked_jnp(q, k, v, g, beta, seg, state0, chunk, dtype)
    pad = (-T) % chunk
    if pad:  # g = 0, beta = 0: the state passes through, nothing is written
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (q, k, v, g, beta)
        )
        seg = jnp.concatenate([seg, jnp.repeat(seg[:, -1:], pad, axis=1)], axis=1)
    o, last = _kernels(q, k, v, g, beta, seg, state0, chunk, dtype, hb, interpret)
    return o[:, :T], last


def _chunked_jnp(q, k, v, g, beta, seg, state0, chunk: int, dtype, span_fn=None):
    """``gated_delta_chunked`` as ``einsum``s and ``lax.scan``s: the CPU's path
    and the kernels' oracle. The window is walked in spans of ``SPAN_CHUNKS``
    chunks (``lax.scan``), each span rematerialised in the backward pass.
    ``span_fn``: another rule's span over the same walk (``ops/kda.py``, whose
    ``g`` has one more axis); this rule's ``_span`` by default."""
    span_fn = span_fn or _span
    b, T = q.shape[:2]
    span = chunk * min(SPAN_CHUNKS, -(-T // chunk))
    pad = (-T) % span
    if pad:  # g = 0, beta = 0: the state passes through, nothing is written
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (q, k, v, g, beta)
        )
        seg = jnp.concatenate([seg, jnp.repeat(seg[:, -1:], pad, axis=1)], axis=1)

    def spans_first(a):  # (b, T, ...) -> (spans, b, span, ...)
        return jnp.moveaxis(a.reshape(b, -1, span, *a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one_span(carry, xs):
        state, seg_before = carry
        q, k, v, g, beta, seg = xs
        o, state = span_fn(q, k, v, g, beta, seg, seg_before, state, chunk, dtype)
        return (state, seg[:, -1]), o

    (last, _), o = jax.lax.scan(
        one_span, (state0.astype(jnp.float32), jnp.zeros_like(seg[:, 0])),
        tuple(spans_first(a) for a in (q, k, v, g, beta, seg)),
    )
    return jnp.moveaxis(o, 0, 1).reshape(b, T + pad, *o.shape[3:])[:, :T], last


def _span(q, k, v, g, beta, seg, seg_before, state0, Q: int, dtype):
    """``gated_delta_chunked`` on whole chunks computed at once; ``seg_before``
    (b,): the segment of the step before the first."""
    b, T, hk, dk = q.shape
    hv, dv = v.shape[2:]
    r, nc = hv // hk, T // Q
    cd = dtype or jnp.float32
    f32 = jnp.float32

    def heads_first(a, h):  # (b, T, h, d) -> (b, nc, h, Q, d)
        return a.reshape(b, nc, Q, h, -1).transpose(0, 1, 3, 2, 4)

    qc = heads_first(l2norm(q) * dk ** -0.5, hk).astype(cd)
    kc = heads_first(l2norm(k), hk).astype(cd)
    vc = heads_first(v, hv).astype(cd)
    gamma = jnp.cumsum(g.reshape(b, nc, Q, hv).transpose(0, 1, 3, 2), axis=-1)  # (b, nc, hv, Q)
    bc = beta.reshape(b, nc, Q, hv).transpose(0, 1, 3, 2)
    segc = seg.reshape(b, nc, Q)
    # the segment a chunk is entered in: that of the step before it
    seg_in = jnp.concatenate([seg_before[:, None], segc[:, :-1, -1]], axis=1)

    # step j reaches step i >= j of the same segment
    reach = (segc[:, :, :, None] == segc[:, :, None, :]) & jnp.tril(jnp.ones((Q, Q), bool))
    D = _decay(gamma[..., :, None] - gamma[..., None, :], reach[:, :, None])  # (b, nc, hv, i, j)
    into = _decay(gamma, (segc == seg_in[:, :, None])[:, :, None])  # D_i0, (b, nc, hv, Q)
    to_end = D[..., -1, :]  # D_Cj
    through = into[..., -1]  # D_C0, (b, nc, hv)

    def per_value_head(a):  # (b, nc, hk, Q, Q) -> (b, nc, hv, Q, Q)
        return jnp.repeat(a, r, axis=2)

    kk = jnp.einsum("bcgid,bcgjd->bcgij", kc, kc, preferred_element_type=f32)
    qk = jnp.einsum("bcgid,bcgjd->bcgij", qc, kc, preferred_element_type=f32)
    A = _unit_lower_inverse(jnp.tril(bc[..., :, None] * per_value_head(kk) * D, -1))
    a_beta = A * bc[..., None, :]  # A diag(beta)
    U = jnp.einsum(
        "bchij,bchjv->bchiv", a_beta.astype(cd), vc, preferred_element_type=f32)
    W = jnp.einsum(
        "bcgrij,bcgjd->bcgrid",
        (a_beta * into[..., None, :]).astype(cd).reshape(b, nc, hk, r, Q, Q), kc,
        preferred_element_type=f32,
    ).reshape(b, nc, hv, Q, dk)

    def across(state, c):
        W_c, U_c, k_c, to_end_c, through_c = c
        fresh = U_c - jnp.einsum(
            "bhid,bhdv->bhiv", W_c, state.astype(cd), preferred_element_type=f32)
        written = jnp.einsum(
            "bgid,bgriv->bgrdv", k_c,
            (to_end_c[..., None] * fresh).astype(cd).reshape(b, hk, r, Q, dv),
            preferred_element_type=f32,
        ).reshape(b, hv, dk, dv)
        return through_c[..., None, None] * state + written, (state, fresh)

    chunks_first = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    last, (entered, fresh) = jax.lax.scan(
        across, state0,
        tuple(chunks_first(a) for a in (W.astype(cd), U, kc, to_end, through)),
    )
    entered, fresh = jnp.moveaxis(entered, 0, 1), jnp.moveaxis(fresh, 0, 1)

    o_in = jnp.einsum(
        "bcgid,bcgrdv->bcgriv", qc, entered.astype(cd).reshape(b, nc, hk, r, dk, dv),
        preferred_element_type=f32,
    ).reshape(b, nc, hv, Q, dv)
    o = into[..., None] * o_in + jnp.einsum(
        "bchij,bchjv->bchiv", (per_value_head(qk) * D).astype(cd), fresh.astype(cd),
        preferred_element_type=f32)
    return o.transpose(0, 1, 3, 2, 4).reshape(b, T, hv, dv), last
