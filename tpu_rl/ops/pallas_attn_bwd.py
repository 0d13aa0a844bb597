"""The splash attention backward over the band's tiles alone: one Pallas TPU
kernel for dq, dk and dv.

The library's fused backward (``jax.experimental.pallas.ops.tpu
.splash_attention``: ``_flash_attention_dkv_kernel``) walks the whole
``(key block, head, query block)`` rectangle whatever the mask keeps, and a
step it skips still zeroes a dq tile and writes it to HBM; dq leaves it as
one partial per key block, ``(T / bkv, H, T, D)``, which XLA sums. At T 16,384
in tiles of 1,024 that is 256 steps a head where a causal band holds 136 and a
4,096-key window 70, and 1.9 GB of partials a row and layer at 28 heads of 128
(``PERF.md``, PR 40).

Here the grid is ``(heads, steps)`` with one step a tile of the static band
(:func:`band_steps`): per row, scalar-prefetched arrays name each step's key
block and query block — the band's tiles that no seam emptied, key block by
key block, then steps that do nothing (their indices repeat the last computing
step's, so nothing is fetched or written) — and say where a key block's steps
begin and end. One head's dq gathers in a float32 ``(T, D)`` scratch: a tile's
dq is added where it lands, in VMEM, and at the head's last step the whole is
rounded once into an output block whose index changes with the head alone, so
it goes to HBM once. dk and dv gather in float32 scratch — over a key block's
consecutive steps where a key head serves one query head; in a key head's
whole ``(T, D)`` where it serves several, whose walks come one after another —
and a key block's pair is written when its last query head's last tile is in:
the library's order of additions, so dk and dv are its to the bit (in the
interpreter and on the chip: ``chip_smoke.py``'s rows).

A tile is computed as the library computes it: the same five products on
operands of the inputs' dtype with float32 accumulation, ``exp(qk −
logsumexp)``, the same mask function over the query and key indices, the same
segment-id mask and ``mask_value``, inner steps of ``block_kv_compute`` keys,
q / k / v as ``(head dim, T)``. dq, dk and dv are written in the caller's
``(T, heads, D)`` layout, a head a column block of ``(T, heads * D)`` — where
a head fills whole 128-lane columns; a narrower head (64) cannot be a column
block of its own (Pallas' TPU lowering refuses a block whose last dimension is
neither a multiple of 128 nor the array's), so its three gradients are written
head-major, ``(heads, T, D)``, and re-laid once by XLA. The forward stays the
library's kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_rl.ops.pallas_ssd import _nn, _nt

_F32 = jnp.float32
_LANES = 128
_SUBLANES = 8
_RUN, _FIRST, _LAST = 1, 2, 4  # bits of a step's flag


def band_steps(band: np.ndarray, empty):
    """The backward's walk over one row's tiles. ``band`` (query blocks, key
    blocks) bool, static: the tiles the mask keeps; ``empty`` the same shape,
    traced: those of them a seam emptied. Returns ``(kv_of, q_of, flags)``,
    int32 ``(band.sum(),)``: the kept tiles key block by key block, query
    blocks ascending inside one (the library's order), then the tail — steps
    with flag 0 whose indices repeat the last kept tile's. Flag bits: the
    step computes; it is the first / the last of its key block's."""
    kv_static, q_static = np.nonzero(band.T)  # rows of the transpose: key blocks
    n = kv_static.size
    keep = ~empty[q_static, kv_static]
    rank = jnp.cumsum(keep) - 1  # a kept tile's place in the walk
    count = rank[-1] + 1
    at = jnp.arange(n)
    live = at < count
    # step t takes the kept tile of rank t; a step of the tail the last one's
    takes = keep[None, :] & (rank[None, :] == jnp.minimum(at, count - 1)[:, None])
    kv_of, q_of = (jnp.sum(jnp.where(takes, jnp.asarray(x, jnp.int32)[None, :], 0), axis=1)
                   for x in (kv_static, q_static))
    last = count - 1
    first = live & ((at == 0) | (kv_of != jnp.roll(kv_of, 1)))
    end = live & ((at == last) | (kv_of != jnp.roll(kv_of, -1)))
    flags = _RUN * live + _FIRST * first + _LAST * end
    return kv_of, q_of, flags.astype(jnp.int32)


def _vmem_bytes(T: int, D: int, group: int, bq: int, bkv: int, bkc: int, itemsize: int) -> int:
    """VMEM a call asks for: one head's dq block and every block of a step
    double-buffered, the float32 scratch (a head's dq; for dk and dv a key
    block's rows where a key head serves one query head, a key head's
    ``(T, D)`` where it serves several), and what a tile keeps between its
    products (scores, probabilities, dp, ds and their casts: six ``(bkc, bq)``
    float32 arrays by the count of the library's kernel). 60 MiB at T 16,384
    and 28 : 4 heads of 128 in bf16, 67 MiB at 20 : 20 heads of 256, 64 MiB at
    T 8,192 and 16 : 2 heads of 256."""
    D = max(D, _LANES)  # a narrower head's rows are padded to the lanes
    blocks = itemsize * D * (T + 2 * bq + 4 * bkv)  # dq; q, do; k, v, dk, dv
    blocks += 4 * (bkv * _LANES + 4 * _SUBLANES * bq)  # segment ids, indices, logsumexp, di
    scratch = 4 * D * (T + 2 * (T if group > 1 else bkv))
    return (2 * blocks + scratch + 6 * 4 * bkc * bq) * 5 // 4  # and a quarter for Mosaic's own


def fits(T: int, D: int, group: int, bq: int, bkv: int, bkc: int, itemsize: int) -> bool:
    """Whether a head's dq, the scratch and a step's blocks fit three quarters
    of a core's VMEM on the chip the program is traced for. Where Pallas'
    table does not know the device (the interpreter, a CPU host compiling for
    a described chip) nothing is refused here: the compiler says."""
    try:
        capacity = pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:
        return True
    return _vmem_bytes(T, D, group, bq, bkv, bkc, itemsize) <= 3 * capacity // 4


def _kernel(kv_of, q_of, flags, q_ref, k_ref, v_ref, qseg_ref, kseg_ref, lse_ref, do_ref,
            di_ref, qpos_ref, dq_ref, dk_ref, dv_ref, *rest, group, bq, bkv, bkc, mask_value,
            mask_function):
    dq_acc, dk_acc, dv_acc = rest[-3:]  # before them: the ballast, which nothing touches
    head, step = pl.program_id(0), pl.program_id(1)
    flag = flags[step]
    # a key head's scratch holds every key block when its query heads come one
    # after another, each over all key blocks; else the one block being walked
    base = pl.multiple_of(kv_of[step] * bkv, bkv) if group > 1 else 0

    @pl.when(step == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(((flag & _FIRST) > 0) & (head % group == 0))
    def _():
        dk_acc[pl.ds(base, bkv), :] = jnp.zeros((bkv, dk_acc.shape[1]), _F32)
        dv_acc[pl.ds(base, bkv), :] = jnp.zeros((bkv, dv_acc.shape[1]), _F32)

    @pl.when((flag & _RUN) > 0)
    def _():
        q, do = q_ref[...], do_ref[...]  # (D, bq), (bq, D)
        lse, di = lse_ref[:1, :], di_ref[:1, :]  # (1, bq)
        rows = pl.ds(pl.multiple_of(q_of[step] * bq, bq), bq)
        for c in range(bkv // bkc):
            keys = pl.ds(c * bkc, bkc)
            into = pl.ds(base + c * bkc, bkc)
            k, v = k_ref[:, keys].T, v_ref[:, keys].T  # (bkc, D)
            qk = _nn(k, q)  # (bkc, bq): keys down the rows, as the library's
            keep = jnp.tile(kseg_ref[keys, :], (1, bq // _LANES)) == qseg_ref[:1, :]
            if mask_function is not None:
                k_pos = kv_of[step] * bkv + c * bkc + jax.lax.broadcasted_iota(
                    jnp.int32, (bkc, bq), 0)
                q_pos = jnp.broadcast_to(qpos_ref[:1, :], (bkc, bq))
                keep = mask_function(q_pos, k_pos) & keep
            p = jnp.exp(jnp.where(keep, qk, mask_value) - lse)
            dv_acc[into, :] = _nn(p.astype(do.dtype), do) + dv_acc[into, :]
            ds = (_nt(v, do) - di) * p
            dk_acc[into, :] = _nt(ds.astype(do.dtype), q) + dk_acc[into, :]
            dq_acc[rows, :] = _nn(ds.T.astype(k.dtype), k) + dq_acc[rows, :]

    @pl.when(((flag & _LAST) > 0) & (head % group == group - 1))
    def _():
        dk_ref[...] = dk_acc[pl.ds(base, bkv), :].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[pl.ds(base, bkv), :].astype(dv_ref.dtype)

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def attention_bwd(q, k, v, seg, logsumexp, do, di, steps, *, q_sequence, mask_function,
                  mask_value, block_q, block_kv, block_kv_compute, ballast=0, interpret=False,
                  kv_seg=None):
    """dq, dk, dv of one row in the caller's layout: q ``(T, H, D)``, k, v
    ``(T, Hkv, D)``, seg ``(T,)`` int32, do ``(H, T, D)`` (where the forward's
    output lies, beside which ``di`` was summed), logsumexp, di ``(H, T)`` float32,
    ``steps`` :func:`band_steps`' three arrays, ``q_sequence`` ``(T,)`` and
    ``mask_function`` the static mask's (None: a full mask). dq as q, dk and dv
    as k and v, each rounded once from float32. q, k and v enter as
    ``(heads, D, T)`` (one re-laying each, XLA's, as the library's kernels take
    them); the three gradients are written where they lie, a head a column
    block of ``(T, heads * D)`` (head-major and re-laid after the call where
    ``D`` is no multiple of 128). A key block's dk and dv take
    the tiles of a key head's query heads one after another, each head's query
    blocks ascending — the library's order, so they are the library's to the
    bit. ``ballast``: that many ``(H, T, D)`` arrays of q's dtype declared as
    one more output in HBM that the kernel never touches, kept alive by a sum
    that adds nothing (``parallel/sequence._splash_rows_skipping_seams`` says
    why). ``kv_seg`` ``(Tk,)``: the keys' own segment ids where they are not the
    queries' steps (k, v ``(Tk, Hkv, D)`` with ``Tk`` any multiple of
    ``block_kv``: the summaries an EVA layer reads, ``parallel/sequence.
    summary_attention_lse``); None: the keys are the window's steps and share
    ``seg``."""
    T, H, D = q.shape
    Tk, heads_kv = k.shape[:2]
    kv_seg = seg if kv_seg is None else kv_seg
    group = H // heads_kv
    bq, bkv, bkc = block_q, block_kv, block_kv_compute
    assert T % bq == 0 and Tk % bkv == 0 and bkv % bkc == 0 and bq % _LANES == 0, (
        T, Tk, bq, bkv, bkc)
    acc = pltpu.VMEM((Tk if group > 1 else bkv, D), _F32)

    rows8 = lambda x: jnp.broadcast_to(x[..., None, :], (*x.shape[:-1], _SUBLANES, T))  # noqa: E731
    tile = lambda h, s, kv_of, q_of, flags: (h, 0, q_of[s])  # noqa: E731
    key_tile = lambda h, s, kv_of, q_of, flags: (h // group, 0, kv_of[s])  # noqa: E731
    row_tile = lambda h, s, kv_of, q_of, flags: (0, q_of[s])  # noqa: E731

    def dkv_tile(h, s, kv_of, q_of, flags):
        # only a key head's last query head writes: the others keep one block
        # index (their first's), so no unwritten block is copied out
        return jnp.where(h % group == group - 1, kv_of[s], 0), h // group

    columns = D % _LANES == 0  # a head is a column block of the caller's (T, heads * D)

    in_specs = [
        pl.BlockSpec((None, D, bq), tile),  # q as (H, D, T)
        pl.BlockSpec((None, D, bkv), key_tile),
        pl.BlockSpec((None, D, bkv), key_tile),
        pl.BlockSpec((_SUBLANES, bq), row_tile),  # the queries' segment ids along the lanes
        pl.BlockSpec((bkv, _LANES), lambda h, s, kv_of, q_of, flags: (kv_of[s], 0)),
        pl.BlockSpec((None, _SUBLANES, bq), tile),  # logsumexp
        pl.BlockSpec((None, bq, D), lambda h, s, kv_of, q_of, flags: (h, q_of[s], 0)),  # do
        pl.BlockSpec((None, _SUBLANES, bq), tile),  # di
        pl.BlockSpec((_SUBLANES, bq), row_tile),  # the queries' indices
    ]
    if columns:
        out_specs = [
            pl.BlockSpec((T, D), lambda h, s, *_: (0, h)),
            pl.BlockSpec((bkv, D), dkv_tile),
            pl.BlockSpec((bkv, D), dkv_tile),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((T, H * D), q.dtype),
            jax.ShapeDtypeStruct((Tk, heads_kv * D), k.dtype),
            jax.ShapeDtypeStruct((Tk, heads_kv * D), v.dtype),
        ]
    else:
        dkv_major = lambda *at: (*dkv_tile(*at)[::-1], 0)  # noqa: E731
        out_specs = [
            pl.BlockSpec((None, T, D), lambda h, s, *_: (h, 0, 0)),
            pl.BlockSpec((None, bkv, D), dkv_major),
            pl.BlockSpec((None, bkv, D), dkv_major),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((H, T, D), q.dtype),
            jax.ShapeDtypeStruct((heads_kv, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((heads_kv, Tk, D), v.dtype),
        ]
    if ballast:
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        out_shape.append(jax.ShapeDtypeStruct((ballast * H, T, D), q.dtype))
    kernel = functools.partial(
        _kernel, group=group, bq=bq, bkv=bkv, bkc=bkc, mask_value=mask_value,
        mask_function=mask_function)
    heads_minor = lambda x: x.transpose(1, 2, 0)  # (T, heads, D) -> (heads, D, T)  # noqa: E731
    dq, dk, dv, *held = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, in_specs=in_specs, out_specs=out_specs,
            grid=(H, steps[0].shape[0]),
            scratch_shapes=[pltpu.VMEM((T, D), _F32), acc, acc],
        ),
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            # heads one after another too: a head's dq, a key head's scratch
            # and the prefetch schedule assume one core walks the grid in order
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(T, D, group, bq, bkv, bkc, q.dtype.itemsize),
        ),
        name="attn_bwd_band",
    )(
        *steps,
        heads_minor(q), heads_minor(k), heads_minor(v),
        rows8(seg), jnp.broadcast_to(kv_seg[:, None], (Tk, _LANES)),
        rows8(logsumexp), do, rows8(di), rows8(q_sequence.astype(jnp.int32)),
    )
    if columns:
        dq, dk, dv = dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)
    else:
        dq, dk, dv = (x.transpose(1, 0, 2) for x in (dq, dk, dv))
    for x in held:  # the ballast: one element read by a sum no compiler can drop (ids are positive)
        dq = dq + jnp.where(seg[0] < 0, x[0, 0, 0], 0).astype(dq.dtype)
    return dq, dk, dv
