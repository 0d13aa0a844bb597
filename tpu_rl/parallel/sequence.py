"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no attention and no sequence parallelism of any kind
(SURVEY.md §5.7 — its "sequence" is a 5-step LSTM window). This module is the
TPU-native long-context subsystem: train on sequences far longer than one
chip's HBM by sharding the time dimension across a ``"seq"`` mesh axis.

Two standard schemes, both exact (not approximations):

- **Ring attention** (`ring_attention`): queries stay put; K/V blocks rotate
  around the ring via ``jax.lax.ppermute``, one neighbor hop per step, while
  a flash-style online softmax (running max + normalizer) accumulates the
  exact attention output. Memory per chip is O(T/n); the K/V transfer rides
  ICI and overlaps with the block matmuls.
- **Ulysses all-to-all** (`ulysses_attention`): ``all_to_all`` re-shards from
  sequence-sharded to head-sharded, runs full-sequence attention on each
  chip's head subset, then re-shards back. Cheaper collectives for moderate
  T; requires heads % n == 0.

Both take explicit global *positions* and *segment ids* so causal masking and
episode-boundary resets (``is_fir`` seams, the RL analog of document masking)
stay correct under sharding — segment ids are computed once, globally, by the
caller (a cumsum over ``is_fir``) and sharded alongside Q/K/V.

Used inside ``shard_map`` with the mesh from :func:`make_sp_mesh`; wrapped
for end users by ``tpu_rl.models.transformer`` and the long-context train
step. All ops are differentiable (``ppermute``/``all_to_all`` have exact
transposes), so one ``jax.grad`` of the wrapped loss backpropagates through
the ring.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
SEQ_AXIS = "seq"

_NEG_INF = -1e30  # finite -inf stand-in: keeps exp()/max() NaN-free


def _splash_edge(T: int) -> int:
    """The edge of the splash kernel's tiles at sequence length T."""
    return math.gcd(1024, T)


def _splash_block_sizes(T: int):
    """Tiles of the splash kernel (``jax.experimental.pallas.ops.tpu
    .splash_attention.BlockSizes``) for sequence length T; None = the kernel
    cannot tile T (not a multiple of its 128 lanes): the caller takes
    :func:`full_attention`.

    The rule: one edge everywhere, gcd(1024, T); 512 columns per inner step;
    the single-pass backward (``use_fused_bwd_kernel``: dq, dk and dv from one
    recomputation of the scores); q, k and v handed over as (head dim, T) —
    head dim 64 fills half a 128-lane row, T fills it, and XLA folds the
    ``swapaxes`` into the transpose the call needs anyway. The same choice won
    at both measured shapes, so T alone enters the rule. One layer, bf16, head
    dim 64, ms forward / forward + backward on a TPU v5e
    (``examples/bench_flash_attention.py``, PR 27):

    ================================  ===============  =====================
    tiles                             B32 T2048 H8:8   B2 T4096 H32:8 (1/64)
    ================================  ===============  =====================
    the old library kernel, 512       5.08 / 22.66     4.07 / 16.80
    256, single pass                  10.67 / 27.39    8.04 / 23.80
    512, single pass                  5.50 / 14.80     3.71 / 11.32
    512, two passes                   5.47 / 18.03     3.74 / 13.58
    1024 / 512, single pass           4.93 / 13.77     3.15 / 9.41
    **the same, q k v (head dim, T)** **3.81 / 12.69** **2.80 / 8.94**
    the same, k v (head dim, T)       4.46 / 13.23     2.87 / 9.13
    1024 / 256, single pass           5.04 / 14.16     3.21 / 9.63
    1024 / 1024, single pass          5.26 / 13.98     3.41 / 9.59
    1024, two passes                  4.92 / 16.56     3.16 / 11.30
    q 512 x kv 1024                   5.26 / 14.40     3.39 / 10.07
    q 1024 x kv 512                   5.49 / 15.04     3.56 / 10.72
    q 1024 x kv 2048                  5.44 / 15.08     3.26 / 9.65
    ================================  ===============  =====================

    A whole-T tile (2048, 4096) does not fit VMEM."""
    if T % 128:
        return None
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes, QKVLayout

    edge = _splash_edge(T)
    compute = min(512, edge)
    return BlockSizes(
        block_q=edge, block_kv=edge, block_kv_compute=compute,
        block_q_dkv=edge, block_kv_dkv=edge, block_kv_dkv_compute=compute,
        use_fused_bwd_kernel=True,
        q_layout=QKVLayout.SEQ_MINOR, k_layout=QKVLayout.SEQ_MINOR,
        v_layout=QKVLayout.SEQ_MINOR,
    )


# The splash grid's edge, in blocks, from which each row's block masks are read
# from its segment ids. The rule needs three (the diagonal tile is never empty,
# the one next to it only when a seam falls on the very edge between them). It
# is eight because the traced masks cost a walk over the rows
# (:func:`_splash_rows_skipping_seams`), and at a 4 x 4 grid that walk over
# nemotron-3-nano's four rows raised the cell's peak device memory by 2% (the
# update program's temporaries +0.8 GiB compiled for a v5e; PERF.md section 6,
# PR 35) for 3 of 10 tiles a seam can empty; at 16 x 16 it is 105 of 136.
_SEAM_BLOCKS = 8


def seam_empty_tiles(seg, edge: int):
    """Tiles of the (T / edge)^2 attention grid in which no query and key
    share a segment: seg (B, T) -> (B, T / edge, T / edge) bool, indexed
    [row, query block, key block]. From each block's smallest and largest id:
    two blocks whose id ranges do not meet hold no equal pair. Sound for any
    ids; complete for the monotone ids every caller passes
    (:func:`segment_ids_from_firsts`), where a range has no gaps. The diagonal
    is never empty. O(T); takes ``jnp`` and ``numpy`` arrays alike."""
    blocks = seg.reshape(seg.shape[0], seg.shape[1] // edge, edge)
    lo, hi = blocks.min(axis=2), blocks.max(axis=2)
    return (hi[:, None, :] < lo[:, :, None]) | (lo[:, None, :] > hi[:, :, None])


def band_tiles(T: int, edge: int, window: int | None = None) -> np.ndarray:
    """Tiles of that grid the static causal mask keeps, (T / edge, T / edge)
    bool [query block, key block]: on or under the diagonal and, with
    ``window``, holding a pair less than ``window`` steps apart. The nonzero
    entries of the library's block mask (``tests/test_sequence_parallel.py``
    holds the two together)."""
    i = np.arange(T // edge)
    behind = i[:, None] - i[None, :]  # key blocks behind the query's
    band = behind >= 0
    if window is not None:
        band &= (behind - 1) * edge + 1 < window
    return band


def attention_tiles(seg, window: int | None = None, edge: int | None = None):
    """What the splash kernels do with a batch of windows, counted from
    ``seg`` (B, T): ``(run, band, steps)`` — the tiles they compute (in the
    static band and not emptied by a seam), the static band's, and the grid
    steps the backward takes a head, each summed over the rows, float32.
    ``edge``: the tile's, :func:`_splash_edge`'s by default. Where the grid is
    too small for :func:`_splash_mha` to read the seams, every band tile runs
    and the library's fused backward steps over the whole (T / edge)^2
    rectangle; from ``_SEAM_BLOCKS`` blocks an edge on the backward is the
    repo's own and its grid is the band."""
    T = seg.shape[1]
    edge = _splash_edge(T) if edge is None else edge
    band = band_tiles(T, edge, window)
    total = jnp.float32(seg.shape[0] * int(band.sum()))
    if T // edge < _SEAM_BLOCKS:
        return total, total, jnp.float32(seg.shape[0] * band.size)
    run = jnp.asarray(band) & ~seam_empty_tiles(seg, edge)
    return jnp.sum(run.astype(jnp.float32)), total, total


def _next_computed(block_mask, data_next):
    """``data_next`` of one head's (rows, columns) grid for a block mask with
    more zeros than the one it was made for: the kernels walk the grid row by
    row and, at a step they skip, prefetch the block ``data_next`` names —
    so each step names the block of the next step that computes (its own if
    it does, the first one's after the last), as the library does for its
    static zeros, and a skipped tile costs no fetch."""
    n = block_mask.size
    at = jnp.where(block_mask.reshape(n) > 0, jnp.arange(n), n)
    nxt = jax.lax.cummin(at, reverse=True)
    nxt = jnp.where(nxt == n, jnp.min(at) % n, nxt)
    return data_next.reshape(n)[nxt].reshape(data_next.shape)


def _skip_seams(splash, empty):
    """``splash`` (a ``SplashAttentionKernel`` over static mask info) with the
    tiles of ``empty`` (T / edge, T / edge; one row of
    :func:`seam_empty_tiles`) zeroed in both block masks, so neither of the
    library's kernels computes them, and the prefetch indices following
    (:func:`_next_computed`), so none fetches their blocks. Zeros are only
    added: an entry of 1 or 2 and the in-kernel segment mask mean what they
    meant, and a wrong "not empty" costs time, never correctness. The
    forward's grid is shrunk to the band — a column is a slot, and the static
    ``data_next`` says which key block the slot holds. The third mask info is
    the library's fused backward's, over the whole [query block, key block]
    grid walked key block by key block: only a row whose backward stays the
    library's reads it (:func:`_splash_rows_skipping_seams`); the repo's own
    backward walks a list of the band's tiles instead
    (``ops/pallas_attn_bwd.band_steps``, from the same ``empty``)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel

    fwd, dkv = splash.fwd_mask_info, splash.dkv_mask_info
    assert splash.dq_mask_info is None and dkv.block_mask.shape[1:] == empty.shape
    slot_block = fwd.data_next[0].astype(jnp.int32)  # (query blocks, slots)
    in_slot = jnp.take_along_axis(empty, slot_block, axis=1)

    def zeroed(info, e, walk):
        mask = jnp.where(e[None], 0, info.block_mask).astype(info.block_mask.dtype)
        nxt = walk(_next_computed(walk(mask[0]), walk(info.data_next[0])))
        return info._replace(block_mask=mask, data_next=nxt[None].astype(info.data_next.dtype))

    return splash_attention_kernel.SplashAttentionKernel(
        zeroed(fwd, in_slot, lambda x: x), None, zeroed(dkv, empty, jnp.transpose),
        **splash.kwargs)


def _splash_kernel(T, H, *, causal, window, block_sizes, interpret, keys=None):
    """The library's kernel object over the static mask of ``H`` equal heads:
    causal (or full), and inside ``window`` where one is given. ``keys``: the
    key side's length where it is not the queries' (``T`` by default)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        CausalMask,
        FullMask,
        LocalMask,
        MultiHeadMask,
        make_splash_mha,
    )

    if window is None:
        head_mask = (CausalMask if causal else FullMask)((T, T if keys is None else keys))
    else:
        assert causal, "a window is the causal one: the keys that end with the query"
        head_mask = LocalMask((T, T), window_size=(window - 1, 0), offset=0)
    return make_splash_mha(
        MultiHeadMask([head_mask] * H),
        block_sizes=block_sizes,
        head_shards=1,
        q_seq_shards=1,
        interpret=interpret,
    )


def _splash_mha(q, k, v, seg, *, causal, scale, block_sizes, interpret=False, window=None):
    """The splash kernel on this module's layout: q (B, T, H, D), k and v
    (B, T, Hkv, D) with ``H % Hkv == 0`` (every key/value head serves
    ``H // Hkv`` consecutive query heads, unrepeated), seg (B, T). Causal by
    index plus same-segment, built per trace: the block-sparse mask info is
    numpy work on a (T / tile)^2 grid. With ``window`` (causal only) a query
    sees the ``window`` keys that end with itself (the library's ``LocalMask``):
    the mask info then names the tiles inside the band alone. The forward's
    grid is shrunk to them, so it never visits a tile that lies wholly behind
    the band; the library's fused backward's is not (``shrink_grid=not
    use_fused_bwd_kernel``): it steps over every tile of the grid, computes the
    band's, and leaves dq as one partial a key block for XLA to sum. Where the
    grid has ``_SEAM_BLOCKS`` blocks an edge or more, each row's block masks
    are read from its segment ids (:func:`_splash_rows_skipping_seams`): a band
    tile in which no query and key share a segment is stepped over in the
    forward instead of computed and masked whole inside the kernel, and the
    backward is the repo's own (``ops/pallas_attn_bwd.py``), whose grid *is*
    the band: one step a band tile, the tiles a seam emptied left out of the
    walk, dq added in float32 where it lands. A smaller grid keeps the static
    masks and the library's backward and lowers to the program it always did.
    The kernel takes no softmax scale, so ``scale`` is folded into q first —
    exact in bf16 for a power of two (tf-longctx: 1/8; granite: 1/64); a
    general scale (head size 128: 128^-0.5) rounds q once more.
    ``interpret=True`` runs the same construction on the CPU (tier-1 tests)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import SegmentIds

    build = dict(causal=causal, window=window, block_sizes=block_sizes, interpret=interpret)
    if q.shape[1] // block_sizes.block_q >= _SEAM_BLOCKS:
        return _splash_rows_skipping_seams(q, k, v, seg, scale=scale, **build)
    splash = _splash_kernel(q.shape[1], q.shape[2], **build)
    # our layout (B, T, H, D) -> kernel layout (H, T, D), one batch row a call
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q * scale, k, v))
    seg32 = seg.astype(jnp.int32)
    o = jax.vmap(
        lambda q, k, v, s: splash(q, k, v, segment_ids=SegmentIds(q=s, kv=s))
    )(qt, kt, vt, seg32)
    return o.transpose(0, 2, 1, 3)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_sizes", "interpret", "window", "lse"))
def _splash_rows_skipping_seams(
        q, k, v, seg, *, causal, scale, block_sizes, interpret, window, lse=False):
    """:func:`_splash_mha` with each row's block masks read from its segment
    ids. One splash call a row, in a Python loop: a ``jax.vmap`` over the
    traced scalar-prefetch operands makes Pallas loop over the rows inside one
    ``while`` whose state takes a copy of each kernel output (7.8 GiB of
    temporaries a layer at T 16,384 against this loop's 2.2 and the static
    call's 3.8, compiled for a v5e, PR 35), and with one call a row only one
    row's gradients are alive at a time. Under one ``jax.jit``: an eager
    caller dispatches one program, and the layers of a model that share shapes
    and window are traced once.

    The forward of a row is the library's kernel on the masks
    :func:`_skip_seams` builds; its backward is the repo's own
    (:func:`_seam_row_bwd`, ``ops/pallas_attn_bwd.py``) over the band's tiles
    alone, unless a head's dq does not fit the core's VMEM
    (``pallas_attn_bwd.fits``), where the library's fused backward stays.

    ``lse``: each row's logsumexp ``(B, H, T)`` float32 comes back beside the
    output, differentiable too (:func:`flash_attention_lse`: a caller that
    merges this softmax with another over further keys). Only the repo's own
    backward takes its cotangent."""
    from tpu_rl.ops import pallas_attn_bwd

    T, D = q.shape[1], q.shape[3]
    splash = _splash_kernel(
        T, q.shape[2], causal=causal, window=window, block_sizes=block_sizes,
        interpret=interpret)
    seg32 = seg.astype(jnp.int32)
    empty = seam_empty_tiles(seg32, block_sizes.block_q)
    own_bwd = pallas_attn_bwd.fits(
        T, D, q.shape[2] // k.shape[2], block_sizes.block_q_dkv, block_sizes.block_kv_dkv,
        block_sizes.block_kv_dkv_compute, q.dtype.itemsize)

    # Where several rows are walked, a row's backward still declares the HBM the
    # library's declared for its dq partials — T / bkv arrays of (H, T, D), never
    # written, one element read — because XLA's TPU pipeline packs a program only
    # when its first schedule overflows the chip, and without every row's
    # partials smallthinker-21b-a3b's does not: 10.85 GB at the program's peak
    # for a v5e against the parent's 8.83, and 8.83 with them declared (PERF.md
    # section 6, PR 40: from 14 of its 16 blocks on; 12: 10.86; 17: 8.87). One row
    # has no second row's buffers to be kept beside (glm-4.7-flash) and declares none.
    ballast = T // block_sizes.block_kv_dkv if q.shape[0] > 1 else 0
    mask = (causal, window, ballast)
    assert own_bwd or not lse, "the library's backward takes no logsumexp cotangent"
    row = _seam_row_lse if lse else _seam_row if own_bwd else _seam_row_forward
    rows = [
        row(mask, splash, q[b] * scale, k[b], v[b], seg32[b], empty[b])
        for b in range(q.shape[0])
    ]
    if lse:
        return jnp.stack([o for o, _ in rows]), jnp.stack([l for _, l in rows])
    return jnp.stack(rows)


def _library_row(splash, q, k, v, seg, empty, save_residuals, kv_seg=None, reach=None):
    """One row, q (T, H, D), through the library's kernels on the masks
    ``empty`` leaves: our layout -> the kernels' (H, T, D) and back, each a
    pass XLA fuses, none over a stacked batch. ``save_residuals``: the
    logsumexp (H, T) beside the output. ``kv_seg``: the keys' own segment ids
    where they are not the window's steps. ``reach`` (T,) int: the index of
    the last key each query may read, in place of the query's own index — the
    causal mask function then runs on every tile the row computes, none is
    taken as wholly kept (:func:`summary_attention_lse`)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import SegmentIds

    kernel = _skip_seams(splash, empty)
    if reach is not None:
        fwd = kernel.fwd_mask_info
        kernel.fwd_mask_info = fwd._replace(
            block_mask=jnp.minimum(fwd.block_mask, 1).astype(fwd.block_mask.dtype),
            q_sequence=reach)
    kernel.kwargs["save_residuals"] = save_residuals
    out = kernel(
        *(x.transpose(1, 0, 2) for x in (q, k, v)),
        segment_ids=SegmentIds(q=seg, kv=seg if kv_seg is None else kv_seg))
    if save_residuals:
        out, (logsumexp,) = out
        return out.transpose(1, 0, 2), logsumexp
    return out.transpose(1, 0, 2)


def _seam_row_forward(mask, splash, q, k, v, seg, empty):
    """A row whose backward, if taken, is the library's. ``mask``: (causal,
    window, ballast), what :func:`_seam_row`'s backward needs that is no array."""
    return _library_row(splash, q, k, v, seg, empty, False)


_seam_row = jax.custom_vjp(_seam_row_forward, nondiff_argnums=(0,))


def _seam_row_fwd(mask, splash, q, k, v, seg, empty):
    out, logsumexp = _library_row(splash, q, k, v, seg, empty, True)
    return out, (splash, q, k, v, seg, empty, out, logsumexp)


def _seam_row_bwd(mask, res, do, dlse=None):
    """dq, dk, dv of one row from the forward's ``(out, logsumexp)``: ``di``
    as the library computes it, then one kernel over the steps
    :func:`tpu_rl.ops.pallas_attn_bwd.band_steps` lists — the grid *is* the
    band — under the scope ``attn_bwd_pallas``. ``dlse`` (H, T): the
    logsumexp's own cotangent where it was an output (:func:`_seam_row_lse`).
    A score's gradient is ``p (dp - di)`` and the logsumexp's derivative by a
    score is ``p``, so it folds into ``di`` and the kernel is the same."""
    from tpu_rl.ops import pallas_attn_bwd

    causal, window, ballast = mask
    splash, q, k, v, seg, empty, out, logsumexp, *others = res
    kv_seg, reach = others or (None, None)  # :func:`_reach_row`'s: keys that are no steps
    info, bs = splash.dkv_mask_info, splash.kwargs["block_sizes"]
    assert info.partial_mask_blocks is None and info.block_mask.shape[0] == 1
    T = q.shape[0]
    square = causal and reach is None
    band = band_tiles(T, bs.block_q_dkv, window) if square else np.ones(empty.shape, bool)
    with jax.named_scope("attn_bwd_pallas"):
        # out lies as the forward's kernel wrote it, (H, T, D): do is brought there once,
        # in its own dtype, for di and for the kernel (left as (T, H, D), XLA re-lays both
        # operands of this sum in float32)
        out, do = out.transpose(1, 0, 2), do.transpose(1, 0, 2)
        di = jnp.einsum("hsd,hsd->hs", out.astype(jnp.float32), do.astype(jnp.float32))
        if dlse is not None:
            di = di - dlse
        dq, dk, dv = pallas_attn_bwd.attention_bwd(
            q, k, v, seg, logsumexp, do, di, pallas_attn_bwd.band_steps(band, empty),
            q_sequence=reach if reach is not None else (
                jnp.arange(T) if info.q_sequence is None else info.q_sequence),
            mask_function=splash.kwargs["mask_function"],
            mask_value=splash.kwargs["mask_value"], block_q=bs.block_q_dkv,
            block_kv=bs.block_kv_dkv, block_kv_compute=bs.block_kv_dkv_compute,
            ballast=ballast, interpret=splash.kwargs["interpret"], kv_seg=kv_seg)
    return (None, dq, dk, dv, *(None,) * (2 + len(others)))


_seam_row.defvjp(_seam_row_fwd, _seam_row_bwd)


def _seam_row_lse_forward(mask, splash, q, k, v, seg, empty):
    """:func:`_seam_row` with the row's logsumexp (H, T) as a second output."""
    return _library_row(splash, q, k, v, seg, empty, True)


_seam_row_lse = jax.custom_vjp(_seam_row_lse_forward, nondiff_argnums=(0,))


def _seam_row_lse_fwd(mask, splash, q, k, v, seg, empty):
    out, logsumexp = _seam_row_lse_forward(mask, splash, q, k, v, seg, empty)
    return (out, logsumexp), (splash, q, k, v, seg, empty, out, logsumexp)


_seam_row_lse.defvjp(_seam_row_lse_fwd, lambda mask, res, ct: _seam_row_bwd(mask, res, *ct))


def _reach_row_forward(splash, q, k, v, seg, empty, kv_seg, reach):
    """One row's queries (T, H, D) against keys that are no steps of the window
    (Tk, H, D) with segment ids of their own, each query reading the keys of
    its segment up to index ``reach[t]``: the output, normalised over those
    alone, and their logsumexp (H, T)."""
    return _library_row(splash, q, k, v, seg, empty, True, kv_seg, reach)


_reach_row = jax.custom_vjp(_reach_row_forward)


def _reach_row_fwd(splash, q, k, v, seg, empty, kv_seg, reach):
    out, logsumexp = _reach_row_forward(splash, q, k, v, seg, empty, kv_seg, reach)
    return (out, logsumexp), (splash, q, k, v, seg, empty, out, logsumexp, kv_seg, reach)


_reach_row.defvjp(_reach_row_fwd, lambda res, ct: _seam_row_bwd((True, None, 0), res, *ct))


def make_sp_mesh(n_data: int, n_seq: int, devices=None) -> Mesh:
    """2-D (data, seq) mesh. Sequence ring hops are between mesh neighbors,
    so keep the seq axis minor (fastest-varying) — on TPU that maps the ring
    onto adjacent ICI links."""
    devs = list(devices) if devices is not None else jax.devices()
    need = n_data * n_seq
    if need > len(devs):
        raise ValueError(f"need {need} devices, have {len(devs)}")
    grid = np.asarray(devs[:need]).reshape(n_data, n_seq)
    return Mesh(grid, (DATA_AXIS, SEQ_AXIS))


# --------------------------------------------------------------------- core
def _masked_block_scores(q, k, q_pos, k_pos, q_seg, k_seg, scale, causal, window=None):
    """(B, H, Tq, Tk) masked logits for one Q-block/K-block pair. Always
    float32: bf16 inputs hit the MXU, accumulation stays full-precision
    (the canonical TPU mixed-precision pattern). ``window``: a query sees
    the keys at most ``window - 1`` positions behind it."""
    scores = _qk_scores_dot(q, k, _contract_dtype(q)) * jnp.float32(scale)
    mask = q_seg[:, None, :, None] == k_seg[:, None, None, :]
    if causal:
        mask &= q_pos[:, None, :, None] >= k_pos[:, None, None, :]
    if window is not None:
        mask &= q_pos[:, None, :, None] - k_pos[:, None, None, :] < window
    return jnp.where(mask, scores, _NEG_INF)


def _contract_dtype(x: jax.Array) -> jnp.dtype:
    """Dtype for attention CONTRACTION operands: the input's own dtype for
    low-precision inputs (bf16 x bf16 hits the MXU fast path; a mixed
    f32 x bf16 dot runs at f32 rate — the softmax probabilities are f32, so
    without the cast every probs-against-values contraction pays full f32),
    f32 otherwise. Accumulation is always f32 (``preferred_element_type``);
    softmax statistics and elementwise math stay f32 regardless.
    Returns the scalar type CLASS (``jnp.bfloat16``), not a dtype instance
    — custom_vjp static args must be plain hashable Python values."""
    return jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32


def _make_mp_einsum(spec, da_spec, db_spec, db_primal_first):
    """Bilinear einsum as a custom-VJP op whose BACKWARD also contracts in
    ``dtype``: the autodiff transpose of a plain einsum receives an f32
    cotangent, so for bf16 inputs every backward dot would be a mixed
    f32 x bf16 dot at f32 MXU rate (the same failure mode
    ``ops.pallas_lstm.mixed_dot`` fixes for the LSTM). The ring/blockwise
    paths hand-write their backward and never AD through these; full and
    Ulysses attention rely on them. Each returned cotangent is cast to its
    PRIMAL's dtype (JAX's own transpose convention) so the chain upstream
    — e.g. the Q/K/V projection backward against bf16 weights — stays
    same-dtype too. f32 inputs are bit-identical to the plain einsum.

    ``da_spec`` contracts (g, b) -> da; ``db_spec`` contracts (a, g) when
    ``db_primal_first`` else (g, a) -> db. Accumulation is f32 throughout.
    """

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def op(a, b, dtype):
        return jnp.einsum(
            spec, a.astype(dtype), b.astype(dtype),
            preferred_element_type=jnp.float32,
        )

    def fwd(a, b, dtype):
        # zero-dim dtype tokens: residual pytree leaves must be arrays, and
        # bwd needs the PRIMAL dtypes to cast the cotangents back
        return op(a, b, dtype), (
            a.astype(dtype), b.astype(dtype),
            jnp.zeros((), a.dtype), jnp.zeros((), b.dtype),
        )

    def bwd(dtype, res, g):
        ad, bd, a_tok, b_tok = res
        gd = g.astype(dtype)
        da = jnp.einsum(da_spec, gd, bd, preferred_element_type=jnp.float32)
        db_ops = (ad, gd) if db_primal_first else (gd, ad)
        db = jnp.einsum(db_spec, *db_ops, preferred_element_type=jnp.float32)
        return da.astype(a_tok.dtype), db.astype(b_tok.dtype)

    op.defvjp(fwd, bwd)
    return op


# scores = einsum('bqhd,bkhd->bhqk', q, k)
_qk_scores_dot = _make_mp_einsum(
    "bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd", "bhqk,bqhd->bkhd",
    db_primal_first=False,
)
# out = einsum('bhqk,bkhd->bqhd', p, v); dp stays f32 automatically
# (p's primal dtype is f32 — softmax statistics are always f32).
_pv_dot = _make_mp_einsum(
    "bhqk,bkhd->bqhd", "bqhd,bkhd->bhqk", "bhqk,bqhd->bkhd",
    db_primal_first=True,
)


def _online_update(o, m, l, scores, v_blk):
    """Flash-attention online-softmax accumulation of one K/V block.
    o: (B, Tq, H, D); m, l: (B, H, Tq); scores: (B, H, Tq, Tk)."""
    m_new = jnp.maximum(m, scores.max(axis=-1))
    alpha = jnp.exp(m - m_new)  # rescale of previous accumulators
    p = jnp.exp(scores - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    cd = _contract_dtype(v_blk)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd",
        p.astype(cd),
        v_blk.astype(cd),
        preferred_element_type=jnp.float32,
    )
    return o_new, m_new, l_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    seg: jax.Array,
    axis_name: str = SEQ_AXIS,
    causal: bool = True,
) -> jax.Array:
    """Exact attention over a sequence sharded on ``axis_name``.

    Args (all per-device shards):
      q, k, v : (B, Tl, H, D)
      q_pos   : (B, Tl) global positions of this shard's rows
      seg     : (B, Tl) global segment ids (episode index) of this shard
    Returns (B, Tl, H, D).

    Differentiable via a custom VJP that re-runs the ring on the backward
    pass (the ring attention paper's scheme): K/V blocks are *recomputed by
    re-rotating*, never stored per step. Without this, autodiff would save
    the scan carry — which includes the rotating ``(B, Tl, H, D)`` K/V
    blocks — once per ring step, making backward residuals O(n · Tl) = the
    full sequence per chip, defeating the O(T/n) memory claim exactly when
    it matters (training). Residuals here are O(Tl): q, k, v, o, and the
    per-row logsumexp.
    """
    return _ring_attention_vjp(axis_name, bool(causal), q, k, v, q_pos, seg)


def _ring_forward(axis_name, causal, q, k, v, q_pos, seg):
    """One rotation of the ring: flash-style online softmax over the n K/V
    blocks. Returns the normalized output and the per-row logsumexp (the
    only softmax stat the backward pass needs)."""
    n = jax.lax.psum(1, axis_name)
    scale = 1.0 / np.sqrt(q.shape[-1])
    # Derive the accumulators from q so they carry q's device-varying type
    # (shard_map's varying-axis tracking requires scan carries to keep a
    # stable type across iterations), then hold them in float32: softmax
    # stats and the output accumulate full-precision even for bf16 q/k/v.
    o = (q * 0.0).astype(jnp.float32)
    zero_bht = (q.sum(axis=-1).transpose(0, 2, 1) * 0.0).astype(jnp.float32)
    m = zero_bht + _NEG_INF
    l = zero_bht
    # Each ring step sees the K/V block originally owned by device
    # (idx - step) mod n; its rows' global positions/segments travel with it.
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, _):
        o, m, l, k_blk, v_blk, k_pos, k_seg = carry
        scores = _masked_block_scores(
            q, k_blk, q_pos, k_pos, seg, k_seg, scale, causal
        )
        o, m, l = _online_update(o, m, l, scores, v_blk)
        k_blk, v_blk, k_pos, k_seg = jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, axis_name, perm),
            (k_blk, v_blk, k_pos, k_seg),
        )
        return (o, m, l, k_blk, v_blk, k_pos, k_seg), None

    (o, m, l, *_), _ = jax.lax.scan(
        body, (o, m, l, k, v, q_pos, seg), None, length=n
    )
    # Rows whose mask was empty everywhere (can't happen under causal
    # self-attention — a row always sees itself) would have l == 0; guard
    # anyway so non-causal edge cases stay finite.
    l = jnp.maximum(l, 1e-30)
    lse = m + jnp.log(l)  # (B, H, Tq)
    out = (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ring_attention_vjp(axis_name, causal, q, k, v, q_pos, seg):
    out, _ = _ring_forward(axis_name, causal, q, k, v, q_pos, seg)
    return out


def _ring_vjp_fwd(axis_name, causal, q, k, v, q_pos, seg):
    out, lse = _ring_forward(axis_name, causal, q, k, v, q_pos, seg)
    return out, (q, k, v, q_pos, seg, out, lse)


def _ring_vjp_bwd(axis_name, causal, res, do):
    """Second ring pass (flash-attention backward over rotating blocks).

    Fixed per device: q, do, o, lse, delta. Rotating: the K/V block, its
    positions/segments, and its dK/dV accumulators — after n hops each
    dK/dV block has collected the contribution of every q shard and is
    back on the device that owns that K/V shard. dQ accumulates locally.
    """
    q, k, v, q_pos, seg, out, lse = res
    n = jax.lax.psum(1, axis_name)
    scale = 1.0 / np.sqrt(q.shape[-1])
    do32 = do.astype(jnp.float32)
    out32 = out.astype(jnp.float32)
    # Contraction operand dtype: bf16 inputs keep the backward's four big
    # per-block matmuls on the MXU fast path (f32 accumulation; ds/p/delta
    # elementwise math stays f32). f32 inputs: all-f32, as before.
    cd = _contract_dtype(q)
    qc = q.astype(cd)
    doc = do.astype(cd)
    # delta_i = rowsum(dO * O): (B, Tq, H) -> (B, H, Tq)
    delta = (do32 * out32).sum(axis=-1).transpose(0, 2, 1)
    dq = jnp.zeros_like(q, dtype=jnp.float32)
    dk = jnp.zeros_like(k, dtype=jnp.float32)
    dv = jnp.zeros_like(v, dtype=jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, _):
        dq, k_blk, v_blk, k_pos, k_seg, dk_blk, dv_blk = carry
        scores = _masked_block_scores(
            q, k_blk, q_pos, k_pos, seg, k_seg, scale, causal
        )
        # p = softmax prob against the GLOBAL normalizer; explicit zero on
        # masked entries (a fully-masked row has lse ~ _NEG_INF, where
        # exp(scores - lse) would bogusly be 1).
        p = jnp.where(
            scores <= _NEG_INF * 0.5,
            0.0,
            jnp.exp(scores - lse[..., None]),
        )
        dv_blk = dv_blk + jnp.einsum(
            "bhqk,bqhd->bkhd", p.astype(cd), doc,
            preferred_element_type=jnp.float32,
        )
        dp = jnp.einsum(
            "bqhd,bkhd->bhqk", doc, v_blk.astype(cd),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[..., None]) * jnp.float32(scale)
        dq = dq + jnp.einsum(
            "bhqk,bkhd->bqhd", ds.astype(cd), k_blk.astype(cd),
            preferred_element_type=jnp.float32,
        )
        dk_blk = dk_blk + jnp.einsum(
            "bhqk,bqhd->bkhd", ds.astype(cd), qc,
            preferred_element_type=jnp.float32,
        )
        k_blk, v_blk, k_pos, k_seg, dk_blk, dv_blk = jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, axis_name, perm),
            (k_blk, v_blk, k_pos, k_seg, dk_blk, dv_blk),
        )
        return (dq, k_blk, v_blk, k_pos, k_seg, dk_blk, dv_blk), None

    (dq, _, _, _, _, dk, dv), _ = jax.lax.scan(
        body, (dq, k, v, q_pos, seg, dk, dv), None, length=n
    )
    zero_pos = np.zeros(q_pos.shape, dtype=jax.dtypes.float0)
    zero_seg = np.zeros(seg.shape, dtype=jax.dtypes.float0)
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        zero_pos,
        zero_seg,
    )


_ring_attention_vjp.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    seg: jax.Array,
    axis_name: str = SEQ_AXIS,
    causal: bool = True,
) -> jax.Array:
    """Exact attention via all-to-all head re-sharding (DeepSpeed-Ulysses
    scheme). Same contract as :func:`ring_attention`; requires H % n == 0."""
    n = jax.lax.psum(1, axis_name)
    B, Tl, H, D = q.shape
    scale = 1.0 / np.sqrt(D)

    def to_heads(x):
        # (B, Tl, H, D) seq-sharded -> (B, n*Tl, H/n, D) head-sharded: tiled
        # all_to_all splits the head axis into n chunks (chunk j to device j)
        # and concatenates received sequence blocks in device order, i.e.
        # global sequence order.
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    # positions/segments: gather the full sequence (small: B x T ints).
    pos_full = _all_gather_seq(q_pos, axis_name)
    seg_full = _all_gather_seq(seg, axis_name)

    scores = _masked_block_scores(
        qh, kh, pos_full, pos_full, seg_full, seg_full, scale, causal
    )
    p = jax.nn.softmax(scores, axis=-1)
    oh = _pv_dot(p, vh, _contract_dtype(vh)).astype(qh.dtype)

    # back: (B, n*Tl, H/n, D) -> (B, Tl, H, D), the exact inverse exchange.
    return jax.lax.all_to_all(
        oh, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def _all_gather_seq(x: jax.Array, axis_name: str) -> jax.Array:
    """(B, Tl) -> (B, T) concatenated in ring order."""
    g = jax.lax.all_gather(x, axis_name, axis=1)  # (B, n, Tl)
    return g.reshape(x.shape[0], -1)


@jax.named_scope("attn_full")  # read back by utils.platform.program_paths
def full_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    seg: jax.Array,
    axis_name: str | None = None,
    causal: bool = True,
    sm_scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Single-device reference implementation (same contract, no sharding).
    This is also the implementation the transformer uses when no seq mesh is
    in scope. ``sm_scale``: the softmax scale, ``1/sqrt(head_dim)`` if None.
    ``window``: a sliding window of that many keys, the query's own included
    (``q_pos - k_pos < window``); None = the whole episode so far."""
    scale = 1.0 / np.sqrt(q.shape[-1]) if sm_scale is None else sm_scale
    scores = _masked_block_scores(q, k, q_pos, q_pos, seg, seg, scale, causal, window)
    p = jax.nn.softmax(scores, axis=-1)
    # Output in q.dtype, matching ring/blockwise (which cast their f32
    # accumulators back); for f32 inputs this is exactly the old behavior.
    return _pv_dot(p, v, _contract_dtype(v)).astype(q.dtype)


# ------------------------------------------------------- blockwise (1 chip)
# Default tile: (B, H, 512, 512) f32 score transients stay in the few-MB
# range for typical model widths while each matmul is still MXU-sized.
BLOCKWISE_BLOCK = 512


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    seg: jax.Array,
    axis_name: str | None = None,
    causal: bool = True,
    block: int = BLOCKWISE_BLOCK,
) -> jax.Array:
    """Exact single-device attention that never materializes the (T, T)
    score matrix — the memory-efficient / flash-attention scheme, as a
    ``lax.scan`` over (Q-block, K-block) tiles with the same online-softmax
    accumulator the ring uses. Memory is O(T·D + block²) instead of O(T²),
    which is what caps ``full_attention``'s long-context batch size (at
    T=2048, B=32, H=8 the materialized scores alone are 4 GB).

    Same contract as :func:`full_attention` (full arrays, no sharding); the
    custom VJP recomputes block scores from the saved per-row logsumexp, so
    backward residuals are O(T) (q, k, v, out, lse), matching the ring.
    ``T % block`` need not be 0: the sequence is padded up to a whole number
    of near-``block`` tiles with segment-id -1 rows (matching no real
    segment, so they are fully masked out), and the padding is sliced off the
    output — padding/slicing sit OUTSIDE the custom VJP, so autodiff handles
    their cotangents exactly."""
    T = q.shape[1]
    nb = max(1, -(-T // block))  # ceil
    blk = -(-T // nb)  # ceil: nb tiles of blk >= T rows
    pad = nb * blk - T
    if pad:
        pad3 = ((0, 0), (0, pad), (0, 0), (0, 0))
        q_p = jnp.pad(q, pad3)
        k_p = jnp.pad(k, pad3)
        v_p = jnp.pad(v, pad3)
        pos_p = jnp.pad(q_pos, ((0, 0), (0, pad)))
        seg_p = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-1)
        out = _blockwise_vjp(bool(causal), int(blk), q_p, k_p, v_p, pos_p, seg_p)
        return out[:, :T]
    return _blockwise_vjp(bool(causal), int(blk), q, k, v, q_pos, seg)


def _split_blocks(x, nb):
    """(B, T, ...) -> (nb, B, T/nb, ...) scan-major blocks."""
    B, T = x.shape[0], x.shape[1]
    return jnp.moveaxis(x.reshape(B, nb, T // nb, *x.shape[2:]), 1, 0)


def _merge_blocks(xb):
    """(nb, B, blk, ...) -> (B, nb*blk, ...)."""
    nb, B, blk = xb.shape[0], xb.shape[1], xb.shape[2]
    return jnp.moveaxis(xb, 0, 1).reshape(B, nb * blk, *xb.shape[3:])


def _blockwise_forward(causal, block, q, k, v, q_pos, seg):
    B, T, H, D = q.shape
    nb = T // block
    scale = 1.0 / np.sqrt(D)
    kb = (_split_blocks(k, nb), _split_blocks(v, nb),
          _split_blocks(q_pos, nb), _split_blocks(seg, nb))

    def q_body(_, xs):
        q_blk, qpos, qseg = xs

        def k_body(carry, ks):
            k_blk, v_blk, kpos, kseg = ks
            scores = _masked_block_scores(
                q_blk, k_blk, qpos, kpos, qseg, kseg, scale, causal
            )
            return _online_update(*carry, scores, v_blk), None

        o = jnp.zeros((B, block, H, D), jnp.float32)
        m = jnp.full((B, H, block), _NEG_INF, jnp.float32)
        l = jnp.zeros((B, H, block), jnp.float32)
        (o, m, l), _ = jax.lax.scan(k_body, (o, m, l), kb)
        l = jnp.maximum(l, 1e-30)
        lse = m + jnp.log(l)  # (B, H, blk)
        out_blk = (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)
        return None, (out_blk, lse)

    _, (out_b, lse_b) = jax.lax.scan(
        q_body, None,
        (_split_blocks(q, nb), _split_blocks(q_pos, nb), _split_blocks(seg, nb)),
    )
    return _merge_blocks(out_b), lse_b  # out (B,T,H,D); lse (nb,B,H,blk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _blockwise_vjp(causal, block, q, k, v, q_pos, seg):
    out, _ = _blockwise_forward(causal, block, q, k, v, q_pos, seg)
    return out


def _blockwise_vjp_fwd(causal, block, q, k, v, q_pos, seg):
    out, lse_b = _blockwise_forward(causal, block, q, k, v, q_pos, seg)
    return out, (q, k, v, q_pos, seg, out, lse_b)


def _blockwise_vjp_bwd(causal, block, res, do):
    """Flash-attention backward over local tiles: outer scan over Q blocks
    carries full dK/dV accumulators (updated per K block by dynamic slice),
    emitting dQ blocks; probabilities are recomputed from the saved
    logsumexp, exactly as the ring backward does across devices."""
    q, k, v, q_pos, seg, out, lse_b = res
    B, T, H, D = q.shape
    nb = T // block
    scale = 1.0 / np.sqrt(D)
    do32 = do.astype(jnp.float32)
    # See the ring backward: contraction operands in the input dtype (bf16
    # fast path), f32 accumulation, f32 elementwise.
    cd = _contract_dtype(q)
    delta = (do32 * out.astype(jnp.float32)).sum(axis=-1)  # (B, T, H)
    kb = (
        _split_blocks(k, nb), _split_blocks(v, nb),
        _split_blocks(q_pos, nb), _split_blocks(seg, nb),
        jnp.arange(nb),
    )

    def q_body(carry, xs):
        dk, dv = carry
        q_blk, qpos, qseg, doc, lse, delta_blk = xs  # doc pre-cast to cd
        qc = q_blk.astype(cd)

        def k_body(inner, ks):
            dq_blk, dk, dv = inner
            k_blk, v_blk, kpos, kseg, kidx = ks
            scores = _masked_block_scores(
                q_blk, k_blk, qpos, kpos, qseg, kseg, scale, causal
            )
            p = jnp.where(
                scores <= _NEG_INF * 0.5, 0.0, jnp.exp(scores - lse[..., None])
            )
            dv_c = jnp.einsum(
                "bhqk,bqhd->bkhd", p.astype(cd), doc,
                preferred_element_type=jnp.float32,
            )
            dp = jnp.einsum(
                "bqhd,bkhd->bhqk", doc, v_blk.astype(cd),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta_blk[..., None]) * jnp.float32(scale)
            dq_blk = dq_blk + jnp.einsum(
                "bhqk,bkhd->bqhd", ds.astype(cd), k_blk.astype(cd),
                preferred_element_type=jnp.float32,
            )
            dk_c = jnp.einsum(
                "bhqk,bqhd->bkhd", ds.astype(cd), qc,
                preferred_element_type=jnp.float32,
            )
            start = kidx * block
            dk = jax.lax.dynamic_update_slice_in_dim(
                dk, jax.lax.dynamic_slice_in_dim(dk, start, block, 1) + dk_c,
                start, axis=1,
            )
            dv = jax.lax.dynamic_update_slice_in_dim(
                dv, jax.lax.dynamic_slice_in_dim(dv, start, block, 1) + dv_c,
                start, axis=1,
            )
            return (dq_blk, dk, dv), None

        dq_blk = jnp.zeros((B, block, H, D), jnp.float32)
        (dq_blk, dk, dv), _ = jax.lax.scan(k_body, (dq_blk, dk, dv), kb)
        return (dk, dv), dq_blk

    do_b = _split_blocks(do.astype(cd), nb)
    (dk, dv), dq_b = jax.lax.scan(
        q_body,
        (jnp.zeros_like(k, dtype=jnp.float32), jnp.zeros_like(v, dtype=jnp.float32)),
        (
            _split_blocks(q, nb), _split_blocks(q_pos, nb),
            _split_blocks(seg, nb), do_b, lse_b,
            # (nb, B, blk, H) -> (nb, B, H, blk) to match ds's row axis
            _split_blocks(delta, nb).transpose(0, 1, 3, 2),
        ),
    )
    zero_pos = np.zeros(q_pos.shape, dtype=jax.dtypes.float0)
    zero_seg = np.zeros(seg.shape, dtype=jax.dtypes.float0)
    return (
        _merge_blocks(dq_b).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        zero_pos,
        zero_seg,
    )


_blockwise_vjp.defvjp(_blockwise_vjp_fwd, _blockwise_vjp_bwd)


def flash_attention_tpu(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    seg: jax.Array,
    axis_name: str | None = None,
    causal: bool = True,
    sm_scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Single-device fused attention via the splash kernel that ships with
    JAX (``jax.experimental.pallas.ops.tpu.splash_attention``: Mosaic forward
    and single-pass backward under a custom VJP; see
    :func:`_splash_mha`). Same contract as :func:`full_attention`, and
    ``k``, ``v`` may carry fewer heads than ``q`` (grouped-query attention:
    ``H % Hkv == 0``, unrepeated), and ``v``'s head size may differ from q's and
    k's (latent attention at 192 : 128): the kernels then run on zero-padded
    heads of one size, 256 there (:func:`full_attention` takes the two sizes
    as they are).

    Masking equivalence: the kernel masks causally by global index plus
    same-segment — identical to our ``q_pos >= k_pos`` + same-segment mask
    because positions are segment-relative and monotone within a segment, and
    the segment mask kills every cross-segment pair anyway
    (``tests/test_sequence_parallel.py::TestFlashImpl`` runs this kernel in
    interpret mode against :func:`full_attention`, forward and gradients).
    ``window`` (a sliding window of that many keys, the query's own included)
    is masked by index too, ``q - k < window``, against ``q_pos - k_pos <
    window`` in :func:`full_attention`: the same pairs wherever positions step
    by one inside a segment, as every caller's do.

    Off-TPU (CPU tests, the virtual mesh), or at a length the kernel cannot
    tile (``T % 128``), this falls back to :func:`full_attention` —
    bit-compatible masking, different arithmetic order. The program's devices
    decide the placement, as for the LSTM kernel (``models/cells.py``): no
    registered data mesh means a plain single-device jit and a bare kernel
    call; under ``make_parallel_train_step``'s mesh the Mosaic call cannot be
    auto-partitioned by GSPMD, so it runs as a ``shard_map`` island over the
    ``"data"`` axis (including the 1-device mesh, so one chip exercises the
    island four chips use). Which path a program took is readable from its
    lowering (``attn_flash_pallas`` / ``attn_full`` named scopes). The
    sharded LONG-CONTEXT (seq-axis) path remains ``ring``/``ulysses``.
    """
    from tpu_rl.models import cells

    mesh = cells._DATA_MESH
    platform, n_data = cells._program_devices()
    # a multi-device program whose batch does not tile the mesh (init trace):
    # a bare Mosaic custom call has no GSPMD partitioning rule
    tiles = q.shape[0] % n_data == 0
    bs = _splash_block_sizes(q.shape[1]) if tiles else None
    if platform != "tpu" or bs is None:
        # the partitionable jnp path takes equal head counts
        rep = q.shape[2] // k.shape[2]
        if rep > 1:
            k, v = (jnp.repeat(x, rep, axis=2) for x in (k, v))
        return full_attention(
            q, k, v, q_pos, seg, causal=causal, sm_scale=sm_scale, window=window
        )
    scale = float(1.0 / np.sqrt(q.shape[-1]) if sm_scale is None else sm_scale)
    d_v = v.shape[-1]
    padded = q.shape[-1] != d_v
    if padded:
        # the kernels (the library's forward, ops/pallas_attn_bwd.py) take one head size:
        # q, k and v are padded with zero features to the next lane multiple that holds
        # both — no score and no output changes, the padding's gradients are dropped
        width = -(-max(q.shape[-1], d_v) // 128) * 128
        q, k, v = (jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[-1]),)) for x in (q, k, v))

    @jax.named_scope("attn_flash_pallas")
    def kernel(q, k, v, seg):
        o = _splash_mha(
            q, k, v, seg, causal=causal, scale=scale, block_sizes=bs, window=window
        )
        return o[..., :d_v] if padded else o

    if mesh is None:
        return kernel(q, k, v, seg)
    from jax.sharding import PartitionSpec as P

    qs = P(DATA_AXIS, None, None, None)
    return jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(qs, qs, qs, P(DATA_AXIS, None)),
        out_specs=qs,
        # No collectives inside; pallas out_shapes carry no vma
        # annotations, so varying-axis checking must be off (same as
        # the cells.py LSTM island).
        check_vma=False,
    )(q, k, v, seg)


def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    seg: jax.Array,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Causal same-segment attention (:func:`flash_attention_tpu`'s contract,
    no window) that also hands back each query's logsumexp over the keys it
    kept, ``(B, H, T)`` float32 — for a caller whose softmax runs over further
    keys that are not steps of this window (``models/evabyte.py``: one
    normaliser over a block's exact keys and the summaries of earlier chunks)
    and merges the two parts by it. Both outputs are differentiable.

    On a TPU, at a length the splash kernel tiles, this is the walk over the
    rows with each row's block masks read from its segment ids
    (:func:`_splash_rows_skipping_seams`) at any grid size, because only the
    repo's own backward (``ops/pallas_attn_bwd.py``) takes the logsumexp's
    cotangent — it folds into ``di``; the library's custom VJP has no such
    input. Off-TPU, at a length the kernel cannot tile, under a registered
    data mesh (no ``shard_map`` island yet: nothing runs an EVA layer over a
    mesh) or where a head's dq does not fit the core's VMEM, the ``jnp`` form
    under ``attn_full`` (a (T, T) score matrix a head: test sizes).
    ``interpret`` runs the kernels' construction on the CPU."""
    from tpu_rl.models import cells
    from tpu_rl.ops import pallas_attn_bwd

    platform, _ = cells._program_devices()
    scale = float(1.0 / np.sqrt(q.shape[-1]) if sm_scale is None else sm_scale)
    bs = _splash_block_sizes(q.shape[1]) if cells._DATA_MESH is None else None
    fits = bs is not None and pallas_attn_bwd.fits(
        q.shape[1], q.shape[3], q.shape[2] // k.shape[2], bs.block_q_dkv, bs.block_kv_dkv,
        bs.block_kv_dkv_compute, q.dtype.itemsize)
    if not interpret and (platform != "tpu" or not fits):
        rep = q.shape[2] // k.shape[2]
        if rep > 1:
            k, v = (jnp.repeat(x, rep, axis=2) for x in (k, v))
        with jax.named_scope("attn_full"):
            scores = _masked_block_scores(q, k, q_pos, q_pos, seg, seg, scale, True)
            lse = jax.nn.logsumexp(scores, axis=-1)
            p = jnp.exp(scores - lse[..., None])
            return _pv_dot(p, v, _contract_dtype(v)).astype(q.dtype), lse

    with jax.named_scope("attn_flash_pallas"):
        return _splash_rows_skipping_seams(
            q, k, v, seg, causal=True, scale=scale, block_sizes=bs, interpret=interpret,
            window=None, lse=True)


def summary_attention_lse(q, ks, vs, seg, seg_k, reach, sm_scale, interpret=False):
    """Queries against keys that are not the window's steps — an EVA layer's
    chunk summaries — through the splash kernels: q (B, T, H, D); ks, vs
    (B, N, H, D) with segment ids ``seg_k`` (B, N) of their own (an id no query
    has marks an absent one); query ``t`` reads the keys of its segment
    ``seg`` (B, T) with index ``<= reach[b, t]`` (B, T) int, none where that is
    negative; ``reach[b, t] <= t`` (a summary is of steps before the query: the
    library's static causal structure on the rectangle stands). Returns the
    output normalised over the keys read (B, T, H, D) and their logsumexp
    (B, H, T) float32 — hugely negative where a query reads none, so that a
    merge by it gives this part no weight — or None where the kernels do not
    take the shapes (off-TPU, a length or a key count they cannot tile, a
    registered data mesh): the caller keeps its ``jnp`` form.

    The mask is the library's causal one on a (T, N) rectangle with each
    query's own index replaced by ``reach`` (the kernels take the queries'
    indices as an operand), every computed tile under the mask function; a
    tile no query of which reaches, or whose keys are all of earlier segments,
    is stepped over (forward) and left out of the backward's walk, which is
    ``ops/pallas_attn_bwd.py``'s with the keys' ids beside the queries' and
    the logsumexp's cotangent folded into ``di``. One call a row, as
    :func:`_splash_rows_skipping_seams`."""
    from tpu_rl.models import cells
    from tpu_rl.ops import pallas_attn_bwd

    T, N = q.shape[1], ks.shape[1]
    platform, n_data = cells._program_devices()
    bs = _splash_block_sizes(T) if q.shape[0] % n_data == 0 and N % 128 == 0 else None
    if bs is None or not (interpret or platform == "tpu") or cells._DATA_MESH is not None:
        return None
    edge = min(bs.block_kv, N)
    if N % edge:
        return None
    compute = min(bs.block_kv_compute, edge)
    bs = dataclasses.replace(
        bs, block_kv=edge, block_kv_compute=compute, block_kv_dkv=edge,
        block_kv_dkv_compute=compute)
    if not pallas_attn_bwd.fits(
            T, q.shape[3], 1, bs.block_q_dkv, edge, compute, q.dtype.itemsize):
        return None
    return _summary_rows(
        q, ks, vs, seg, seg_k, reach, scale=float(sm_scale), block_sizes=bs, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "block_sizes", "interpret"))
@jax.named_scope("attn_flash_pallas")
def _summary_rows(q, ks, vs, seg, seg_k, reach, *, scale, block_sizes, interpret):
    T, N = q.shape[1], ks.shape[1]
    splash = _splash_kernel(
        T, q.shape[2], causal=True, window=None, block_sizes=block_sizes, interpret=interpret,
        keys=N)
    bq, bkv = block_sizes.block_q, block_sizes.block_kv
    seg, seg_k, reach = (x.astype(jnp.int32) for x in (seg, seg_k, reach))
    # tile (i, j) holds no kept pair if no query of block i reaches key block j's first
    # key, or block j's keys are all of segments before block i's queries'
    far = reach.reshape(-1, T // bq, bq).max(axis=2)[:, :, None] < (jnp.arange(N // bkv) * bkv)
    old = (seg_k.reshape(-1, N // bkv, bkv).max(axis=2)[:, None, :]
           < seg.reshape(-1, T // bq, bq).min(axis=2)[:, :, None])
    empty = far | old
    # A query block with no tile left computes its first one all the same, every pair of it
    # masked: the library's forward divides by the row's sum, which only a computed tile
    # makes nonzero (the square band's diagonal is never empty; a rectangle's first query
    # blocks — the window's opening block — reach nothing).
    first = jnp.arange(N // bkv) == 0
    empty &= ~(empty.all(axis=2, keepdims=True) & first)
    rows = [
        _reach_row(splash, q[b] * scale, ks[b], vs[b], seg[b], empty[b], seg_k[b], reach[b])
        for b in range(q.shape[0])
    ]
    return jnp.stack([o for o, _ in rows]), jnp.stack([l for _, l in rows])


ATTENTION_IMPLS = {
    "full": full_attention,
    "blockwise": blockwise_attention,
    "flash": flash_attention_tpu,
    "ring": ring_attention,
    "ulysses": ulysses_attention,
}


def segment_ids_from_firsts(firsts: jax.Array) -> jax.Array:
    """Global segment ids from episode-first flags: (B, T, 1) -> (B, T).
    Computed on the FULL sequence before sharding so seams are correct
    across shard boundaries."""
    return jnp.cumsum(firsts[..., 0].astype(jnp.int32), axis=1)
