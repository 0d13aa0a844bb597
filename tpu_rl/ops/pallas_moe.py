"""The row-add of the expert walk as a Pallas TPU kernel.

A trip of ``ops/moe.py``'s walk ends by adding its ``(C, d)`` rows into the
``(N, d)`` float32 result at the rows' tokens (the forward's combine, the
backward's gradient of the tokens). ``row_add`` does that without moving the
result: it stays in HBM (``memory_space=ANY``, aliased to the output), and a
grid step brings the rows it touches into VMEM one DMA a row, many in flight,
adds the trip's tile to them, and sends them back.

**The result is kept as lane rows**, ``(N d / 128, 128)``: row-major memory
under the chip's ``(8, 128)`` tiling, so a token's ``d`` floats are one
contiguous run and one DMA. (In an ``(N, d)`` array tiled ``(8, 128)`` a
token's row is ``d / 128`` pieces of 512 bytes, and Mosaic refuses a slice of
one row of it.) Inside the kernel the same memory is viewed as ``(N, 1, d)``
and a row is a slice of the leading axis; the trip's rows arrive as lane rows
too, so the add is one full-width vector add over the tile. The interpreter
has no such view of a reference: there the three arrays are reshaped before
the call instead.

**The one hazard, and why the grid is the grouped matmul's.** The trip's rows
are ordered by held expert (group), and inside a group by token (the sort is
stable over token-major assignments; a token chooses an expert at most once).
So inside one group the tokens are distinct, and a step that takes rows of
*one* group reads and writes rows of the result that never alias. A token
held by two experts has a row in two groups, hence in two steps — and a step
waits for every one of its writes before it ends, so the next step reads what
this one wrote. The grid is therefore megablox's group metadata at this
kernel's tile height: a step is (group, row tile), a tile that holds a group
edge is visited once a group, and the grid ends with the last group's last
row — rows past the groups' total are never added anywhere, whatever they
hold, and their tokens never looked at.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

_F32 = jnp.float32
LANES = 128
# Rows a grid step takes, and with them the row DMAs in flight: three float32
# tiles (the result's, and the trip's twice: the pipeline's two buffers) stay
# under VMEM_BYTES, the scoped VMEM of the smallest TPU generation less what
# the compiler keeps for itself. On a v5e tiles of 64, 128, 256 and 512 rows
# took the same time within 3% (PERF.md section 6, PR 33): the step waits on
# its loops of DMA descriptors, not on their number in flight.
MAX_TILE = 256
VMEM_BYTES = 12 << 20


def _tile_bytes(tile: int, d: int) -> int:
    return 3 * 4 * tile * d


def tile_rows(c: int, d: int) -> int | None:
    """The tile height for a trip of ``c`` rows of width ``d``: the largest
    power of two up to ``MAX_TILE`` that divides ``c`` and whose buffers fit,
    or None where the kernel does not take the shape (``d`` no lane multiple,
    ``c`` no multiple of 8)."""
    if d % LANES:
        return None
    for tile in (2**e for e in range(MAX_TILE.bit_length() - 1, 2, -1)):
        if c % tile == 0 and _tile_bytes(tile, d) <= VMEM_BYTES:
            return tile
    return None


def _kernel(offsets, group_ids, tile_ids, tok, rows, _, y, buf, sems, *, tile: int, d: int,
            view: bool):
    step = pl.program_id(0)
    group, base = group_ids[step], tile_ids[step] * tile
    lo = jnp.maximum(offsets[group], base)
    hi = jnp.minimum(offsets[group + 1], base + tile)
    y_rows, buf_rows = (y.reshape(-1, 1, d), buf.reshape(tile, 1, d)) if view else (y, buf)

    def fetch(i):
        return pltpu.make_async_copy(
            y_rows.at[pl.ds(tok[i], 1)], buf_rows.at[pl.ds(i - base, 1)], sems.at[0])

    def store(i):
        return pltpu.make_async_copy(
            buf_rows.at[pl.ds(i - base, 1)], y_rows.at[pl.ds(tok[i], 1)], sems.at[1])

    def each(f):
        jax.lax.fori_loop(lo, hi, lambda i, _: f(i), None)

    each(lambda i: fetch(i).start())
    each(lambda i: fetch(i).wait())
    # the whole tile: what lies outside [lo, hi) is added to rows that were
    # not fetched and are not stored
    buf[...] += rows[...].astype(_F32)
    each(lambda i: store(i).start())
    each(lambda i: store(i).wait())


# Jitted: the layers of a model, their forward and backward passes (and every
# later trace of the train step in the process) share one trace and one
# lowering of the group metadata and the kernel, as ``ops/pallas_ssd.py``'s
# passes do. Un-jitted, the twelve calls of smallthinker-21b-a3b's update
# program added ~1.4 s to each of its traces and 10-12 s to the cell's 86 s of
# set-up in a warm compile cache (PERF.md section 6, PR 33).
@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def row_add(y, rows, tok, part, interpret: bool = False, tile: int | None = None):
    """``y`` (N d / 128, 128) float32, the lane rows of an ``(N, d)`` result,
    with row ``i`` of ``rows`` (C, d) added into the result's row ``tok[i]``,
    for ``i`` under ``sum(part)``. ``tok`` (C,) int32; ``part`` (G,) int32: the
    groups' rows among the ``C``, in order; inside a group the tokens are
    distinct. ``y`` is updated in place."""
    (c, d), groups = rows.shape, part.shape[0]
    tile = tile or tile_rows(c, d)
    assert tile is not None and y.dtype == _F32 and y.shape[1] == LANES, (y.shape, rows.shape)
    lanes = d // LANES
    (offsets, group_ids, tile_ids), steps = make_group_metadata(
        group_sizes=part, m=c, tm=tile, start_group=0, num_nonzero_groups=groups,
        visit_empty_groups=False)
    if interpret:
        shape, block = (-1, 1, d), (tile, 1, d)
    else:
        shape, block = (-1, LANES), (tile * lanes, LANES)
    result = y.reshape(shape)

    def at(step, _offsets, _group_ids, tile_ids, _tok):
        return (tile_ids[step],) + (0,) * (len(block) - 1)

    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, d=d, view=not interpret),
        out_shape=jax.ShapeDtypeStruct(result.shape, result.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[pl.BlockSpec(block, at), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM(block, _F32), pltpu.SemaphoreType.DMA((2,))],
        ),
        input_output_aliases={5: 0},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_tile_bytes(tile, d) + (4 << 20)),
        name="moe_row_add",
    )(offsets, group_ids, tile_ids, tok, rows.reshape(shape), result).reshape(y.shape)
