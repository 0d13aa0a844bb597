"""A sparse-expert feed-forward block as one expert-parallel rank computes it.

The block is told which routed experts it holds (``first``: the global id of
the first; how many: the leading axis of its weights). It routes every token
over *all* the model's experts, as the published router does, and computes

    y = shared(u) + sum over the token's chosen experts that are held here of
        w_e * expert_e(u)

with one of three published expert forms (``EXPERT_FORMS``, chosen by name),
each without bias:

    ``relu2``   W_out relu(W_in u)^2                     two leaves an expert
    ``reglu``   W_out (relu(W_gate u) * W_in u)          three (gated)
    ``swiglu``  W_out (silu(W_gate u) * W_in u)          three (gated)

What the absent experts would add is left out: in a deployment the other ranks
compute it and an exchange brings it home; here there is no exchange and no
code that stands in for one. The shared expert is the caller's (a family that
has none adds none).

Router (``route``, float32 throughout), one of two published scores over
``l = W_r r`` (``r``: what the router reads, the block's input or another
state the caller hands over):

    ``sigmoid``  ``s = sigmoid(l)``; the choice is the ``top_k`` largest of
                 ``s + b`` (``b``: a correction bias that only the choice
                 reads, so no gradient reaches it); ``w = scale * s_e / (sum
                 of the chosen s + 1e-20)``
    ``softmax``  the choice is the ``top_k`` largest of ``l``; ``w`` is the
                 softmax over the chosen logits (= the softmax over all,
                 renormalised over the chosen); no bias, no scale

The sigmoid router may be *group-limited* (``n_group``, ``topk_group``): the
experts lie in ``n_group`` groups of consecutive ids, a group's score is the
sum of its two largest ``s + b``, only the ``topk_group`` best groups' experts
can be chosen, and the weights are formed as above (``kept_groups``).

The discrete choice carries no gradient, the chosen scores do.

Dispatch (``routed_experts``), with static shapes and without dropping a token
whatever the imbalance: the ``tokens x top_k`` assignments are sorted by held
expert (those on absent experts last), and the block **walks the held part of
that order in chunks of ``C`` rows, ``ceil(held rows / C)`` times** — a trip
count the device reads from the routing's own counts (a ``fori_loop`` with a
traced bound: no conditional, nothing compiled per count). A trip gathers its
``C`` assignments' token rows, runs one grouped matmul per projection over the
chunk's part of each group (a group's rows stay contiguous inside a chunk, and
a group may straddle an edge), and adds the weighted rows into the ``(N, d)``
float32 result at their tokens. Every buffer, gather, cast and activation is
``C`` rows tall; ``C`` (``chunk_rows``) is a ``ROW_TILE`` multiple near twice
the rows expected from the static shapes, so a rank that holds a sixteenth of
the experts takes one trip most updates and routing that puts every
assignment here ``N k / C``, and at most ``WALK_ROWS``: a rank that holds a
quarter of the experts walks a layer in a few trips. Rows past the
held total inside the last chunk are never computed and never read: the grouped matmul's grid ends with the last
held row, and the combine and the backward **select** live rows. The walk is
one ``custom_vjp`` (``_walk``) that keeps its inputs only: the backward is the
same walk — gather the tokens' rows and the result's cotangent, recompute the
hidden rows, the two transposed products per projection, add into the tokens'
gradient — with the weights' gradients accumulated across trips in float32
(inside ``tgmm`` where the kernel runs). The expert form is the walk's static
parameter (``EXPERT_FORMS``: the hidden rows from the first products, and their
cotangents); everything else is shared. ``route_stats`` counts the trips
(``chunks``).

The add into the tokens (``add_rows``), in the forward's combine and the
backward's gradient of the tokens: where the grouped matmul's gate takes its
kernel and ``d`` is a lane multiple, ``pallas_moe.row_add`` (scope
``moe_row_add_pallas``) — the result stays in HBM as lane rows, a grid step
moves one group's rows of a tile by DMA, rows past the held total are skipped
by count —; elsewhere XLA's ``.at[].add(mode="drop")``, the kernel's oracle.

The grouped matmul (``grouped_matmul``): on a TPU, at widths whose tiles the
kernel takes, ``pallas.ops.tpu.megablox`` (scope ``moe_gmm_pallas``), with the
transposed product for the weights' gradient; elsewhere ``jax.lax.ragged_dot``,
which is also the kernel's oracle. The gate is ``models/cells.py``'s
(``set_pallas_mode``: ``"interpret"`` runs the kernel in the interpreter,
``"off"`` forces ``ragged_dot``).

Scopes, for the device trace: ``moe_route``, ``moe_dispatch``, ``moe_experts``
(``moe_gmm_pallas`` inside it when the kernel was taken), ``moe_combine``
(``moe_row_add_pallas`` inside it and inside the backward's ``moe_dispatch``
when the row-add kernel was taken); the caller wraps the block in ``moe`` and its shared expert, where it has
one, in ``moe_shared``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _megablox_gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as _megablox_tgmm

from tpu_rl.ops import pallas_moe

# Rows a grid step of the grouped matmul takes. A held expert sees a few
# hundred rows an update at the cell's batch: a taller tile would be mostly
# another group's rows, masked.
ROW_TILE = 256
# The most rows a trip takes. A trip's buffers (gathered rows, hidden rows,
# the float32 addend) are this tall, and the last trip of a walk is computed
# whole however few of its rows are live. On a TPU v5e one layer's walk over
# 49,248 held rows of 32,768 tokens at width 2,560 (gated experts of width
# 768, forward + backward) took 62.7 / 58.2 / 57.7 / 56.9 / 60.4 ms in trips of
# 4,096 / 8,192 / 12,288 / 16,384 / 24,576 rows, and the update program built
# on it 8.84 / 8.84 / 8.77 / 8.77 GB of temporaries at the first four
# (PERF.md section 6, PR 33).
WALK_ROWS = 16_384


# ------------------------------------------------------------------ the router
def kept_groups(biased, n_group: int, topk_group: int):
    """The group stage of the group-limited router (DeepSeek-V3,
    arXiv:2412.19437, as the Ling family runs it): ``biased`` (N, E) the
    scores the choice reads, in ``n_group`` groups of consecutive experts; a
    group's score is the sum of its two largest; the ``topk_group`` best groups
    are kept. Returns their ids (N, topk_group) int32."""
    groups = biased.reshape(biased.shape[0], n_group, -1)
    best_two, _ = jax.lax.top_k(groups, min(2, groups.shape[-1]))
    _, kept = jax.lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
    return kept.astype(jnp.int32)


@jax.named_scope("moe_route")
def route(u, kernel, bias, top_k: int, scale: float, score: str = "sigmoid",
          n_group: int = 1, topk_group: int = 1, with_groups: bool = False):
    """``u`` (N, d); ``kernel`` (d, E); ``bias`` (E,). Returns the chosen
    experts (N, top_k) int32 and their weights (N, top_k) float32.
    ``score="softmax"`` reads neither ``bias`` nor ``scale``. With ``n_group``
    > 1 (sigmoid only) the choice is group-limited: of the experts' ``n_group``
    groups the ``topk_group`` best are kept (``kept_groups``, on ``s + b``) and
    the ``top_k`` largest ``s + b`` are taken among their experts alone; the
    weights are the chosen experts' unbiased scores as before. ``with_groups``
    hands the kept groups' ids (N, topk_group) back as a third value. One
    group (the default) is the plain choice and lowers to the program it
    always did."""
    logits = jnp.dot(
        u.astype(jnp.float32), kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if score == "softmax":
        assert n_group == 1, "the softmax router has no group stage"
        _, choice = jax.lax.top_k(jax.lax.stop_gradient(logits), top_k)
        chosen = jnp.take_along_axis(logits, choice, axis=-1)
        out = choice.astype(jnp.int32), jax.nn.softmax(chosen, axis=-1)
        return (*out, None) if with_groups else out
    s = jax.nn.sigmoid(logits)
    biased = jax.lax.stop_gradient(s + bias)
    kept = None
    if n_group > 1:
        kept = kept_groups(biased, n_group, topk_group)
        group_of = jnp.arange(biased.shape[-1], dtype=jnp.int32) // (biased.shape[-1] // n_group)
        survives = jnp.any(group_of[None, :, None] == kept[:, None, :], axis=-1)
        biased = jnp.where(survives, biased, -jnp.inf)
    _, choice = jax.lax.top_k(biased, top_k)
    chosen = jnp.take_along_axis(s, choice, axis=-1)
    chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    out = choice.astype(jnp.int32), scale * chosen
    return (*out, kept) if with_groups else out


def chunk_rows(n: int, k: int, held: int, n_experts: int) -> int:
    """Rows a trip of ``routed_experts``'s walk takes (``C``), from static
    shapes alone: the ``ROW_TILE`` multiple next above twice the rows a fair
    router sends here (``n k held / n_experts``; never more than all ``n k``
    assignments) — one trip most updates —, and at most ``WALK_ROWS``."""
    tiles = -(-2 * n * k * held // (n_experts * ROW_TILE))
    return min(ROW_TILE * max(1, min(tiles, -(-n * k // ROW_TILE))), WALK_ROWS)


def route_stats(choice, first: int, held: int, chunk: int, kept=None, group_size: int = 0) -> dict:
    """Counters of one block's routing, as float32 scalars (in-jit, no
    gradient): rows computed, rows of the fullest held expert and of the mean
    one, the share of assignments on held experts, the share of tokens with
    none, and the trips the walk takes at ``chunk`` rows each. Under a
    group-limited router (``kept`` (N, topk_group): ``route``'s kept groups of
    ``group_size`` experts each) also ``group-hit-share``: the share of tokens
    whose kept groups hold a held expert — no other token can send a row here."""
    local = choice - first
    mine = (local >= 0) & (local < held)
    counts = jnp.sum(
        (local[..., None] == jnp.arange(held)) & mine[..., None], axis=(0, 1)
    ).astype(jnp.float32)
    rows = jnp.sum(counts)
    stats = {
        "rows": rows,
        "rows-max": jnp.max(counts),
        "rows-mean": rows / held,
        "held-share": rows / choice.size,
        "no-held-share": jnp.mean(1.0 - jnp.any(mine, axis=-1).astype(jnp.float32)),
        "chunks": jnp.ceil(rows / chunk),
    }
    if kept is not None:
        lo, hi = first // group_size, (first + held - 1) // group_size
        hit = jnp.any((kept >= lo) & (kept <= hi), axis=-1)
        stats["group-hit-share"] = jnp.mean(hit.astype(jnp.float32))
    return stats


# ------------------------------------------------------- the grouped products
def _gmm_tiles(m: int, k: int, n: int) -> tuple[int, int, int] | None:
    """(rows, contraction, columns) a grid step takes, or None where the
    kernel's tiles do not fit the widths: the contraction whole when it is no
    lane multiple (no masked remainder), else its largest lane-multiple
    divisor up to 1024; 512 columns, the last tile partial."""
    if m % ROW_TILE or k % 64 or n % 64:
        return None
    if k % 128:
        tk = k
    else:
        tk = max(t for t in range(128, min(k, 1024) + 1, 128) if k % t == 0)
    return ROW_TILE, tk, min(n, 512)


def _tiles(m: int, k: int, n: int) -> tuple[int, int, int]:
    """``_gmm_tiles``, or whole operands where the interpreter runs widths
    that do not tile."""
    return _gmm_tiles(m, k, n) or (ROW_TILE, k, n)


def _gmm_gate(m: int, k: int, n: int) -> tuple[bool, bool]:
    """(use the Pallas kernel, interpret): ``models/cells.py``'s gate applied
    to the grouped matmul. A program over more than one device keeps
    ``ragged_dot``: the kernel has no SPMD rule and the block no island."""
    from tpu_rl.models import cells

    mode = cells._PALLAS_MODE
    if mode == "off":
        return False, False
    if mode == "interpret":
        return m % ROW_TILE == 0, True
    platform, n_data = cells._program_devices()
    if platform != "tpu" or n_data != 1:
        return False, False
    return _gmm_tiles(m, k, n) is not None, False


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_pallas(lhs, rhs, sizes, interpret: bool):
    """``lhs`` (m, k) rows ordered by group; ``rhs`` (G, k, n); ``sizes`` (G,)
    int32. The kernel's grid ends with the last group's last row: **rows past
    the groups' total are never written** (whatever the buffer held), in the
    product and in the gradient of ``lhs`` alike — the caller selects."""
    tiles = _tiles(lhs.shape[0], lhs.shape[1], rhs.shape[2])
    return _megablox_gmm(lhs, rhs, sizes, lhs.dtype, tiles, interpret=interpret)


def _gmm_pallas_fwd(lhs, rhs, sizes, interpret):
    return _gmm_pallas(lhs, rhs, sizes, interpret), (lhs, rhs, sizes)


def _gmm_grads(lhs, rhs, sizes, g, interpret: bool, acc=None):
    """Both gradients of ``_gmm_pallas(lhs, rhs, sizes)`` for the cotangent
    ``g``: ``lhs``'s (rows past the groups' total unwritten), and ``rhs``'s —
    added to ``acc`` (G, k, n) float32 inside the kernel where one is given."""
    m, k = lhs.shape
    n = rhs.shape[2]
    g = g.astype(lhs.dtype)
    d_lhs = _megablox_gmm(
        g, rhs, sizes, lhs.dtype, _tiles(m, n, k), transpose_rhs=True, interpret=interpret,
    )
    tm, _, tn = _tiles(m, k, n)
    d_rhs = _megablox_tgmm(
        lhs.swapaxes(0, 1), g, sizes, rhs.dtype if acc is None else acc.dtype,
        (tm, min(k, 512), tn), existing_out=acc, interpret=interpret,
    )
    return d_lhs, d_rhs


def _gmm_pallas_bwd(interpret, residual, g):
    lhs, rhs, sizes = residual
    return (*_gmm_grads(lhs, rhs, sizes, g, interpret), None)


_gmm_pallas.defvjp(_gmm_pallas_fwd, _gmm_pallas_bwd)


def grouped_matmul(lhs, rhs, sizes, kernel: tuple[bool, bool] | None = None):
    """``lhs[rows of group g] @ rhs[g]`` for every group. ``sizes`` (G,) int32.
    Rows past the groups' total: zero from ``ragged_dot``, **unwritten** by the
    Pallas kernel, which never visits them — in the product and in ``lhs``'s
    gradient alike; a caller whose groups do not fill the buffer selects what
    it reads. ``kernel``: ``(use the Pallas kernel, interpret)`` where the
    caller and not the gate chooses (tests, ``chip_smoke.py``)."""
    m, k = lhs.shape
    use, interpret = kernel or _gmm_gate(m, k, rhs.shape[2])
    if not use:
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    with jax.named_scope("moe_gmm_pallas"):  # the backward's kernels carry it too
        return _gmm_pallas(lhs, rhs, sizes, interpret)


def grouped_grads(lhs, rhs, sizes, g, acc, kernel: tuple[bool, bool] | None = None):
    """What ``grouped_matmul(lhs, rhs, sizes)``'s transpose gives for ``g``:
    ``lhs``'s gradient (rows past the groups' total as the product leaves
    them) and ``acc`` (G, k, n) float32 plus ``rhs``'s."""
    m, k = lhs.shape
    use, interpret = kernel or _gmm_gate(m, k, rhs.shape[2])
    if not use:
        _, transpose = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = transpose(g.astype(lhs.dtype))
        return d_lhs, acc + d_rhs
    with jax.named_scope("moe_gmm_pallas"):
        return _gmm_grads(lhs, rhs, sizes, g, interpret, acc)


# ------------------------------------------------------------------ the row-add
def _row_add_gate(chunk: int, d: int, kernel: tuple[bool, bool] | None) -> tuple[bool, bool]:
    """(use the Pallas row-add, interpret): the grouped matmul's gate (or the
    caller's choice for it) at a width the row-add takes."""
    use, interpret = kernel or _gmm_gate(chunk, d, d)
    return use and pallas_moe.tile_rows(chunk, d) is not None, interpret


def _result(shape, adder: tuple[bool, bool]):
    """Zeros the trips add into: ``(N, d)`` float32, or its lane rows
    ``(N d / 128, 128)`` where the kernel adds (``pallas_moe.row_add``: a
    token's row contiguous in HBM)."""
    n, d = shape
    lanes = pallas_moe.LANES
    return jnp.zeros((n * d // lanes, lanes) if adder[0] else (n, d), jnp.float32)


def add_rows(y, add, tok, part, live, adder: tuple[bool, bool]):
    """``y`` with the live rows of ``add`` (C, d) float32 added at their tokens
    ``tok``; ``part`` the groups' rows among the ``C`` (their sum: the live
    count). XLA's scatter-add (a dead row's index lies past the end and is
    dropped), or the kernel under the scope ``moe_row_add_pallas``."""
    use, interpret = adder
    if not use:
        return y.at[jnp.where(live, tok, y.shape[0])].add(add, mode="drop")
    with jax.named_scope("moe_row_add_pallas"):
        return pallas_moe.row_add(y, add, tok, part, interpret)


# ------------------------------------------------------------------- the walk
# An expert form: its hidden rows from the products of its first projections
# (one a leaf of ``w_in``), and those products' cotangents from the hidden
# rows' cotangent ``t``.
def _relu2(pre):
    return jnp.square(jax.nn.relu(pre[0]))


def _relu2_bwd(pre, t):
    return (t * 2.0 * jax.nn.relu(pre[0]),)


def _reglu(pre):
    gate, up = pre
    return jax.nn.relu(gate) * up


def _reglu_bwd(pre, t):
    gate, up = pre
    return jnp.where(gate > 0, t * up, 0.0), t * jax.nn.relu(gate)


def _swiglu(pre):
    gate, up = pre
    return jax.nn.silu(gate) * up


def _swiglu_bwd(pre, t):
    gate, up = pre
    s = jax.nn.sigmoid(gate)
    return t * up * s * (1.0 + gate * (1.0 - s)), t * gate * s


EXPERT_FORMS = {
    "relu2": (_relu2, _relu2_bwd),
    "reglu": (_reglu, _reglu_bwd),
    "swiglu": (_swiglu, _swiglu_bwd),
}


def _first_projections(form: str, w_in, w_gate) -> tuple:
    """The form's first projections in the order its functions read them."""
    assert form in EXPERT_FORMS, f"expert form {form!r}: one of {sorted(EXPERT_FORMS)}"
    assert (w_gate is None) == (form == "relu2"), f"{form} experts with w_gate {w_gate is not None}"
    return (w_in,) if w_gate is None else (w_gate, w_in)


@jax.named_scope("moe_dispatch")
def _trip(c, order, sizes, chunk: int):
    """Trip ``c`` of the walk over the sorted assignments: the assignments at
    sorted positions ``[c chunk, (c + 1) chunk)``, each group's rows among
    them, and which of the positions hold a held assignment."""
    lo = c * chunk
    ends = jnp.cumsum(sizes)
    part = jnp.clip(ends, lo, lo + chunk) - jnp.clip(ends - sizes, lo, lo + chunk)
    live = lo + jnp.arange(chunk, dtype=jnp.int32) < ends[-1]
    return jax.lax.dynamic_slice(order, (lo,), (chunk,)), part, live


def _trips(sizes, chunk: int):
    return (jnp.sum(sizes) + chunk - 1) // chunk


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _walk(u, weight, w_in, w_out, order, sizes, chunk: int, kernel, form: str):
    """``u`` (N, d), ``weight`` (N, k) float32, ``w_in`` the form's first
    projections (a tuple of (H, d, f) leaves) and ``w_out`` (H, f, d) in the
    operands' dtype; ``order`` the assignments (token ``a // k``, slot
    ``a % k``) sorted by held expert, padded to whole chunks; ``sizes`` (H,)
    the held experts' rows; ``form`` a key of ``EXPERT_FORMS``. (N, d) float32."""
    n, k = weight.shape
    x, flat = u.astype(w_out.dtype), weight.reshape(-1)
    act, _ = EXPERT_FORMS[form]
    adder = _row_add_gate(chunk, u.shape[1], kernel)

    def trip(c, y):
        at, part, live = _trip(c, order, sizes, chunk)
        with jax.named_scope("moe_dispatch"):
            tok = at // k
            rows = x[tok]
        with jax.named_scope("moe_experts"):
            hidden = act(tuple(grouped_matmul(rows, w, part, kernel) for w in w_in))
            out = grouped_matmul(hidden.astype(x.dtype), w_out, part, kernel)
        with jax.named_scope("moe_combine"):
            # select, not a product with a zero weight: a row past the held
            # total is unwritten and may hold anything
            add = jnp.where(live[:, None], flat[at][:, None] * out.astype(jnp.float32), 0.0)
            return add_rows(y, add, tok, part, live, adder)

    return jax.lax.fori_loop(
        0, _trips(sizes, chunk), trip, _result(u.shape, adder)).reshape(u.shape)


def _walk_fwd(u, weight, w_in, w_out, order, sizes, chunk, kernel, form):
    return _walk(u, weight, w_in, w_out, order, sizes, chunk, kernel, form), (
        u, weight, w_in, w_out, order, sizes)


def _walk_bwd(chunk, kernel, form, residual, dy):
    """The same walk: with the hidden rows ``h`` recomputed per trip from the
    first products ``x W_in`` and ``t = dy W_out^T`` at the chunk's tokens,
    ``d weight = <h, t>``, ``d W_out += (weight h)^T dy``, and per first
    projection ``d W_in += x^T p`` and ``d u += p W_in^T`` for ``p`` the
    form's cotangent of that product under ``weight t`` (``relu2``:
    ``weight t 2 relu(x W_in)``)."""
    u, weight, w_in, w_out, order, sizes = residual
    n, k = weight.shape
    x, g, flat = u.astype(w_out.dtype), dy.astype(w_out.dtype), weight.reshape(-1)
    act, act_bwd = EXPERT_FORMS[form]
    adder = _row_add_gate(chunk, u.shape[1], kernel)

    def trip(c, carry):
        d_x, d_flat, d_in, d_out = carry
        at, part, live = _trip(c, order, sizes, chunk)
        with jax.named_scope("moe_dispatch"):
            tok = at // k
            rows = x[tok]
        with jax.named_scope("moe_combine"):
            dy_rows, wt = g[tok], flat[at][:, None]
        with jax.named_scope("moe_experts"):
            pre = tuple(grouped_matmul(rows, w, part, kernel) for w in w_in)
            hidden = act(pre)
            t, d_out = grouped_grads(
                (wt * hidden).astype(x.dtype), w_out, part, dy_rows, d_out, kernel)
            t = t.astype(jnp.float32)
            d_rows, d_in = [], list(d_in)
            for i, p in enumerate(act_bwd(pre, wt * t)):
                d, d_in[i] = grouped_grads(
                    rows, w_in[i], part, p.astype(x.dtype), d_in[i], kernel)
                d_rows.append(d)
            d_in = tuple(d_in)
        with jax.named_scope("moe_combine"):
            d_wt = jnp.sum(hidden.astype(jnp.float32) * t, axis=-1)
            # distinct indices all: a dead row's lies past the end and is dropped
            d_flat = d_flat.at[jnp.where(live, at, n * k + jnp.arange(chunk))].set(
                d_wt, mode="drop", unique_indices=True)
        with jax.named_scope("moe_dispatch"):
            d_x = add_rows(d_x, jnp.where(
                live[:, None], functools.reduce(jnp.add, [d.astype(jnp.float32) for d in d_rows]),
                0.0), tok, part, live, adder)
        return d_x, d_flat, d_in, d_out

    d_x, d_flat, d_in, d_out = jax.lax.fori_loop(0, _trips(sizes, chunk), trip, (
        _result(u.shape, adder), jnp.zeros(n * k, jnp.float32),
        tuple(jnp.zeros(w.shape, jnp.float32) for w in w_in),
        jnp.zeros(w_out.shape, jnp.float32)))
    # the barrier ties the weights' gradients, cast to the operands' dtype as
    # one product's would be, to the tokens': without it the casts are fused
    # into the optimizer's pass and every layer's float32 accumulators live
    # through the whole backward
    with jax.named_scope("moe_experts"):
        d_in = tuple(d.astype(w.dtype) for d, w in zip(d_in, w_in))
        d_out = d_out.astype(w_out.dtype)
    d_x, d_in, d_out = jax.lax.optimization_barrier(
        (d_x.reshape(u.shape).astype(u.dtype), d_in, d_out))
    return d_x, d_flat.reshape(n, k).astype(weight.dtype), d_in, d_out, None, None


_walk.defvjp(_walk_fwd, _walk_bwd)


def routed_experts(u, choice, weight, w_in, w_out, first: int, dtype=None, kernel=None,
                   chunk: int | None = None, w_gate=None, form: str = "relu2"):
    """The held experts' part of the block's output for ``u`` (N, d):
    ``sum over chosen and held e of weight_e * relu(u W_in[e])^2 W_out[e]``,
    float32 — or, at a gated ``form`` (``reglu``, ``swiglu``) with ``w_gate``
    (H, d, f), of ``weight_e * (act(u W_gate[e]) * u W_in[e]) W_out[e]``.
    ``choice`` (N, k) global expert ids, ``weight`` (N, k);
    ``w_in`` (H, d, f), ``w_out`` (H, f, d): experts ``first .. first + H``.
    Matmul operands in ``dtype``. ``chunk``: the rows a trip of the walk takes
    (``chunk_rows``, a ``ROW_TILE`` multiple); every assignment where the
    caller does not know the router's width."""
    n, k = choice.shape
    held = w_in.shape[0]
    cd = dtype or jnp.float32
    chunk = chunk or chunk_rows(n, k, held, held)
    with jax.named_scope("moe_dispatch"):
        local = (choice - first).reshape(-1)
        key = jnp.where((local >= 0) & (local < held), local, held)  # absent experts' last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0).astype(jnp.int32)
        order = jnp.pad(order, (0, (-n * k) % chunk))  # whole chunks; the pad is never live
    first_projections = _first_projections(form, w_in, w_gate)
    with jax.named_scope("moe_experts"):
        first_projections = tuple(w.astype(cd) for w in first_projections)
        w_out = w_out.astype(cd)
    return _walk(
        u, weight.astype(jnp.float32), first_projections, w_out, order, sizes, chunk, kernel, form)


def routed_experts_dense(u, choice, weight, w_in, w_out, first: int, dtype=None, w_gate=None,
                         form: str = "relu2"):
    """The same sum with every held expert applied to every row under a mask:
    the acting form (a few rows a step), and the sparse form's oracle."""
    held = w_in.shape[0]
    cd = dtype or jnp.float32
    gate = jnp.sum(
        jnp.where(choice[..., None] - first == jnp.arange(held), weight[..., None], 0.0),
        axis=-2,
    )  # (N, H): the weight of held expert e for this row, 0 where not chosen
    first_projections = _first_projections(form, w_in, w_gate)
    act, _ = EXPERT_FORMS[form]
    hidden = act(tuple(
        jnp.einsum("nd,edf->nef", u.astype(cd), w.astype(cd), preferred_element_type=jnp.float32)
        for w in first_projections))
    out = jnp.einsum(
        "nef,efd->ned", hidden.astype(cd), w_out.astype(cd), preferred_element_type=jnp.float32)
    return jnp.einsum("ne,ned->nd", gate, out)
