"""A sparse-expert feed-forward block as one expert-parallel rank computes it.

The block is told which routed experts it holds (``first``: the global id of
the first; how many: the leading axis of its weights). It routes every token
over *all* the model's experts, as the published router does, and computes

    y = shared(u) + sum over the token's chosen experts that are held here of
        w_e * W2_e relu(W1_e u)^2

What the absent experts would add is left out: in a deployment the other ranks
compute it and an exchange brings it home; here there is no exchange and no
code that stands in for one.

Router (``route``, float32 throughout): ``s = sigmoid(W_r u)``; the choice is
the ``top_k`` largest of ``s + b`` (``b``: a correction bias that only the
choice reads, so no gradient reaches it); ``w = scale * s_e / (sum of the
chosen s + 1e-20)``. The discrete choice carries no gradient, ``s`` does.

Dispatch (``routed_experts``), with static shapes and without dropping a token
whatever the imbalance: the ``tokens x top_k`` assignments are sorted by held
expert (those on absent experts last), the tokens' rows gathered in that order
into a buffer sized for what can arrive — every assignment —, one grouped
matmul per projection runs over the ragged groups, and the rows go back to
their tokens' slots, weighted. Rows past the held total are never computed
and never read: the grouped matmul's grid ends with the last held row, and the
combine and the dispatch's backward select held assignments only. Gather and
its inverse are each other's transposes (``_dispatch`` / ``_collect``), so the
backward pass gathers too and never scatter-adds.

The grouped matmul (``grouped_matmul``): on a TPU, at widths whose tiles the
kernel takes, ``pallas.ops.tpu.megablox`` (scope ``moe_gmm_pallas``), with the
transposed product for the weights' gradient; elsewhere ``jax.lax.ragged_dot``,
which is also the kernel's oracle. The gate is ``models/cells.py``'s
(``set_pallas_mode``: ``"interpret"`` runs the kernel in the interpreter,
``"off"`` forces ``ragged_dot``).

Scopes, for the device trace: ``moe_route``, ``moe_dispatch``, ``moe_experts``
(``moe_gmm_pallas`` inside it when the kernel was taken), ``moe_combine``,
``moe_shared``; the caller wraps the block in ``moe``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _megablox_gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as _megablox_tgmm

# Rows a grid step of the grouped matmul takes. A held expert sees a few
# hundred rows an update at the cell's batch: a taller tile would be mostly
# another group's rows, masked.
ROW_TILE = 256


# ------------------------------------------------------------------ the router
@jax.named_scope("moe_route")
def route(u, kernel, bias, top_k: int, scale: float):
    """``u`` (N, d); ``kernel`` (d, E); ``bias`` (E,). Returns the chosen
    experts (N, top_k) int32 and their weights (N, top_k) float32."""
    s = jax.nn.sigmoid(jnp.dot(
        u.astype(jnp.float32), kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    _, choice = jax.lax.top_k(jax.lax.stop_gradient(s + bias), top_k)
    chosen = jnp.take_along_axis(s, choice, axis=-1)
    chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return choice.astype(jnp.int32), scale * chosen


def route_stats(choice, first: int, held: int) -> dict:
    """Counters of one block's routing, as float32 scalars (in-jit, no
    gradient): rows computed, rows of the fullest held expert and of the mean
    one, the share of assignments on held experts, the share of tokens with
    none."""
    local = choice - first
    mine = (local >= 0) & (local < held)
    counts = jnp.sum(
        (local[..., None] == jnp.arange(held)) & mine[..., None], axis=(0, 1)
    ).astype(jnp.float32)
    rows = jnp.sum(counts)
    return {
        "rows": rows,
        "rows-max": jnp.max(counts),
        "rows-mean": rows / held,
        "held-share": rows / choice.size,
        "no-held-share": jnp.mean(1.0 - jnp.any(mine, axis=-1).astype(jnp.float32)),
    }


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


def _gmm_pallas_bwd(interpret, residual, g):
    lhs, rhs, sizes = residual
    m, k = lhs.shape
    n = rhs.shape[2]
    g = g.astype(lhs.dtype)
    d_lhs = _megablox_gmm(
        g, rhs, sizes, lhs.dtype, _tiles(m, n, k), transpose_rhs=True, interpret=interpret,
    )
    tm, _, tn = _tiles(m, k, n)
    d_rhs = _megablox_tgmm(
        lhs.swapaxes(0, 1), g, sizes, rhs.dtype, (tm, min(k, 512), tn), interpret=interpret,
    )
    return d_lhs, d_rhs, None


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


# ------------------------------------------------------- dispatch and combine
@jax.custom_vjp
def _dispatch(u, order, place, mine):
    """Rows of ``u`` (N, d) in the order the experts take them: (N k, d).
    ``order`` is a permutation of the N k assignments (assignment ``a`` is
    token ``a // k``, slot ``a % k``), ``place`` its inverse, ``mine`` (N, k)
    the assignments on held experts: only their rows' gradients are read."""
    return u[order // (order.shape[0] // u.shape[0])]


def _dispatch_fwd(u, order, place, mine):
    return _dispatch(u, order, place, mine), (place, mine)


def _dispatch_bwd(residual, g):
    place, mine = residual
    slots = g[place].reshape(*mine.shape, g.shape[-1])
    return jnp.where(mine[..., None], slots, 0).sum(axis=1), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _collect(rows, order, place):
    """The inverse permutation: rows (N k, d) in the experts' order back to
    assignment order (token-major, slot-minor)."""
    return rows[place]


def _collect_fwd(rows, order, place):
    return rows[place], order


def _collect_bwd(order, g):
    return g[order], None, None


_collect.defvjp(_collect_fwd, _collect_bwd)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def routed_experts(u, choice, weight, w_in, w_out, first: int, dtype=None, kernel=None):
    """The held experts' part of the block's output for ``u`` (N, d):
    ``sum over chosen and held e of weight_e * relu(u W_in[e])^2 W_out[e]``,
    float32. ``choice`` (N, k) global expert ids, ``weight`` (N, k);
    ``w_in`` (H, d, f), ``w_out`` (H, f, d): experts ``first .. first + H``.
    Matmul operands in ``dtype``."""
    n, k = choice.shape
    held = w_in.shape[0]
    cd = dtype or jnp.float32
    with jax.named_scope("moe_dispatch"):
        local = (choice - first).reshape(-1)
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)  # absent experts' assignments last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        place = jnp.zeros_like(order).at[order].set(jnp.arange(n * k, dtype=jnp.int32))
        sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0).astype(jnp.int32)
        pad = (-n * k) % ROW_TILE  # whole row tiles; never inside a group
        mine = mine.reshape(n, k)
        rows = _dispatch(u.astype(cd), order, place, mine)
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    with jax.named_scope("moe_experts"):
        # rows past the held total are never computed and never read: the
        # combine below and the dispatch's backward select held assignments
        hidden = _relu2(grouped_matmul(rows, w_in.astype(cd), sizes, kernel))
        out = grouped_matmul(hidden.astype(cd), w_out.astype(cd), sizes, kernel)
    with jax.named_scope("moe_combine"):
        slots = _collect(out[: n * k], order, place).reshape(n, k, -1)
        # select, not a product with a zero weight: an unwritten row may hold
        # anything, and a product's gradient would multiply it by zero
        kept = jnp.where(mine[..., None], slots.astype(jnp.float32), 0.0)
        return jnp.einsum(
            "nk,nkd->nd", jnp.where(mine, weight, 0.0), kept,
            precision=jax.lax.Precision.HIGHEST,
        )


def routed_experts_dense(u, choice, weight, w_in, w_out, first: int, dtype=None):
    """The same sum with every held expert applied to every row under a mask:
    the acting form (a few rows a step), and the sparse form's oracle."""
    held = w_in.shape[0]
    cd = dtype or jnp.float32
    gate = jnp.sum(
        jnp.where(choice[..., None] - first == jnp.arange(held), weight[..., None], 0.0),
        axis=-2,
    )  # (N, H): the weight of held expert e for this row, 0 where not chosen
    hidden = _relu2(jnp.einsum(
        "nd,edf->nef", u.astype(cd), w_in.astype(cd), preferred_element_type=jnp.float32))
    out = jnp.einsum(
        "nef,efd->ned", hidden.astype(cd), w_out.astype(cd), preferred_element_type=jnp.float32)
    return jnp.einsum("ne,ned->nd", gate, out)
