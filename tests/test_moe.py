"""The sparse-expert block's pieces (``tpu_rl/ops/moe.py``) on the CPU: the
router, the grouped matmul (``ragged_dot`` body against a per-expert loop, the
Pallas kernel in the interpreter against the body, both operands' gradients),
and the walk over the sorted held assignments in row chunks (gather, grouped
products, add into the tokens) against every held expert applied densely
under a mask: under forced imbalance, at every count of trips, with groups
that straddle a chunk's edge; and the walk's row-add kernel
(``tpu_rl/ops/pallas_moe.py``) in the interpreter against XLA's scatter-add."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_rl.ops import moe, pallas_moe

D, F, HELD, TOTAL, K = 32, 24, 4, 16, 3
WIDE = 128  # a width the row-add kernel takes: a lane multiple


def close(got, want, tol=1e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=tol)


def operands(seed: int, rows: int, sizes):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.standard_normal((rows, D)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), D, F)) / np.sqrt(D), jnp.float32)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def loop_matmul(lhs, rhs, sizes):
    """Group g's rows times ``rhs[g]``, one expert at a time; zeros after."""
    out, start = jnp.zeros((lhs.shape[0], rhs.shape[2])), 0
    for g, n in enumerate(np.asarray(sizes)):
        out = out.at[start:start + n].set(lhs[start:start + n] @ rhs[g])
        start += n
    return out


SIZES = {"even": [64, 64, 64, 64], "ragged": [5, 130, 0, 70], "one-group": [0, 0, 256, 0],
         "empty": [0, 0, 0, 0], "partly-filled": [3, 0, 1, 9]}


@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
@pytest.mark.parametrize("kernel", [(False, False), (True, True)], ids=["ragged_dot", "pallas"])
def test_grouped_matmul_equals_a_loop_over_the_experts(sizes, kernel):
    """Forward and both operands' gradients over the groups' rows (past their
    total ``ragged_dot`` gives zeros and the kernel writes nothing). ``pallas``:
    the megablox kernels (product, transposed product) in the interpreter."""
    lhs, rhs, sz = operands(0, 256, sizes)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((256, F)), jnp.float32)
    total = int(sum(sizes))
    live = (jnp.arange(256) < total)[:, None]

    def weighted(f):
        return jax.value_and_grad(
            lambda a, b: jnp.sum(jnp.where(live, w * f(a, b, sz), 0.0)), argnums=(0, 1))

    got, (d_lhs, d_rhs) = weighted(lambda a, b, s: moe.grouped_matmul(a, b, s, kernel))(lhs, rhs)
    want, (r_lhs, r_rhs) = weighted(loop_matmul)(lhs, rhs)
    close(got, want, 1e-3)
    close(d_lhs[:total], r_lhs[:total], 1e-4)
    close(d_rhs, r_rhs, 1e-4)
    if not kernel[0]:
        assert not np.asarray(d_lhs)[total:].any()


def router_params(seed: int):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((D, TOTAL)) / np.sqrt(D), jnp.float32),
            jnp.asarray(0.05 * rng.standard_normal(TOTAL), jnp.float32))


def test_the_router_scores_every_expert_and_weights_the_chosen():
    u = jnp.asarray(np.random.default_rng(2).standard_normal((40, D)), jnp.float32)
    kernel, bias = router_params(3)
    choice, weight = moe.route(u, kernel, bias, K, 2.5)
    s = 1.0 / (1.0 + np.exp(-np.asarray(u) @ np.asarray(kernel)))
    want = np.argsort(-(s + np.asarray(bias)), axis=-1, kind="stable")[:, :K]
    assert np.array_equal(choice, want)
    chosen = np.take_along_axis(s, want, axis=-1)
    close(weight, 2.5 * chosen / chosen.sum(-1, keepdims=True), 1e-6)
    close(weight.sum(-1), 2.5, 1e-5)


def test_the_bias_moves_a_choice_and_gets_no_gradient():
    """``b`` is read by the choice alone: raising one expert's bias puts it
    among the chosen, its weight is still its unbiased score's share, and the
    gradient of anything downstream with respect to ``b`` is zero while the
    router's own weights get one."""
    u = jnp.asarray(np.random.default_rng(4).standard_normal((40, D)), jnp.float32)
    kernel, bias = router_params(5)
    choice, _ = moe.route(u, kernel, bias, K, 1.0)
    never = int(np.setdiff1d(np.arange(TOTAL), np.asarray(choice)[0])[0])
    moved, weight = moe.route(u, kernel, bias.at[never].add(10.0), K, 1.0)
    assert (np.asarray(moved) == never).any(axis=-1).all() and not (np.asarray(choice)[0] == never).any()
    s = 1.0 / (1.0 + np.exp(-np.asarray(u) @ np.asarray(kernel)))
    chosen = np.take_along_axis(s, np.asarray(moved), axis=-1)
    close(weight, chosen / chosen.sum(-1, keepdims=True), 1e-6)

    def downstream(kernel, bias):
        _, w = moe.route(u, kernel, bias, K, 1.0)
        return jnp.sum(w * jnp.arange(1.0, K + 1))

    d_kernel, d_bias = jax.grad(downstream, argnums=(0, 1))(kernel, bias)
    assert not np.asarray(d_bias).any() and np.abs(np.asarray(d_kernel)).max() > 1e-4


def expert_weights(seed: int, d: int = D):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((HELD, d, F)) / np.sqrt(d), jnp.float32),
            jnp.asarray(rng.standard_normal((HELD, F, d)) / np.sqrt(F), jnp.float32))


def assignments(kind: str, n: int, first: int):
    """(choice, weight) for ``n`` tokens; held experts are first..first+HELD."""
    rng = np.random.default_rng(6)
    weight = jnp.asarray(rng.random((n, K)) + 0.1, jnp.float32)
    if kind == "random":
        choice = np.stack([rng.permutation(TOTAL)[:K] for _ in range(n)])
    elif kind == "all-on-one-held":  # every token's first choice is one held expert
        others = [e for e in range(TOTAL) if not first <= e < first + HELD]
        choice = np.stack([[first + 2, *rng.permutation(others)[: K - 1]] for _ in range(n)])
    elif kind == "all-held":  # every assignment of every token lands here
        choice = np.stack([first + rng.permutation(HELD)[:K] for _ in range(n)])
    else:  # "none-held"
        others = [e for e in range(TOTAL) if not first <= e < first + HELD]
        choice = np.stack([rng.permutation(others)[:K] for _ in range(n)])
    return jnp.asarray(choice, jnp.int32), weight


def sparse_and_dense(u, choice, weight, w_in, w_out, first, kernel, chunk):
    """Output and the gradients of the tokens, the weights and both expert
    projections: the walk's, and the dense form's."""
    mix = jnp.asarray(np.random.default_rng(9).standard_normal(u.shape), jnp.float32)

    def out_and_grads(f):
        def weighted(u, wt, a, b):
            y = f(u, choice, wt, a, b, first)
            return jnp.sum(mix * y), y

        (_, y), grads = jax.jit(jax.value_and_grad(
            weighted, argnums=(0, 1, 2, 3), has_aux=True))(u, weight, w_in, w_out)
        return y, grads

    return (out_and_grads(lambda *a: moe.routed_experts(*a, kernel=kernel, chunk=chunk)),
            out_and_grads(moe.routed_experts_dense))


def assert_sparse_is_dense(choice, weight, first, kernel, chunk, d=D):
    u = jnp.asarray(np.random.default_rng(7).standard_normal((choice.shape[0], d)), jnp.float32)
    (got, grads), (want, ref_grads) = sparse_and_dense(
        u, choice, weight, *expert_weights(8, d), first, kernel, chunk)
    close(got, want, 1e-4)
    for g, r in zip(grads, ref_grads):
        close(g, r, 1e-4)
    return got


CHUNK = 256  # one row tile: 400 tokens x 3 choices are up to five trips


@pytest.mark.parametrize("kind", ["random", "all-on-one-held", "all-held", "none-held"])
@pytest.mark.parametrize("kernel", [(False, False), (True, True)], ids=["ragged_dot", "pallas"])
def test_the_dispatch_drops_no_token_whatever_the_imbalance(kind, kernel):
    """Sparse against dense-under-a-mask. The walk takes as many trips as the
    held rows need, so a fully one-sided routing loses nothing (five trips)
    and one that sends nothing here takes none."""
    n, first = 400, 8
    choice, weight = assignments(kind, n, first)
    got = assert_sparse_is_dense(choice, weight, first, kernel, CHUNK)
    stats = moe.route_stats(choice, first, HELD, CHUNK)
    expect = {"random": None, "all-on-one-held": n, "all-held": n * K, "none-held": 0}[kind]
    if expect is not None:
        assert float(stats["rows"]) == expect
        assert float(stats["chunks"]) == -(-expect // CHUNK)  # 2, 5, 0
    else:
        assert float(stats["chunks"]) == -(-float(stats["rows"]) // CHUNK) == 2
    if kind == "none-held":
        assert not np.asarray(got).any() and float(stats["no-held-share"]) == 1.0
    if kind == "all-on-one-held":
        assert float(stats["rows-max"]) == n and float(stats["rows-mean"]) == n / HELD


def held_rows(counts, n: int, first: int):
    """A routing of ``n`` tokens whose held expert ``e`` gets ``counts[e]``
    rows: token ``i``'s first choice is held while ``i`` is under their total
    (expert 0's tokens first), every other choice absent."""
    rng = np.random.default_rng(11)
    others = [e for e in range(TOTAL) if not first <= e < first + HELD]
    held = np.repeat(np.arange(HELD), counts)
    choice = np.stack([rng.permutation(others)[:K] for _ in range(n)])
    choice[: len(held), 0] = first + held
    return jnp.asarray(choice, jnp.int32), jnp.asarray(rng.random((n, K)) + 0.1, jnp.float32)


EDGES = {
    # held total an exact multiple of the chunk: no partly live last chunk
    "exact-multiple": ([128, 128, 128, 128], 2),
    "one-full-chunk": ([64, 64, 64, 64], 1),
    # expert 1's rows 100..400 lie on both sides of row 256
    "straddles-an-edge": ([100, 300, 50, 50], 2),
    # expert 1 alone fills the middle chunk: experts 0, 2, 3 have no row in it
    "no-rows-in-a-chunk": ([200, 400, 10, 90], 3),
    "one-expert-many-chunks": ([0, 0, 600, 0], 3),
    "one-row": ([0, 1, 0, 0], 1),
}


@pytest.mark.parametrize("counts, trips", EDGES.values(), ids=EDGES.keys())
@pytest.mark.parametrize("kernel", [(False, False), (True, True)], ids=["ragged_dot", "pallas"])
def test_a_group_may_lie_anywhere_across_the_chunks(counts, trips, kernel):
    n, first = 700, 8
    choice, weight = held_rows(counts, n, first)
    assert_sparse_is_dense(choice, weight, first, kernel, CHUNK)
    stats = moe.route_stats(choice, first, HELD, CHUNK)
    assert float(stats["rows"]) == sum(counts) and float(stats["chunks"]) == trips
    assert float(stats["rows-max"]) == max(counts)


def test_a_trip_takes_its_part_of_every_group():
    """``_trip``: the chunk's assignments in sorted order, each group's rows
    inside the chunk (they add up to the chunk, or to what is left of the held
    rows), and the live positions."""
    sizes = jnp.asarray([200, 400, 10, 90], jnp.int32)
    order = jnp.arange(1024, dtype=jnp.int32)[::-1]
    parts = []
    for c, want in enumerate([[200, 56, 0, 0], [0, 256, 0, 0], [0, 88, 10, 90], [0, 0, 0, 0]]):
        at, part, live = moe._trip(c, order, sizes, CHUNK)
        assert np.array_equal(at, order[c * CHUNK:(c + 1) * CHUNK]) and list(map(int, part)) == want
        assert int(live.sum()) == sum(want) and bool(live[: sum(want)].all())
        parts.append(part)
    assert np.array_equal(sum(parts), sizes) and int(moe._trips(sizes, CHUNK)) == 3
    assert int(moe._trips(jnp.zeros(4, jnp.int32), CHUNK)) == 0
    assert int(moe._trips(jnp.asarray([256, 256, 0, 0]), CHUNK)) == 2


def test_the_chunk_is_twice_the_rows_a_fair_router_sends():
    # the cell: 16,384 tokens, 6 choices, 8 held of 128 -> 6,144 rows expected
    assert moe.chunk_rows(16384, 6, 8, 128) == 12288 == 48 * moe.ROW_TILE
    assert 6 * 16384 // moe.chunk_rows(16384, 6, 8, 128) == 8  # what all-held costs
    assert moe.chunk_rows(2048, 6, 128, 128) == 6 * 2048  # every expert held: every row
    assert moe.chunk_rows(40, 3, 4, 16) == moe.ROW_TILE  # never less than a row tile
    assert moe.chunk_rows(1000, 3, 4, 16) == 1536  # 750 expected, doubled, whole tiles


@pytest.mark.parametrize("n, held, total, want", [
    (16384, 8, 128, 12288),  # nemotron's cell: the doubled share, its one trip
    (20480, 8, 128, min(15360, moe.WALK_ROWS)),  # a fifth window: one trip, if the cap allows
    (65536, 16, 64, moe.WALK_ROWS),  # 196,608 by the doubled share
    (32768, 16, 64, moe.WALK_ROWS),  # smallthinker's cell: 98,304 by the doubled share
    (32768, 1, 128, 3072),  # ... where the share is less than the cap, the share
], ids=["nemotron", "five-windows", "many-tokens", "smallthinker", "small-share"])
def test_one_cap_bounds_a_trip(n, held, total, want):
    """``WALK_ROWS`` alone caps a trip, whatever the size of the result the
    trips add into: under it a trip is as tall as the routing suggests."""
    assert moe.chunk_rows(n, 6, held, total) == want <= moe.WALK_ROWS
    assert want % moe.ROW_TILE == 0 and not hasattr(moe, "ONE_TRIP_BYTES")


def test_route_stats_count_rows_per_held_expert():
    choice = jnp.asarray([[8, 9, 0], [9, 1, 2], [9, 10, 11], [3, 4, 5]], jnp.int32)
    stats = {k: float(v) for k, v in moe.route_stats(choice, 8, HELD, 4).items()}
    assert stats == pytest.approx({
        "rows": 6.0, "rows-max": 3.0, "rows-mean": 1.5, "held-share": 0.5, "no-held-share": 0.25,
        "chunks": 2.0})


@pytest.mark.parametrize("d, kernel", [(D, None), (WIDE, (True, True))],
                         ids=["scatter-add", "row-add-kernel"])
def test_what_an_unwritten_row_holds_goes_nowhere(monkeypatch, d, kernel):
    """The kernel never writes the rows past the held total, so in the last
    chunk they may hold anything. With NaN put there after each product and
    each transposed product, the block's output and every gradient are what
    they were: the combine and the backward select live rows, they do not
    multiply by zero — and the row-add kernel ends with the last live row."""
    n, first = 400, 8
    u = jnp.asarray(np.random.default_rng(7).standard_normal((n, d)), jnp.float32)
    w_in, w_out = expert_weights(8, d)
    assert moe._row_add_gate(CHUNK, d, kernel)[0] == (kernel is not None)
    choice, weight = assignments("random", n, first)
    assert float(moe.route_stats(choice, first, HELD, CHUNK)["rows"]) % CHUNK  # a dead tail

    def value_and_grads():
        return jax.value_and_grad(
            lambda u, wt, a, b: jnp.sum(
                moe.routed_experts(u, choice, wt, a, b, first, kernel=kernel, chunk=CHUNK) ** 2),
            argnums=(0, 1, 2, 3))(u, weight, w_in, w_out)

    want, ref_grads = value_and_grads()
    product, grads_of = moe.grouped_matmul, moe.grouped_grads

    def poison(x, sizes):
        return jnp.where((jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None], x, jnp.nan)

    def poisoned(lhs, rhs, sizes, kernel=None):
        return poison(product(lhs, rhs, sizes, kernel), sizes)

    def poisoned_grads(lhs, rhs, sizes, g, acc, kernel=None):
        d_lhs, acc = grads_of(lhs, rhs, sizes, g, acc, kernel)
        return poison(d_lhs, sizes), acc

    monkeypatch.setattr(moe, "grouped_matmul", poisoned)
    monkeypatch.setattr(moe, "grouped_grads", poisoned_grads)
    got, grads = value_and_grads()
    close(got, want)
    for g, r in zip(grads, ref_grads):
        assert np.isfinite(np.asarray(g)).all()
        close(g, r)


# ------------------------------------------------------------- the row-add
ROWS = 32  # a trip of the cases below; the kernel's tiles are 8 rows there


def tokens(rng, sizes, n):
    """Per group ascending distinct tokens, as the stable sort leaves them."""
    return [np.sort(rng.permutation(n)[:size]) for size in sizes]


def row_add_case(name: str):
    """(tokens of each group, width): the sorted held assignments of one
    layer's routing with ``k = 1``, walked in trips of ``ROWS``."""
    rng = np.random.default_rng(12)
    if name == "one-group":
        return tokens(rng, [20], 64), WIDE
    if name == "a-token-twice-either-side-of-a-group-edge-in-one-tile":
        return [np.asarray([1, 3, 4, 7, 9]), np.asarray([7, 9, 30]), np.asarray([9])], WIDE
    if name == "empty-groups":
        return tokens(rng, [0, 9, 0, 0, 7, 0], 64), WIDE
    if name == "a-group-straddles-a-chunk-edge":
        return tokens(rng, [10, 30, 5, 40], 64), WIDE
    if name == "dead-rows-hold-nan":
        return tokens(rng, [3, 9], 64), WIDE
    assert name == "a-width-the-gate-refuses"
    return tokens(rng, [10, 30, 5], 64), D


@pytest.mark.parametrize("name", [
    "one-group", "a-token-twice-either-side-of-a-group-edge-in-one-tile", "empty-groups",
    "a-group-straddles-a-chunk-edge", "dead-rows-hold-nan", "a-width-the-gate-refuses"])
def test_the_row_add_kernel_is_xlas_scatter_add(name):
    """``add_rows`` trip by trip as the walk calls it, the kernel in the
    interpreter against ``.at[].add(mode="drop")``: every dead row of a last
    chunk holds NaN (and token 0), which must reach nothing."""
    groups, d = row_add_case(name)
    n, sizes = 64, jnp.asarray([len(g) for g in groups], jnp.int32)
    held = int(sizes.sum())
    trips = -(-held // ROWS)
    order = jnp.zeros(trips * ROWS, jnp.int32).at[:held].set(jnp.asarray(np.concatenate(groups)))
    rng = np.random.default_rng(13)
    rows = jnp.asarray(rng.standard_normal((trips * ROWS, d)), jnp.float32).at[held:].set(jnp.nan)
    start = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    chosen = moe._row_add_gate(ROWS, d, (True, True))
    assert chosen == (d == WIDE, True) and moe._row_add_gate(ROWS, d, (False, False))[0] is False
    assert moe._row_add_gate(ROWS, WIDE, None) == (False, False)  # a CPU: the gate's own answer

    def walked(adder):
        y = start.reshape(moe._result((n, d), adder).shape)
        for c in range(trips):
            tok, part, live = moe._trip(c, order, sizes, ROWS)
            add = jnp.where(live[:, None], rows[c * ROWS:(c + 1) * ROWS], 0.0)
            if adder[0]:  # the kernel is handed the rows as they are, NaN and all
                y = pallas_moe.row_add(y, rows[c * ROWS:(c + 1) * ROWS], tok, part, True, tile=8)
            else:
                y = moe.add_rows(y, add, tok, part, live, adder)
        return y.reshape(n, d)

    want = np.asarray(start).copy()
    np.add.at(want, np.concatenate(groups), np.asarray(rows[:held]))
    close(walked((False, False)), want, 1e-6)
    close(walked(chosen), want, 1e-6)
    if name.startswith("a-token-twice"):
        assert 7 in groups[0][:8] and 7 in groups[1] and len(groups[0]) < 8  # one tile, two groups


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "reglu"])
def test_the_walk_with_the_row_add_kernel_is_the_walk_without(gated):
    """Output and every gradient of ``routed_experts`` at a width the kernel
    takes, the kernels in the interpreter against ``ragged_dot`` and XLA's
    scatter-add, over several trips with a dead tail."""
    n, first = 400, 8
    choice, weight = assignments("random", n, first)
    rng = np.random.default_rng(14)
    u, probe = (jnp.asarray(rng.standard_normal((n, WIDE)), jnp.float32) for _ in range(2))
    w_in, w_out = expert_weights(8, WIDE)
    w_gate = expert_weights(15, WIDE)[0] if gated else None
    assert float(moe.route_stats(choice, first, HELD, CHUNK)["chunks"]) >= 2

    def value_and_grads(kernel):
        assert moe._row_add_gate(CHUNK, WIDE, kernel)[0] == kernel[0]

        def loss(u, wt, a, b, g):
            y = moe.routed_experts(u, choice, wt, a, b, first, kernel=kernel, chunk=CHUNK, w_gate=g,
                                   form="reglu" if gated else "relu2")
            return jnp.sum(probe * y), y

        argnums = (0, 1, 2, 3, 4) if gated else (0, 1, 2, 3)
        (_, y), grads = jax.jit(jax.value_and_grad(loss, argnums=argnums, has_aux=True))(
            u, weight, w_in, w_out, w_gate)
        return y, grads

    (got, grads), (want, ref_grads) = value_and_grads((True, True)), value_and_grads((False, False))
    close(got, want, 1e-5)
    for g, r in zip(grads, ref_grads):
        assert float(jnp.abs(r).max()) > 0
        close(g, r, 1e-5 * (1.0 + float(jnp.abs(r).max())))


def test_the_walk_traces_no_conditional():
    """The trip count is a traced bound of one loop: the program holds a
    ``while`` and no ``cond`` (a reader of the device trace counts every
    conditional as the optimizer's)."""
    choice, weight = assignments("random", 400, 8)
    u = jnp.zeros((400, D))
    text = str(jax.make_jaxpr(jax.grad(
        lambda u, a, b: jnp.sum(moe.routed_experts(u, choice, weight, a, b, 8, chunk=CHUNK)),
        argnums=(0, 1, 2)))(u, *expert_weights(8)))
    assert text.count("while[") == 2 and "cond[" not in text.replace("cond_jaxpr", "")


def test_the_gate_keeps_the_body_off_the_chip(monkeypatch):
    from tpu_rl.models import cells

    assert moe._gmm_gate(12288, 2688, 1856) == (False, False)  # a CPU
    monkeypatch.setattr(cells, "_PALLAS_MODE", "interpret")
    assert moe._gmm_gate(12288, 2688, 1856) == (True, True)
    monkeypatch.setattr(cells, "_PALLAS_MODE", "off")
    assert moe._gmm_gate(12288, 2688, 1856) == (False, False)
    # the published widths tile at the cell's chunk: both projections, and their transposes
    assert moe._gmm_tiles(12288, 2688, 1856) == (256, 896, 512)
    assert moe._gmm_tiles(12288, 1856, 2688) == (256, 1856, 512)
    assert moe._gmm_tiles(100, 2688, 1856) is None and moe._gmm_tiles(256, 100, 128) is None
