"""The Pallas kernels of the delta rule with a decay per key channel
(``tpu_rl/ops/pallas_kda.py``) in the interpreter against the ``jax.numpy``
body of ``kda_chunked`` — the oracle and the CPU's path: outputs and every
gradient in float32 and in bf16 over the seams a chunk and a sub-block can
take, every gate at the bound and at no decay, ``dg`` at the bound with bf16
operands against the step recurrence (the pair of a step with itself, summed
exactly), a window that is no whole number of chunks, the gate, the island
under a data mesh and the VMEM count. Chunks of 8 steps in sub-blocks of 4, so
a 64-step window is two of the backward's groups of four chunks."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_rl.models import cells
from tpu_rl.ops import gated_delta, kda, pallas_gdn, pallas_kda

B, H, DK, DV = 2, 2, 16, 16
CHUNK, SUB, T = 8, 4, 64
BOUND = -5.0
NAMES = ("q", "k", "v", "g", "beta", "state0")
# ``kda_chunked(kernel=...)``: the jax.numpy body; (heads a grid step, True): the kernels
JNP = (None, False)


@pytest.fixture(scope="module", autouse=True)
def small_chunks():
    before = kda.SUB, gated_delta.SPAN_CHUNKS
    kda.SUB, gated_delta.SPAN_CHUNKS = SUB, 2
    yield
    kda.SUB, gated_delta.SPAN_CHUNKS = before


def inputs(seams=(), steps=T, gate=None, heads=H, rows=B, seed=1):
    """``gate``: every log decay, or None for the bounded gate on seeded
    inputs. Seams in row 0; the last row is one episode and reads ``state0``
    to the end."""
    keys = jax.random.split(jax.random.key(seed), 8)
    q, k = (jax.random.normal(key, (rows, steps, heads, DK)) for key in keys[:2])
    v = jax.random.normal(keys[2], (rows, steps, heads, DV))
    g = BOUND * jax.nn.sigmoid(2.0 * jax.random.normal(keys[3], (rows, steps, heads, DK)))
    if gate is not None:
        g = jnp.full_like(g, gate)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (rows, steps, heads)))
    first = np.zeros((rows, steps), bool)
    first[:-1, list(seams)] = True
    return dict(
        q=q, k=k, v=v, g=g, beta=beta, state0=jax.random.normal(keys[5], (rows, heads, DK, DV)),
        seg=jnp.cumsum(jnp.asarray(first), axis=1).astype(jnp.int32),
        w_o=jax.random.normal(keys[6], v.shape),
        w_last=jax.random.normal(keys[7], (rows, heads, DK, DV)),
    )


def value_and_grads(q, k, v, g, beta, state0, seg, w_o, w_last, *, dtype, kernel):
    """Outputs and the gradients of a weighted sum of them."""

    def f(q, k, v, g, beta, state0):
        o, last = kda.kda_chunked(q, k, v, g, beta, seg, state0, CHUNK, dtype, kernel=kernel)
        return jnp.sum(o * w_o) + jnp.sum(last * w_last), (o, last)

    (_, outs), grads = jax.value_and_grad(f, argnums=tuple(range(6)), has_aux=True)(
        q, k, v, g, beta, state0)
    return {"o": outs[0], "last": outs[1], **{f"d{k}": v for k, v in zip(NAMES, grads)}}


@functools.cache
def program(kernel, dtype):
    """One jitted program per form of the scan: cases of one shape share its
    compilation. ``kernel`` None: the gate chooses, while the program is traced."""
    return jax.jit(functools.partial(value_and_grads, dtype=dtype, kernel=kernel))


def operands(a):
    return [a[k] for k in (*NAMES, "seg", "w_o", "w_last")]


def run(kernel, a, dtype=None):
    return program(kernel, dtype)(*operands(a))


def gated():
    """A program the gate chooses the form of, traced anew: the gate reads
    ``models.cells``' mode and mesh while tracing."""
    return jax.jit(functools.partial(value_and_grads, dtype=None, kernel=None))


def assert_close(got, want, tol):
    for key, ref in want.items():
        ref = np.asarray(ref, np.float32)
        err = float(np.abs(np.asarray(got[key], np.float32) - ref).max())
        assert np.isfinite(np.asarray(got[key], np.float32)).all(), key
        assert err <= tol * (1.0 + float(np.abs(ref).max())), (key, err)


PRECISIONS = [(None, 1e-5), (jnp.bfloat16, 2e-2)]  # (product dtype, tolerance)

# ``tests/test_kda.py``'s, on a window of two groups of four chunks of two sub-blocks
SEAMS = {
    "none": (), "a-chunks-first-step": (16,), "a-chunks-last-step": (15,),
    "a-sub-blocks-first-step": (20,), "two-in-one-sub-block": (9, 10),
    "a-groups-first-step": (32, 33), "every-kind": (0, 3, 7, 8, 19, 21, 22, 31, 40, 63),
}


@pytest.mark.parametrize("dtype,tol", PRECISIONS, ids=["float32", "bfloat16"])
@pytest.mark.parametrize("seams", SEAMS.values(), ids=SEAMS.keys())
def test_kernels_match_the_jnp_body(seams, dtype, tol):
    """Forward and all six gradients. In float32 the forms differ by the order
    of sums (the kernels' cumulative sum is a tree of shifted adds); in bf16,
    the cell's precision, by the cotangents' rounding: the state, the decays
    and the inverse are float32 in both."""
    a = inputs(seams)
    got, want = run((H, True), a, dtype), run(JNP, a, dtype)
    assert set(got) == {"o", "last"} | {f"d{k}" for k in NAMES}
    assert got["o"].dtype == got["last"].dtype == got["dg"].dtype == jnp.float32
    assert_close(got, want, tol)
    if dtype is not None:  # the forward's products are the body's, operand for operand
        assert_close({k: got[k] for k in ("o", "last")}, {k: want[k] for k in ("o", "last")}, 2e-5)
    if seams and seams[0] == 0:  # nothing of state0 survives a seam at step 0: the seamed rows'
        assert not np.asarray(got["dstate0"][:-1]).any()
    assert np.asarray(got["dstate0"][-1]).any()


@pytest.mark.parametrize("dtype,tol", PRECISIONS, ids=["float32", "bfloat16"])
@pytest.mark.parametrize("gate", [BOUND, 0.0], ids=["every-gate-at-the-bound", "no-decay"])
def test_every_gate_at_one_value_for_the_whole_window(gate, dtype, tol):
    """At the bound the right operand inside a diagonal sub-block reaches
    ``e^(5 x 3)`` here (``e^75`` at sub-blocks of 16) and every factor against
    a state underflows within a chunk; finite and the body's."""
    a = inputs((11, 40), gate=gate, seed=2)
    assert_close(run((H, True), a, dtype), run(JNP, a, dtype), tol)


def step_by_step(q, k, v, g, beta, first, state0):
    """``kda_step`` over the window, the state zeroed where an episode starts."""
    def step(S, at):
        *at, first_t = at
        o, S = kda.kda_step(*at, jnp.where(first_t[:, None, None, None], 0.0, S))
        return S, o

    last, o = jax.lax.scan(
        step, state0, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta, first)))
    return jnp.moveaxis(o, 0, 1), last


def test_bf16_operands_keep_the_decays_gradient_at_the_bound():
    """``tests/test_kda.py``'s case on the kernels: a step's pair with itself
    carries no decay and is summed exactly, in the forward and — the cotangent
    of ``R`` masked to strictly under the diagonal before the factoring's
    transpose — in the backward's ``dΓ``. Through the factoring its two
    gradients by the decay cancel only to bf16 rounding, which at the bound
    read 14% of the largest gradient (PR 51); 0.4% now."""
    a = inputs(gate=BOUND, seed=11, steps=32)
    q, k, v = (a[x].astype(jnp.bfloat16) for x in "qkv")
    first = jnp.zeros(a["seg"].shape, bool)

    def dg(rule):
        return jax.jit(jax.grad(lambda g: jnp.sum(a["w_o"] * rule(g)[0])))(a["g"])

    got = dg(lambda g: kda.kda_chunked(
        q, k, v, g, a["beta"], a["seg"], a["state0"], CHUNK, jnp.bfloat16, kernel=(H, True)))
    want = dg(lambda g: step_by_step(q, k, v, g, a["beta"], first, a["state0"]))
    err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert np.isfinite(np.asarray(got)).all() and err <= 2e-2, err


CASES = {
    "window-no-multiple-of-the-chunk": dict(steps=27, seams=(11,)),
    "three-chunks-one-group": dict(steps=24, seams=(11, 12)),
    "five-chunks-each-its-own-group": dict(steps=40, seams=(17,)),
    "fewer-heads-a-step-than-heads": dict(seams=(13, 41), hb=1),
    "groups-of-two-chunks": dict(seams=(13, 40, 41), group=2),
    "the-inverse-doubled-twice": dict(steps=32, seams=(13,), base=2),
    "one-sub-block-a-chunk": dict(steps=32, seams=(13, 14), sub=8),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_shapes_the_kernels_special_case(case, monkeypatch):
    case = dict(case)
    hb, group, base, sub = (case.pop(k, None) for k in ("hb", "group", "base", "sub"))
    a = inputs(**case)
    monkeypatch.setattr(pallas_kda, "_GROUP", group or pallas_kda._GROUP)
    # diagonal blocks of two rows, so that a chunk of eight doubles twice
    monkeypatch.setattr(pallas_gdn, "_BASE", base or pallas_gdn._BASE)
    monkeypatch.setattr(kda, "SUB", sub or SUB)
    form = lambda kernel: jax.jit(functools.partial(  # noqa: E731 — traced anew: the patches
        value_and_grads, dtype=None, kernel=kernel))(*operands(a))
    got = form((hb or H, True))
    assert got["o"].shape == a["v"].shape
    assert_close(got, form(JNP), 1e-5)


def lowered_text(a):
    scan = jax.jit(lambda *args: kda.kda_chunked(*args, a["seg"], a["state0"], CHUNK, None))
    return scan.lower(*(a[k] for k in NAMES[:5])).as_text(debug_info=True)


def test_off_is_the_jnp_body_bit_for_bit_and_auto_takes_it_on_a_cpu(monkeypatch):
    a = inputs((13,))
    body = run(JNP, a)
    for mode in ("off", "auto"):
        monkeypatch.setattr(cells, "_PALLAS_MODE", mode)  # read while tracing
        got = gated()(*operands(a))
        for key in body:
            assert np.array_equal(np.asarray(body[key]), np.asarray(got[key])), (mode, key)
        assert "kda_pallas" not in lowered_text(a)
    monkeypatch.setattr(cells, "_PALLAS_MODE", "interpret")
    text = lowered_text(a)
    assert "kda_scan/kda_pallas" in text  # the kernel's scope, inside the scan's
    from tpu_rl.utils.platform import _PATH_SCOPES

    assert {"kda_scan", "kda_pallas"} <= set(_PATH_SCOPES.findall(text))


def test_under_a_data_mesh_the_kernels_run_as_an_island(monkeypatch, devices):
    """Rows sharded over ``"data"``: outputs and every gradient as the
    jax.numpy body gives them on one device."""
    from tpu_rl.parallel import make_mesh

    a = inputs((13,), rows=4, steps=32)
    monkeypatch.setattr(cells, "_PALLAS_MODE", "interpret")
    monkeypatch.setattr(cells, "_DATA_MESH", make_mesh(2))
    island = gated()
    assert "sdy.manual_computation" in island.lower(*operands(a)).as_text()
    want = jax.jit(functools.partial(value_and_grads, dtype=None, kernel=JNP))(*operands(a))
    assert_close(island(*operands(a)), want, 1e-5)
    # a batch that does not tile the mesh (init and act traces): no island
    monkeypatch.setattr(cells, "_DATA_MESH", make_mesh(8))
    assert "sdy.manual_computation" not in gated().lower(*operands(a)).as_text()


CELL = dict(b=1, h=32, dk=128, dv=128, Q=64)  # ling-3.0-flash-vl
V5E, V5P, V4 = 128, 64, 16  # MiB of VMEM a core


@pytest.mark.parametrize("mode,platform,data,vmem,shape,sub,want", [
    ("auto", "cpu", 1, V5E, CELL, 16, (None, False)),
    ("auto", "tpu", 1, V5E, CELL, 16, (8, False)),
    ("force", "tpu", 1, V5E, CELL, 16, (8, False)),
    ("auto", "tpu", 2, V5E, {**CELL, "b": 2}, 16, (8, False)),  # an island over two chips
    ("auto", "tpu", 4, V5E, {**CELL, "b": 2}, 16, (None, False)),  # two rows do not tile four chips
    ("auto", "tpu", 1, 96, CELL, 16, (8, False)),
    ("auto", "tpu", 1, V5P, CELL, 16, (None, False)),  # counted at float32 operands: 51 MiB of 48
    ("auto", "tpu", 1, 48, {**CELL, "h": 4}, 16, (4, False)),  # every head, where they are few
    ("auto", "tpu", 1, 48, CELL, 16, (None, False)),  # the body where a tile of eight does not fit
    ("auto", "tpu", 1, V4, CELL, 16, (None, False)),
    ("off", "tpu", 1, V5E, CELL, 16, (None, False)),
    ("interpret", "cpu", 1, V5E, CELL, 16, (8, True)),
    ("interpret", "cpu", 1, V4, dict(b=2, h=3, dk=8, dv=8, Q=8), 4, (3, True)),  # any width
    ("auto", "tpu", 1, V5E, {**CELL, "Q": 8}, 16, (None, False)),  # the tests' chunks
    ("auto", "tpu", 1, V5E, CELL, 8, (None, False)),  # sub-blocks that fill no bf16 sublane group
    ("auto", "tpu", 1, V5E, {**CELL, "Q": 48}, 32, (None, False)),  # no whole number of sub-blocks
    ("auto", "tpu", 1, V5E, {**CELL, "dk": 64}, 16, (None, False)),
    ("auto", "tpu", 1, V5E, {**CELL, "dv": 192}, 16, (None, False)),
    ("auto", "tpu", 1, V5E, {**CELL, "h": 12}, 16, (None, False)),  # no whole tile of o's heads
    ("auto", "tpu", 1, V5E, {**CELL, "h": 16}, 16, (8, False)),
], ids=lambda v: "x".join(map(str, v.values())) if isinstance(v, dict) else str(v).replace(" ", ""))
def test_the_gate(monkeypatch, mode, platform, data, vmem, shape, sub, want):
    monkeypatch.setattr(cells, "_PALLAS_MODE", mode)
    monkeypatch.setattr(cells, "_program_devices", lambda: (platform, data))
    monkeypatch.setattr(pallas_kda, "_vmem_limit", lambda: 3 * vmem * 2**20 // 4)
    monkeypatch.setattr(kda, "SUB", sub)
    assert kda._kernel_block(**shape) == want
    hb = want[0]
    if hb is not None and not want[1]:  # the kernels' need is inside what the call asks for
        need = pallas_kda._vmem_bytes(hb, shape["dk"], shape["dv"], shape["Q"])
        assert need <= 0.75 * vmem * 2**20


def test_the_need_is_counted_over_what_mosaic_allocates():
    """22.60 MiB is the scoped allocation Mosaic reports for the backward at
    the cell's widths in bf16, 8 heads and 4 chunks a step, its own spills
    included (compiled for a described v5e at falling limits: refused at 22
    MiB "with size 22.60M", accepted at 23; ``tests/test_tpu_compile.py``
    compiles the pair at the count where the installation can). The count
    keeps the room ``pallas_gdn``'s keeps: the blocks and the scratch twice."""
    assert pallas_kda._GROUP == 4
    need = pallas_kda._vmem_bytes(8, 128, 128, 64, 2)
    assert need == pytest.approx(39.19 * 2**20, rel=0.005)
    assert 1.5 * 22.60 * 2**20 < need < 2 * 22.60 * 2**20


def test_the_kernels_norm_and_sub_block_are_the_bodys():
    assert pallas_gdn.L2_EPS == gated_delta.L2_EPS
    a = inputs((13,), steps=16)
    with pytest.raises(AssertionError, match="sub-blocks"):
        kda.kda_chunked(*(a[k] for k in NAMES[:5]), a["seg"], a["state0"], 6, kernel=(H, True))
