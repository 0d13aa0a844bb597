"""The Pallas kernels of the gated delta rule (``tpu_rl/ops/pallas_gdn.py``) in
the interpreter against the ``jax.numpy`` body of ``gated_delta_chunked`` — the
oracle and the CPU's path: outputs and every gradient in float32 over the
seams, carried states and widths the kernels special-case, one case in bf16,
the triangle's inverse by blocks, and the gate."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_rl.models import cells
from tpu_rl.ops import gated_delta, pallas_gdn
from tpu_rl.ops.gated_delta import gated_delta_chunked

CHUNK = 8
NAMES = ("q", "k", "v", "g", "beta", "state0")


def inputs(T=32, hk=2, hv=2, dk=16, dv=16, seams=(), state0=False, b=2, seed=0):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    firsts = np.zeros((b, T), np.int32)
    firsts[:, list(seams)] = 1
    return dict(
        q=f32(b, T, hk, dk), k=f32(b, T, hk, dk), v=f32(b, T, hv, dv),
        g=-0.3 * np.log1p(np.exp(f32(b, T, hv))), beta=1 / (1 + np.exp(-f32(b, T, hv))),
        seg=np.cumsum(firsts, axis=1).astype(np.int32),
        state0=f32(b, hv, dk, dv) * (1.0 if state0 else 0.0),
    )


# ``gated_delta_chunked(kernel=...)``: the jax.numpy body; (heads a grid step, True): the kernels
JNP = (None, False)


def value_and_grads(q, k, v, g, beta, state0, seg, w_o, w_last, *, dtype, kernel):
    """Outputs and the gradients of a weighted sum of them."""

    def f(q, k, v, g, beta, state0):
        o, last = gated_delta_chunked(q, k, v, g, beta, seg, state0, CHUNK, dtype, kernel=kernel)
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
    rng = np.random.default_rng(9)
    weights = [rng.standard_normal(a[k].shape).astype(np.float32) for k in ("v", "state0")]
    return [jnp.asarray(v) for v in [*(a[k] for k in NAMES), a["seg"], *weights]]


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


CASES = {
    "no-seam": dict(),
    "seam-inside-a-chunk": dict(seams=(13,)),
    "seam-on-a-chunk-boundary": dict(seams=(16,)),
    "seam-at-step-0": dict(seams=(0,), state0=True),
    "several-seams-in-one-chunk": dict(seams=(9, 12, 14)),
    "state0-whose-episode-ends-in-chunk-0": dict(seams=(5,), state0=True),
    "state0-whose-episode-runs-through": dict(state0=True),
    "window-no-multiple-of-the-chunk": dict(T=27, seams=(11,), state0=True),
    "two-key-heads-serve-four-value-heads": dict(hv=4, seams=(13,), state0=True),
    "fewer-heads-a-step-than-heads": dict(hv=4, seams=(13, 24), state0=True, hb=2),
    "a-key-head-a-value-head": dict(hk=4, hv=4, seams=(6,), state0=True, hb=2),
    "the-inverse-doubled-twice": dict(seams=(13,), state0=True, base=2),
    # the backward's groups of chunks that share an entered state (four in the cases above)
    "two-groups-of-four-chunks": dict(T=64, seams=(13, 40), state0=True),
    "four-groups-of-two-chunks": dict(T=64, hv=4, seams=(13, 40, 41), state0=True, group=2),
    "one-group-of-three-chunks": dict(T=24, seams=(11,), state0=True),
    "five-chunks-each-its-own-group": dict(T=40, seams=(17,), state0=True),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_kernels_match_the_jnp_body_in_float32(case, monkeypatch):
    case = dict(case)
    hb, base, group = case.pop("hb", None), case.pop("base", None), case.pop("group", None)
    a = inputs(**case)
    want = run(JNP, a)
    if base or group:  # diagonal blocks of two rows, so that a chunk of eight doubles twice
        monkeypatch.setattr(pallas_gdn, "_BASE", base or pallas_gdn._BASE)
        monkeypatch.setattr(pallas_gdn, "_GROUP", group or pallas_gdn._GROUP)
        got = jax.jit(functools.partial(
            value_and_grads, dtype=None, kernel=(a["v"].shape[2], True)))(*operands(a))
    else:
        got = run((hb or a["v"].shape[2], True), a)
    assert set(got) == {"o", "last"} | {f"d{k}" for k in NAMES}
    assert_close(got, want, 2e-6)
    if case.get("seams") == (0,):  # nothing of state0 survives a seam at step 0
        assert not np.asarray(got["dstate0"]).any()
    elif case.get("state0"):
        assert np.asarray(got["dstate0"]).any()


def test_kernels_match_the_jnp_body_in_bfloat16():
    """The cell's precision: bf16 operands at the same products, the state,
    the decays and the inverse float32, so the two forms differ by the
    cotangents' rounding and the order of float32 sums; against the float32
    body, by bf16's 2^-8 an operand."""
    a = inputs(hv=4, seams=(13, 14), state0=True, seed=3)
    got = run((4, True), a, jnp.bfloat16)
    same = run(JNP, a, jnp.bfloat16)
    assert got["o"].dtype == got["last"].dtype == jnp.float32
    assert_close({k: got[k] for k in ("o", "last")}, {k: same[k] for k in ("o", "last")}, 1e-5)
    assert_close(got, same, 8e-3)
    exact = run(JNP, a)
    assert_close(got, exact, 3e-2)
    assert float(np.abs(np.asarray(got["o"]) - np.asarray(exact["o"])).max()) > 1e-4


def lowered_text(a):
    seg, state0 = jnp.asarray(a["seg"]), jnp.asarray(a["state0"])
    scan = jax.jit(lambda *args: gated_delta_chunked(*args, seg, state0, CHUNK, None))
    return scan.lower(*(jnp.asarray(a[k]) for k in NAMES[:5])).as_text(debug_info=True)


def test_off_is_the_jnp_body_bit_for_bit_and_auto_takes_it_on_a_cpu(monkeypatch):
    a = inputs(seams=(13,), state0=True)
    body = run(JNP, a)
    for mode in ("off", "auto"):
        monkeypatch.setattr(cells, "_PALLAS_MODE", mode)  # read while tracing
        got = gated()(*operands(a))
        for key in body:
            assert np.array_equal(np.asarray(body[key]), np.asarray(got[key])), (mode, key)
        assert "gdn_pallas" not in lowered_text(a)
    monkeypatch.setattr(cells, "_PALLAS_MODE", "interpret")
    assert "gdn_scan/gdn_pallas" in lowered_text(a)  # the kernel's scope, inside the scan's


def test_under_a_data_mesh_the_kernels_run_as_an_island(monkeypatch, devices):
    """Rows sharded over ``"data"``: outputs and every gradient as the
    jax.numpy body gives them on one device."""
    from tpu_rl.parallel import make_mesh

    a = inputs(b=4, seams=(13,), state0=True)
    monkeypatch.setattr(cells, "_PALLAS_MODE", "interpret")
    monkeypatch.setattr(cells, "_DATA_MESH", make_mesh(2))
    island = gated()
    assert "sdy.manual_computation" in island.lower(*operands(a)).as_text()
    assert_close(island(*operands(a)), run(JNP, a), 2e-6)
    # a batch that does not tile the mesh (init and act traces): no island
    monkeypatch.setattr(cells, "_DATA_MESH", make_mesh(8))
    assert "sdy.manual_computation" not in gated().lower(*operands(a)).as_text()


CELL = dict(b=2, hv=32, hk=16, dk=128, dv=128, Q=64)  # qwen3-next-80b-a3b
V5E, V5P, V4 = 128, 64, 16  # MiB of VMEM a core


@pytest.mark.parametrize("mode,platform,data,vmem,shape,want", [
    ("auto", "cpu", 1, V5E, CELL, (None, False)),
    ("auto", "tpu", 1, V5E, CELL, (8, False)),
    ("force", "tpu", 1, V5E, CELL, (8, False)),
    ("auto", "tpu", 2, V5E, CELL, (8, False)),  # an island over two chips
    ("auto", "tpu", 4, V5E, CELL, (None, False)),  # two rows do not tile four chips
    ("auto", "tpu", 1, V5P, CELL, (8, False)),
    ("auto", "tpu", 1, 32, {**CELL, "hv": 4, "hk": 2}, (4, False)),  # every head, where they are few
    ("auto", "tpu", 1, 32, CELL, (None, False)),  # the jax.numpy body where a tile of eight does not fit
    ("auto", "tpu", 1, V4, CELL, (None, False)),
    ("off", "tpu", 1, V5E, CELL, (None, False)),
    ("interpret", "cpu", 1, V5E, CELL, (8, True)),
    ("interpret", "cpu", 1, V4, dict(b=2, hv=4, hk=2, dk=8, dv=8, Q=8), (4, True)),  # any width
    ("auto", "tpu", 1, V5E, {**CELL, "Q": 8}, (None, False)),  # the tests' chunks
    ("auto", "tpu", 1, V5E, {**CELL, "dk": 64}, (None, False)),
    ("auto", "tpu", 1, V5E, {**CELL, "dv": 192}, (None, False)),
    ("auto", "tpu", 1, V5E, {**CELL, "hk": 32}, (8, False)),  # a key head a value head
    # a key head's group of value heads wider than a block
    ("auto", "tpu", 1, V5E, {**CELL, "hv": 48, "hk": 4}, (None, False)),
    ("auto", "tpu", 1, V5E, {**CELL, "hv": 12, "hk": 4}, (None, False)),  # no whole tile of o's heads
    ("auto", "tpu", 1, V5E, {**CELL, "hv": 16, "hk": 4}, (8, False)),  # blocks of whole groups
], ids=lambda v: "x".join(map(str, v.values())) if isinstance(v, dict) else str(v).replace(" ", ""))
def test_the_gate(monkeypatch, mode, platform, data, vmem, shape, want):
    monkeypatch.setattr(cells, "_PALLAS_MODE", mode)
    monkeypatch.setattr(cells, "_program_devices", lambda: (platform, data))
    monkeypatch.setattr(pallas_gdn, "_vmem_limit", lambda: 3 * vmem * 2**20 // 4)
    assert gated_delta._kernel_block(**shape) == want
    hb = want[0]
    if hb is not None and not want[1]:  # the kernels' need is inside what the call asks for
        need = pallas_gdn._vmem_bytes(
            hb, shape["dk"], shape["dv"], shape["hv"] // shape["hk"], shape["Q"])
        assert need <= 0.75 * vmem * 2**20


def test_the_need_is_counted_at_twice_what_mosaic_allocates():
    """13.50 MiB is the scoped allocation Mosaic reports for the backward's
    blocks and scratch at the cell's widths in bf16, 8 heads and 4 chunks a
    step, 17.48 MiB with its own spills (compiled for a described v5e at
    falling limits until it refused)."""
    assert pallas_gdn._GROUP == 4
    need = pallas_gdn._vmem_bytes(8, 128, 128, 2, 64, 2)
    assert need == pytest.approx(2 * 13.5 * 2**20, rel=0.01)


@pytest.mark.parametrize("size,base", [(64, 16), (8, 8), (8, 2), (32, 4)])
def test_the_triangles_inverse_inside_the_kernel(size, base, monkeypatch):
    """Substitution over the diagonal blocks, then float32 doublings, against
    the repeated squaring of the jax.numpy body and the definition; three
    heads in lockstep."""
    monkeypatch.setattr(pallas_gdn, "_BASE", base)
    rng = np.random.default_rng(size + base)
    n = jnp.tril(jnp.asarray(rng.standard_normal((3, size, size)), jnp.float32), -1) / 4

    def kernel(n_ref, out_ref, n_scr):
        for j, a in enumerate(pallas_gdn._unit_lower_inverses([n_ref[j] for j in range(3)], n_scr)):
            out_ref[j] = a

    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(n.shape, n.dtype),
        scratch_shapes=[pltpu.VMEM((3 * size, size), jnp.float32)], interpret=True)(n)
    eye = jnp.eye(size)
    with jax.default_matmul_precision("highest"):
        want = gated_delta._unit_lower_inverse(n)
        assert float(jnp.abs(got @ (eye + n) - eye).max()) <= 2e-5
    assert float(jnp.abs(got - want).max()) <= 2e-5 * max(1.0, float(jnp.abs(want).max()))
    assert not np.asarray(jnp.triu(got, 1)).any()  # lower triangular to the bit


def test_the_kernels_norm_is_the_bodys():
    assert pallas_gdn.L2_EPS == gated_delta.L2_EPS
