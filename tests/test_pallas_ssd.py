"""The Pallas kernels of the Mamba-2 scan (``tpu_rl/ops/pallas_ssd.py``) in the
interpreter against the ``jnp`` body of ``ssd_chunked`` — the oracle and the
CPU's path: outputs and every gradient in float32 over the seams, carried
states and widths the kernels special-case, one case in bf16, and the gate."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_rl.models import cells, mamba2
from tpu_rl.models.mamba2 import ssd_chunked
from tpu_rl.ops import pallas_ssd

CHUNK = 8
NAMES = ("x", "dt", "A", "B", "C", "D", "state0")


def inputs(T=32, h=8, p=16, g=1, n=16, seams=(), state0=False, b=2, seed=0):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    firsts = np.zeros((b, T), np.int32)
    firsts[:, list(seams)] = 1
    return dict(
        x=f32(b, T, h, p), dt=0.3 * np.log1p(np.exp(f32(b, T, h))),
        A=-np.exp(0.3 * f32(h)), B=f32(b, T, g, n), C=f32(b, T, g, n), D=f32(h),
        seg=np.cumsum(firsts, axis=1).astype(np.int32),
        state0=f32(b, h, p, n) * (1.0 if state0 else 0.0),
    )


JNP = (None, False)  # ``ssd_chunked(kernel=...)``: the jnp body; (heads a grid step, True): the kernels


def value_and_grads(x, dt, A, B, C, D, state0, seg, w_y, w_last, *, dtype, kernel):
    """Outputs and the gradients of a weighted sum of them."""

    def f(x, dt, A, B, C, D, state0):
        y, last = ssd_chunked(x, dt, A, B, C, D, seg, state0, CHUNK, dtype, kernel=kernel)
        return jnp.sum(y * w_y) + jnp.sum(last * w_last), (y, last)

    (_, outs), grads = jax.value_and_grad(f, argnums=tuple(range(7)), has_aux=True)(
        x, dt, A, B, C, D, state0)
    return {"y": outs[0], "last": outs[1], **{f"d{k}": v for k, v in zip(NAMES, grads)}}


@functools.cache
def program(kernel, dtype):
    """One jitted program per form of the scan: cases of one shape share its
    compilation. ``kernel`` None: the gate chooses, while the program is traced."""
    return jax.jit(functools.partial(value_and_grads, dtype=dtype, kernel=kernel))


def operands(a):
    rng = np.random.default_rng(9)
    weights = [rng.standard_normal(a[k].shape).astype(np.float32) for k in ("x", "state0")]
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
    "two-seams-in-one-chunk": dict(seams=(9, 14)),
    "state0-whose-episode-ends-in-chunk-0": dict(seams=(5,), state0=True),
    "state0-whose-episode-runs-through": dict(state0=True),
    "window-no-multiple-of-the-chunk": dict(T=27, seams=(11,), state0=True),
    "two-groups": dict(g=2, seams=(13,), state0=True),
    "fewer-heads-a-step-than-heads": dict(seams=(13, 24), state0=True, hb=2),
    "a-head-block-inside-a-group": dict(g=2, seams=(6,), state0=True, hb=2),
    "head-blocks-of-whole-groups": dict(g=4, seams=(21,), state0=True, hb=4),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_kernels_match_the_jnp_body_in_float32(case):
    case = dict(case)
    hb = case.pop("hb", None)
    a = inputs(**case)
    want = run(JNP, a)
    got = run((hb or a["x"].shape[2], True), a)
    assert set(got) == {"y", "last"} | {f"d{k}" for k in NAMES}
    assert_close(got, want, 2e-6)
    if case.get("seams") == (0,):  # nothing of state0 survives a seam at step 0
        assert not np.asarray(got["dstate0"]).any()
    elif case.get("state0"):
        assert np.asarray(got["dstate0"]).any()


def test_kernels_match_the_jnp_body_in_bfloat16():
    """The cell's precision: bf16 operands at the same matmuls, so the two
    forms differ by the cotangent's rounding and the order of float32 sums;
    against the float32 body, by bf16's 2^-8 an operand."""
    a = inputs(seams=(13, 14), state0=True, seed=3)
    got = run((8, True), a, jnp.bfloat16)
    same = run(JNP, a, jnp.bfloat16)
    assert_close({k: got[k] for k in ("y", "last")}, {k: same[k] for k in ("y", "last")}, 2e-3)
    assert_close(got, same, 8e-3)
    exact = run(JNP, a)
    assert_close(got, exact, 3e-2)
    assert float(np.abs(np.asarray(got["y"]) - np.asarray(exact["y"])).max()) > 1e-4


def lowered_text(a):
    seg, state0 = jnp.asarray(a["seg"]), jnp.asarray(a["state0"])
    scan = jax.jit(lambda *args: ssd_chunked(*args, seg, state0, CHUNK, None))
    return scan.lower(*(jnp.asarray(a[k]) for k in NAMES[:6])).as_text(debug_info=True)


def test_off_is_the_jnp_body_bit_for_bit_and_auto_takes_it_on_a_cpu(monkeypatch):
    a = inputs(seams=(13,), state0=True)
    body = run(JNP, a)
    for mode in ("off", "auto"):
        monkeypatch.setattr(cells, "_PALLAS_MODE", mode)  # read while tracing
        got = gated()(*operands(a))
        for key in body:
            assert np.array_equal(np.asarray(body[key]), np.asarray(got[key])), (mode, key)
        assert "ssd_pallas" not in lowered_text(a)
    monkeypatch.setattr(cells, "_PALLAS_MODE", "interpret")
    assert "ssd_scan/ssd_pallas" in lowered_text(a)  # the kernel's scope, inside the scan's


def test_under_a_data_mesh_the_kernels_run_as_an_island(monkeypatch, devices):
    """Rows sharded over ``"data"``, ``A`` and ``D`` replicated: outputs and
    every gradient as the jnp body gives them on one device."""
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


CELL = dict(b=2, h=64, p=64, g=1, n=128, Q=256)  # granite-4.0-h-micro
V5E, V5P, V4 = 128, 64, 16  # MiB of VMEM a core


@pytest.mark.parametrize("mode,platform,data,vmem,shape,want", [
    ("auto", "cpu", 1, V5E, CELL, (None, False)),
    ("auto", "tpu", 1, V5E, CELL, (32, False)),
    ("force", "tpu", 1, V5E, CELL, (32, False)),
    ("auto", "tpu", 2, V5E, CELL, (32, False)),  # an island over two chips
    ("auto", "tpu", 4, V5E, CELL, (None, False)),  # two rows do not tile four chips
    ("auto", "tpu", 1, V5P, CELL, (16, False)),  # fewer rows where VMEM is smaller
    ("auto", "tpu", 1, V4, CELL, (4, False)),
    ("auto", "tpu", 1, 2, CELL, (None, False)),  # and the jnp body where none fits
    ("off", "tpu", 1, V5E, CELL, (None, False)),
    ("interpret", "cpu", 1, V5E, CELL, (32, True)),
    ("interpret", "cpu", 1, V4, dict(b=2, h=8, p=16, g=2, n=16, Q=8), (8, True)),  # any width
    ("auto", "tpu", 1, V5E, {**CELL, "Q": 8}, (None, False)),  # the tests' chunks
    ("auto", "tpu", 1, V5E, {**CELL, "n": 64}, (None, False)),
    ("auto", "tpu", 1, V5E, {**CELL, "p": 24}, (None, False)),  # rows that split a bf16 sublane group
    ("auto", "tpu", 1, V5E, {**CELL, "g": 8}, (32, False)),  # four groups of 8 heads a block
    ("auto", "tpu", 1, V5E, dict(b=2, h=24, p=64, g=8, n=128, Q=128), (24, False)),
    ("auto", "tpu", 1, V5E, dict(b=2, h=6, p=16, g=1, n=128, Q=128), (None, False)),  # 96 rows
], ids=lambda v: str(v).replace(" ", "") if not isinstance(v, dict) else "x".join(map(str, v.values())))
def test_the_gate(monkeypatch, mode, platform, data, vmem, shape, want):
    monkeypatch.setattr(cells, "_PALLAS_MODE", mode)
    monkeypatch.setattr(cells, "_program_devices", lambda: (platform, data))
    monkeypatch.setattr(pallas_ssd, "_vmem_limit", lambda: 3 * vmem * 2**20 // 4)
    assert mamba2._ssd_kernel_block(**shape) == want
    hb = want[0]
    if hb is not None and not want[1]:  # what the kernels then need is inside what the call asks for
        need = pallas_ssd._vmem_bytes(hb, shape["p"], shape["n"], shape["Q"],
                                      max(1, hb * shape["g"] // shape["h"]))
        assert need <= 0.75 * vmem * 2**20


def test_the_need_is_counted_at_twice_what_mosaic_allocates():
    """26.95 MiB is the scoped allocation Mosaic reports for the backward at
    the cell's widths, 32 heads a step (compiled for a described v5e)."""
    assert pallas_ssd._vmem_bytes(32, 64, 128, 256, 1) == pytest.approx(2 * 26.95 * 2**20, rel=0.03)
