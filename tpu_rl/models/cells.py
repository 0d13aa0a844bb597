"""Recurrent cells.

The LSTM core of the reference model zoo
(``/root/reference/networks/models.py:25-27``), re-architected for the MXU:

- the input projection for a whole sequence is ONE batched (B*S, in) x
  (in, 4H) matmul instead of a per-step concat matmul;
- the sequential part carries only the small (B, H) x (H, 4H) recurrent
  matmul, as a ``lax.scan`` — or, on TPU, as the fused Pallas kernel
  (``tpu_rl.ops.pallas_lstm``) that keeps the recurrent weights VMEM-resident
  for the entire sequence.

Kernel dispatch is controlled by :func:`set_pallas_mode`:
``"auto"`` (default) uses the kernel on TPU backends when the tile fits VMEM,
``"interpret"`` forces the kernel in interpreter mode (CPU tests),
``"off"`` always uses the scan. The choice is made from the devices the
traced PROGRAM runs on — the registered data mesh, else a plain
single-device jit on the default backend — never from how many chips the
host happens to have, and is readable from the lowered program
(``lstm_pallas`` / ``lstm_scan`` named scopes, ``utils.platform``).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

Carry = tuple[jax.Array, jax.Array]

_PALLAS_MODE = "auto"  # "auto" | "interpret" | "off" | "force"
# Data-parallel mesh registered by make_parallel_train_step: when set, the
# Pallas kernel runs as a shard_map island over the mesh's "data" axis (each
# device unrolls its local batch shard) instead of being disabled under GSPMD
# (the Mosaic custom call has no automatic SPMD partitioning rule).
_DATA_MESH = None


def set_pallas_mode(mode: str) -> None:
    """"auto": measured-win dispatch (kernel only where it beats the scan);
    "off": always scan; "interpret": kernel in interpreter mode (CPU tests);
    "force": real kernel wherever it FITS, ignoring the measured-win gate —
    benchmarking only (examples/bench_lstm_kernel.py times the raw kernel
    against the scan to re-derive the gate)."""
    assert mode in ("auto", "interpret", "off", "force"), mode
    global _PALLAS_MODE
    _PALLAS_MODE = mode


def set_data_mesh(mesh) -> None:
    """Register the learner's 1-D data mesh so LSTM unrolls trace the kernel
    inside shard_map. Call before the parallel train step is first traced
    (``parallel.dp.make_parallel_train_step`` does this); pass None to clear."""
    global _DATA_MESH
    _DATA_MESH = mesh


def _use_pallas(
    batch: int, seq: int, hidden: int, platform: str
) -> tuple[bool, bool]:
    """-> (use_kernel, interpret). ``batch`` is the per-device shard size
    and ``platform`` the platform of the devices the traced program runs
    on. The caller decides separately whether the call can be placed at all
    (a multi-device program needs the shard_map island)."""
    from tpu_rl.ops.pallas_lstm import batch_tile, bwd_batch_tile

    if _PALLAS_MODE == "off":
        return False, False
    if _PALLAS_MODE == "interpret":
        # Explicit test/debug override: always exercise the kernel (the
        # interpreter has no VMEM), so equivalence tests can never silently
        # degrade into scan-vs-scan.
        return True, True
    if platform != "tpu":
        return False, False
    if _PALLAS_MODE == "force":
        # Benchmark override: real kernel wherever a tiling fits.
        return batch_tile(batch, seq, hidden) is not None, False
    # Measured-win gate (bench_lstm_kernel.json): the fused kernel beats
    # the scan only when the WHOLE batch is one VMEM tile for both passes
    # (fwd+grad 1.06x at B128/H64, 1.33x at B256/H256). Multi-tile grids
    # starve the MXU (fwd+grad 0.90x at B1024/H1024) and no-tile-fits
    # shapes can't run at all — both keep the scan, whose per-step matmuls
    # always see the full batch. That record is host-clocked
    # (examples/bench_lstm_kernel.py) and no cell has re-measured it
    # (ROADMAP W4).
    return (
        batch_tile(batch, seq, hidden) == batch
        and bwd_batch_tile(batch, seq, hidden) == batch
    ), False


def _program_devices() -> tuple[str, int]:
    """(platform, data-axis width) of the program being traced: the
    registered data mesh when there is one (``make_parallel_train_step``,
    the colocated/sebulba programs), else a plain ``jax.jit`` — one device
    of the default backend, however many chips the host has."""
    mesh = _DATA_MESH
    if mesh is None:
        return jax.default_backend(), 1
    from tpu_rl.parallel.mesh import DATA_AXIS

    return mesh.devices.flat[0].platform, mesh.shape.get(DATA_AXIS, 1)


class LSTMCell(nn.Module):
    """Standard LSTM with torch ``nn.LSTMCell`` gate semantics
    (i, f, g, o; ``c' = sig(f)*c + sig(i)*tanh(g)``; ``h' = sig(o)*tanh(c')``).

    Exposes single-step ``__call__`` (worker act path) and full-sequence
    ``unroll`` (training path) over one parameter set: ``x_proj`` (input
    projection + bias) and ``recurrent_kernel`` (H, 4H).
    """

    hidden: int
    # Matmul compute dtype (params stay float32): jnp.bfloat16 runs the
    # input projection and the recurrent matmul at MXU bf16 rate with f32
    # accumulation — in BOTH passes (the recurrent matmul goes through
    # pallas_lstm.mixed_dot, whose custom VJP casts the cotangent too; a
    # plain bf16 dot's backward receives an f32 cotangent and runs mixed
    # f32 x bf16 at f32 rate, which measured as zero bf16 speedup on the
    # round-4 wide-LSTM row). Gates, carry, and outputs stay float32.
    # None = float32. The fused Pallas kernel is f32-only — bf16 compute
    # always takes the scan path (the MXU-loading wide shapes are
    # multi-tile, where the scan is the measured winner anyway; see
    # _use_pallas).
    dtype: jnp.dtype | None = None

    def setup(self):
        self.x_proj = nn.Dense(4 * self.hidden, name="x_proj", dtype=self.dtype)
        self.recurrent_kernel = self.param(
            "recurrent_kernel",
            nn.initializers.lecun_normal(),
            (self.hidden, 4 * self.hidden),
        )

    def _rec_matmul(self, h: jax.Array) -> jax.Array:
        if self.dtype is None:
            return h @ self.recurrent_kernel
        from tpu_rl.ops.pallas_lstm import mixed_dot

        return mixed_dot(h, self.recurrent_kernel, self.dtype)

    def _gates(self, z: jax.Array, c: jax.Array) -> tuple[jax.Array, jax.Array]:
        H = self.hidden
        i, f, g, o = (
            z[..., :H],
            z[..., H : 2 * H],
            z[..., 2 * H : 3 * H],
            z[..., 3 * H :],
        )
        c2 = nn.sigmoid(f) * c + nn.sigmoid(i) * jnp.tanh(g)
        h2 = nn.sigmoid(o) * jnp.tanh(c2)
        return h2, c2

    def __call__(self, carry: Carry, x: jax.Array) -> tuple[Carry, jax.Array]:
        h, c = carry
        z = self.x_proj(x).astype(jnp.float32) + self._rec_matmul(h)
        h2, c2 = self._gates(z, c)
        return (h2, c2), h2

    def unroll(
        self,
        x: jax.Array,
        carry0: Carry,
        firsts: jax.Array,
        reset_on_first: bool,
    ) -> tuple[Carry, jax.Array]:
        """x (B, S, in), carry0 ((B,H),(B,H)), firsts (B, S, 1) ->
        (final carry, hs (B, S, H))."""
        B, S = x.shape[0], x.shape[1]
        xp = self.x_proj(x)  # one big MXU matmul for every timestep
        keep = (
            1.0 - firsts[..., 0]
            if reset_on_first
            else jnp.ones((B, S), x.dtype)
        )

        platform, n_data = _program_devices()
        tiles = B % n_data == 0
        use_kernel, interpret = _use_pallas(
            B // n_data if tiles else B, S, self.hidden, platform
        )
        if not tiles:
            # A multi-device program whose batch does not tile the mesh
            # (init/act traces): no island, and an unwrapped Mosaic call has
            # no GSPMD partitioning rule — only the interpreter may run.
            use_kernel, n_data = use_kernel and interpret, 1
        if self.dtype is not None and _PALLAS_MODE != "interpret":
            # bf16 compute: the f32-only fused kernel would first cast its
            # operands up, forfeiting the MXU-rate win that motivated bf16 —
            # the mixed-precision scan is the right path. (interpret mode
            # still exercises the kernel for equivalence tests; it casts to
            # f32 explicitly below.)
            use_kernel = False
        if use_kernel:
            from tpu_rl.ops.pallas_lstm import lstm_unroll

            args = (
                xp.astype(jnp.float32),
                self.recurrent_kernel.astype(jnp.float32),
                carry0[0].astype(jnp.float32),
                carry0[1].astype(jnp.float32),
                keep.astype(jnp.float32),
            )
            if n_data > 1:
                from jax.sharding import PartitionSpec as P

                from tpu_rl.parallel.mesh import DATA_AXIS

                def _local_unroll(xp_, wh_, h0_, c0_, keep_):
                    return lstm_unroll(xp_, wh_, h0_, c0_, keep_, interpret)

                bspec = P(DATA_AXIS)  # shard every operand's leading (batch) dim
                hs, cs = jax.shard_map(
                    _local_unroll,
                    mesh=_DATA_MESH,
                    in_specs=(bspec, P(), bspec, bspec, bspec),
                    out_specs=(bspec, bspec),
                    # No collectives inside; pallas out_shapes carry no vma
                    # annotations, so varying-axis checking must be off.
                    check_vma=False,
                )(*args)
            else:
                hs, cs = lstm_unroll(*args, interpret)
            return (hs[:, -1], cs[:, -1]), hs

        # Scan fallback shares ONE implementation of the step math with the
        # custom_vjp primal (pallas_lstm._scan_forward), so the auto-mode
        # non-AD path and the "off" path can never diverge bit-wise.
        from tpu_rl.ops.pallas_lstm import _scan_forward

        hs, (h_last, c_last) = _scan_forward(
            xp, self.recurrent_kernel, carry0[0], carry0[1], keep,
            matmul_dtype=self.dtype,
        )
        return (h_last, c_last), hs

    @staticmethod
    def zero_carry(hidden: int, batch_shape: tuple[int, ...] = ()) -> Carry:
        z = jnp.zeros((*batch_shape, hidden), jnp.float32)
        return (z, z)
