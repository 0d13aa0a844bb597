"""Kernels of the main path compiled for a described TPU v5e at the cells'
widths, without a chip: what Mosaic refuses (a slice off the tiling, too much
VMEM) the interpreter accepts, so the CPU tests of a kernel's arithmetic do not
see it. Nothing runs; no number comes out. One file on purpose: the process
that describes the topology holds the TPU library until it exits."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e!r}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows, tokens, width, groups", [
    (16384, 32768, 2560, 16), (12288, 16384, 2688, 8),
], ids=["smallthinker", "nemotron"])
def test_the_row_add_kernel_compiles_at_the_cells_shapes(one_chip, rows, tokens, width, groups):
    from tpu_rl.ops import pallas_moe

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(pallas_moe.row_add, donate_argnums=(0,)).lower(
            shaped((tokens * width // 128, 128), jnp.float32), shaped((rows, width), jnp.float32),
            shaped((rows,), jnp.int32), shaped((groups,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "moe_row_add" in text
    # the result is updated in place: no second copy of it among the temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * tokens * width
