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


@pytest.mark.parametrize("rows, T, heads, kv_heads, D, window, band", [
    (2, 16384, 28, 4, 128, None, 136), (2, 16384, 28, 4, 128, 4096, 70),
    (1, 16384, 20, 20, 256, None, 136), (2, 8192, 16, 2, 256, None, 36),
], ids=["smallthinker-global", "smallthinker-window", "glm", "qwen3-next"])
def test_the_attention_backward_compiles_at_the_cells_shapes(
        one_chip, rows, T, heads, kv_heads, D, window, band):
    """Forward (the library's kernel on traced masks) and the repo's own
    backward over the band (``ops/pallas_attn_bwd.py``) for a v5e: a head's
    float32 dq, a key head's dk / dv scratch and the blocks of a step have to
    fit the VMEM the call asks for, and a head is a column block of the
    gradients' ``(T, heads * D)``. No dq-partial buffer: the temporaries of two
    rows stay under one row's partials of the library's backward."""
    from tpu_rl.parallel import sequence

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def grads(q, k, v, seg):
        def loss(q, k, v):
            out = sequence._splash_mha(
                q, k, v, seg, causal=True, scale=D ** -0.5,
                block_sizes=sequence._splash_block_sizes(T), window=window)
            return out.astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        lowered = jax.jit(grads).lower(
            shaped((rows, T, heads, D), jnp.bfloat16), shaped((rows, T, kv_heads, D), jnp.bfloat16),
            shaped((rows, T, kv_heads, D), jnp.bfloat16), shaped((rows, T), jnp.int32))
        text = lowered.as_text()
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    assert text.count("tpu_custom_call") >= 2 and "attn_bwd_band" in text  # forward, backward
    assert f"tensor<{band}xi32>" in text  # the step list: as long as the static band
    partials = T // 1024 * heads * T * D * 2  # what one row's dq partials took; two rows declare it
    assert compiled.memory_analysis().temp_size_in_bytes < rows * partials
