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
    (2, 8192, 32, 32, 256, None, 36),  # 192-wide q, k and 128-wide v, padded to one size
], ids=["smallthinker-global", "smallthinker-window", "glm", "qwen3-next", "ling-flash"])
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


def test_the_two_resolution_read_compiles_at_evabytes_shapes(one_chip, monkeypatch):
    """An EVA layer's two calls at 32 heads of 128 over 16,384 steps, forward
    and backward, for a v5e: the rows' walk on the (episode, block) ids with
    the logsumexp as an output (``flash_attention_lse``) and the summaries'
    read on a (16,384, 1,024) rectangle with key-side ids of its own and a
    reach a query (``summary_attention_lse``); both backwards are
    ``attn_bwd_band``, the second over a key side of another length. The
    program's platform is steered here, as a cell's compile test steers it."""
    import types

    from jax.experimental.pallas import tpu as pltpu

    from tpu_rl.models import cells
    from tpu_rl.parallel import sequence

    monkeypatch.setattr(cells, "_program_devices", lambda: ("tpu", 1))
    monkeypatch.setattr(
        pltpu, "get_tpu_info", lambda: types.SimpleNamespace(vmem_capacity_bytes=128 * 2**20))
    T, N, heads, D = 16384, 1024, 32, 128

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def grads(q, k, v, ks, vs, seg, seg_k, reach):
        def loss(q, k, v, ks, vs):
            pos = jnp.broadcast_to(jnp.arange(T), (1, T))
            o_e, lse_e = sequence.flash_attention_lse(q, k, v, pos, seg, sm_scale=D ** -0.5)
            o_s, lse_s = sequence.summary_attention_lse(q, ks, vs, seg, seg_k, reach, D ** -0.5)
            both = o_e.astype(jnp.float32).sum() + o_s.astype(jnp.float32).sum()
            return both + jnp.logaddexp(lse_e, lse_s).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, ks, vs)

    step, summary = shaped((1, T, heads, D), jnp.bfloat16), shaped((1, N, heads, D), jnp.bfloat16)
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        lowered = jax.jit(grads).lower(
            step, step, step, summary, summary, shaped((1, T), jnp.int32),
            shaped((1, N), jnp.int32), shaped((1, T), jnp.int32))
        text = lowered.as_text()
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    assert text.count("tpu_custom_call") >= 4 and text.count("attn_bwd_band") >= 2
    # the two step lists: the square band's 136 tiles, the rectangle's 16
    assert "tensor<136xi32>" in text and "tensor<16xi32>" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


def test_the_per_channel_delta_rules_pair_compiles_at_lings_shapes(one_chip, monkeypatch):
    """``kda_chunked`` at ling-3.0-flash-vl's widths (1 x 8,192 steps, 32
    heads of 128, bf16, chunks of 64), value and all six gradients, for a v5e,
    the program's platform and the chip's own VMEM reading steered as the
    cell's compile test steers them: the gate takes the Pallas pair
    (``ops/pallas_kda.py``) eight heads a grid step, the program holds its two
    Mosaic calls (the differentiated forward, the backward) under the scopes
    the cell's readers use and no ``while`` (the jax.numpy body's walk over
    spans), and the kernels' blocks, scratch and spills fit the call when it
    asks for no more than the gate counted (``_vmem_bytes`` at float32
    operands, 51.2 MiB; Mosaic's own allocation with bf16 ones is 22.60)."""
    import types

    from jax.experimental.pallas import tpu as pltpu

    from tpu_rl.models import cells
    from tpu_rl.ops import kda, pallas_kda
    from tpu_rl.utils.platform import program_paths

    monkeypatch.setattr(cells, "_program_devices", lambda: ("tpu", 1))
    monkeypatch.setattr(
        pltpu, "get_tpu_info", lambda: types.SimpleNamespace(vmem_capacity_bytes=128 * 2**20))
    B, T, H, D, Q = 1, 8192, 32, 128, 64
    assert kda._kernel_block(B, H, D, D, Q) == (8, False)
    need = pallas_kda._vmem_bytes(8, D, D, Q)
    assert need <= pallas_kda._vmem_limit()
    monkeypatch.setattr(pallas_kda, "_vmem_limit", lambda: need)  # what the calls ask for

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def graded(q, k, v, g, beta, state0, seg):
        def loss(q, k, v, g, beta, state0):
            o, last = kda.kda_chunked(q, k, v, g, beta, seg, state0, Q, jnp.bfloat16)
            return jnp.square(o).sum() + last.sum()
        return jax.value_and_grad(loss, argnums=tuple(range(6)))(q, k, v, g, beta, state0)

    step = shaped((B, T, H, D), jnp.bfloat16)
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        lowered = jax.jit(graded).lower(
            step, step, step, shaped((B, T, H, D), jnp.float32), shaped((B, T, H), jnp.float32),
            shaped((B, H, D, D), jnp.float32), shaped((B, T), jnp.int32))
        paths = program_paths(lowered)
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    assert {"kda_scan", "kda_pallas"} <= set(paths["paths"]) and paths["mosaic_calls"] == 2
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 and "kdelta_fwd_res" in text and "kdelta_bwd" in text
    assert " while(" not in text
    # the residuals (a state every fourth chunk and each chunk's A, 64 MiB each), o, the
    # gradients and the re-laid small operands: no (b, nc, h, Q, d_k) float32 array of the
    # jax.numpy spans (256 MiB each)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.75 * 2**30


def test_the_chunk_poolings_backward_holds_no_scatter_at_evabytes_shapes(one_chip):
    """An EVA layer's chunk pooling (``EvaAttention.summaries`` under the
    mixer's scope ``eva_pool``) at 32 heads of 128 over 16,384 steps in bf16,
    value and gradients of k, v and the two pooling vectors, for a v5e. The
    members reach the chunks by a gather; their gradients come back by its
    inverse (``_summaries_bwd``), so the program holds no scatter — the
    transpose of the gather was one, which XLA ran as a loop over the 1,024
    spans —, every op of the backward lies under a scope named ``eva_pool``
    (what ``kernel.eva_pool_ms_per_update`` and ``eva_pool_roofline`` read),
    and no member is gathered again: the temporaries stay under the parent
    commit's, which kept both tensors' members for its backward."""
    import re

    from tpu_rl.models.evabyte import EvaAttention, episode_grid

    T, heads, D, block, chunk = 16384, 32, 128, 2048, 16
    mixer = EvaAttention(
        hidden=heads * D, heads=heads, block=block, chunk=chunk, rope_theta=1e5,
        init_std=0.01275, dtype=jnp.bfloat16)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def pooled(pools, k, v, seg):
        _, blk, _, ends = episode_grid(seg, block, chunk)
        with jax.named_scope("eva_pool"):
            ks, vs, _, _ = mixer.apply({"params": pools}, k, v, seg, blk, ends, method="summaries")
        return ks.astype(jnp.float32).sum() + jnp.square(vs.astype(jnp.float32)).sum()

    step = shaped((1, T, heads, D), jnp.bfloat16)
    pools = {name: shaped((heads, D), jnp.float32) for name in ("pool_k", "pool_v")}
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        lowered = jax.jit(jax.value_and_grad(pooled, argnums=(0, 1, 2))).lower(
            pools, step, step, shaped((1, T), jnp.int32))
        text, named = lowered.as_text(), lowered.as_text(debug_info=True)
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    assert "scatter" not in text and "stablehlo.gather" in text
    # the backward's ops: under the transposed forward scope; each under the rule's own too
    backward = [name for name in re.findall(r'loc\("([^"]*)"', named)
                if "transpose(jvp(eva_pool))" in name]
    assert len(backward) > 10
    assert all("eva_pool" in name.replace("transpose(jvp(eva_pool))", "") for name in backward)
    # 135,250,432 B at the parent commit (f94f406) for this function: the members of k and
    # of v, 2 x 64 MiB, gathered again for the backward
    assert compiled.memory_analysis().temp_size_in_bytes <= 135_250_432


def test_the_update_tail_is_one_pass_over_the_weights(one_chip):
    """The tail of the on-policy update as ``algos/ppo.py`` writes it — clip,
    guard, RMSprop, apply and ``learn_diag``'s norms — on a PPO ``TrainState``
    of four catalog-sized float32 leaves (448 MiB), state donated, for a v5e:
    the gradients are read once for the clip's and the groups' sums and once
    more in the one pass that reads g, nu, p and writes nu', p' (six tree
    reads and writes in all). A conditional, or anything else that stands
    between that pass and the values that read the weights, brings back the
    clipped gradients written out, the sums as passes of their own and a copy
    of every donated parameter."""
    import numpy as np
    import optax

    from tpu_rl.algos.base import TrainState, rmsprop
    from tpu_rl.config import Config
    from tpu_rl.heal.guards import guarded, update_ok
    from tpu_rl.obs.learn import update_scalars
    from tpu_rl.ops.losses import clip_subtree_by_global_norm

    cfg = Config()
    assert cfg.update_guard and cfg.learn_diag  # what every cell runs
    opt = rmsprop(cfg)

    def tail(state, raw, loss):
        params0 = state.params
        grads, gnorm, scale = clip_subtree_by_global_norm(raw, cfg.max_grad_norm)
        ok = update_ok(loss, gnorm)

        def _apply():
            updates, opt_state = opt.update(grads, state.opt_state, state.params)
            return optax.apply_updates(state.params, updates), opt_state

        params, opt_state = guarded(ok, _apply, (state.params, state.opt_state))
        state = state.replace(params=params, opt_state=opt_state, step=state.step + 1)
        return state, (gnorm, update_scalars(raw, scale, state.params, params0))

    shapes = {"actor": {
        "body": {"kernel": (8192, 4096)}, "cell": {"kernel": (16, 2048, 1024)},
        "experts": {"w_in": (8, 2048, 1536)}, "pi_head": {"kernel": (2048, 12288)},
    }}
    params = jax.tree.map(lambda s: jnp.zeros(s, jnp.float32), shapes,
                          is_leaf=lambda s: isinstance(s, tuple))
    state = jax.eval_shape(lambda p: TrainState(
        step=jnp.zeros((), jnp.int32), params=p, opt_state=opt.init(p)), params)
    tree = sum(4 * int(np.prod(x.shape)) for x in jax.tree.leaves(state.params))
    assert tree == 448 * 2**20

    def shaped(t):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), t)

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(tail, donate_argnums=(0,)).lower(
            shaped(state), shaped(state.params),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    text = compiled.as_text()
    assert " conditional(" not in text
    leaves = {f"f32[{','.join(map(str, x.shape))}]" for x in jax.tree.leaves(state.params)}
    assert len(leaves) == 4

    def with_a_leaf(op):  # the lines of such an op that write a parameter-sized array
        return [ln for ln in text.splitlines() if f" {op}(" in ln and any(
            leaf in ln.split(f" {op}(")[0] for leaf in leaves)]

    assert not with_a_leaf("copy")
    # one fusion a leaf writes p' and nu' (with the two sums of the diagnostics);
    # the only other fusions are the four that sum the raw gradients' squares
    assert len(with_a_leaf("fusion")) == 4 and text.count(" fusion(") == 8
    assert compiled.memory_analysis().temp_size_in_bytes < tree / 10
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    # 6 by count; XLA's own staging of a gradient leaf through fast memory reads
    # as up to 1.75 more (6.9-7.75 over leaf sets). 15.4 behind a ``lax.cond``.
    assert 6.0 <= cost["bytes accessed"] / tree < 8.5
