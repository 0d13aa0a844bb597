"""The ``tf-longctx`` update program compiled for a described TPU v5e, for one
chip and for four (``on-chip-measurement`` guide, section 2): what the chip's
compiler would refuse costs no chip time here. Nothing runs, so nothing here
is a measurement. The code under test asks ``jax.default_backend()`` only when
no mesh is registered, so both cases go through ``make_parallel_train_step``
over a mesh of described devices (the one-chip learner jits the same step
without a mesh: same kernels, no island)."""

import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks import flops, harness


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e: {e!r}")


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    and cannot be read back without a chip."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.mark.parametrize("traffic, chips", [("learner", 1), ("dp4", 4)])
def test_update_program_compiles_for_v5e(v5e, traffic, chips):
    from tpu_rl.algos.registry import get_algo
    from tpu_rl.config import Config
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.parallel.dp import make_parallel_train_step
    from tpu_rl.types import Batch

    config = harness.load_json(f"{harness.HERE}/configs/tf-longctx.json")
    mix = harness.load_json(f"{harness.HERE}/traffic/{traffic}.json")
    cfg = Config.from_dict({**config["params"], **mix.get("params", {}),
                            "mesh_data": chips})
    assert cfg.batch_size == chips * config["params"]["batch_size"]  # same per chip
    mesh = Mesh(np.asarray(v5e.devices[:chips]), ("data",))
    _, state, step = get_algo(cfg.algo).build(cfg, jax.random.key(0))
    lay = BatchLayout.from_config(cfg)
    batch = jax.eval_shape(lambda: Batch.zeros(
        cfg.batch_size, cfg.seq_len, cfg.obs_shape, cfg.action_space,
        cfg.hidden_size, hx_width=lay.hx, cx_width=lay.cx))
    rs, bs = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    shaped = lambda tree, s: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree)
    compiled = make_parallel_train_step(step, mesh, cfg).lower(
        shaped(jax.eval_shape(lambda: state), rs), shaped(batch, bs),
        shaped(jax.eval_shape(lambda: jax.random.key(1)), rs),
    ).compile()
    text = compiled.as_text()
    # flash attention forward, dq and dkv for each of the four layers
    assert text.count("tpu_custom_call") == 3 * cfg.n_layers
    assert bool(re.search(r"all-reduce", text)) == (chips > 1)
    mem = compiled.memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes + mem.output_size_in_bytes
    hbm = flops.peaks("TPU v5 lite")["hbm_bytes"]
    assert 0.25 * hbm < used < 0.85 * hbm  # fills the chip like a deployment, and fits
