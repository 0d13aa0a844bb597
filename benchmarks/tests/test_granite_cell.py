"""The ``granite-4.0-h-micro.learner`` cell rehearsed end to end on the CPU at
tiny widths, through ``run.main`` and the real data files (the device check
replaced, as in ``test_runners.py``); its four metric readers on a trace made
by hand; and its update program compiled for a described TPU v5e at the
published widths. What comes out is control flow, counts and a compiler's
verdict, never a device number."""

import json
import types

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks import flops, harness, run, trace

CELL = "granite-4.0-h-micro.learner"
TINY_ARCH = dict(
    hidden_size=64, layer_types=["mamba", "attention", "mamba"], rms_norm_eps=1e-5,
    intermediate_size=96, residual_multiplier=0.22, embedding_multiplier=12,
    logits_scaling=8, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
    mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=8,
    mamba_conv_bias=True, mamba_proj_bias=False, num_attention_heads=4,
    num_key_value_heads=2, attention_multiplier=0.0625, attention_bias=False,
)
TINY = [f"params.arch={json.dumps(TINY_ARCH)}", "params.seq_len=32", "params.obs_shape=[6]",
        "params.action_space=3", 'params.compute_dtype="float32"',
        "windows.pool=8", "windows.episode_len_mean=9",
        "trace.start_update=4", "trace.updates=4"]
BENCH = harness.load_json(f"{harness.ROOT}/BENCHMARK.json")
NEW = {"kernel.ssd_ms_per_update", "ssd_scan_roofline", "step.hybrid_mfu",
       "step.opt_ms_per_update"}


@pytest.fixture
def any_device(monkeypatch):
    monkeypatch.setattr(harness, "check_device", lambda *a: None)


def result_line(capsys, trace: int, seconds: float) -> dict:
    argv = ["--workload", CELL, "--seed", str(2**31 + 11), "--seconds", str(seconds),
            "--trace", str(trace)]
    for item in TINY:
        argv += ["--set", item]
    assert run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_rehearsal(any_device, capsys):
    line = result_line(capsys, trace=0, seconds=4)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"transitions_per_s", "setup_s"} <= set(line["metrics"])
    checks = line["checks"]
    assert not checks["device"] and not line["correct"]  # a CPU is never correct
    assert checks["parity"] and checks["losses_finite"] and checks["no_compile_in_window"]
    assert not checks["kernel_path"]  # no Mosaic kernel on a CPU: attn_full, not flash
    assert line["parity"]["err"]["logits"] < 1e-4  # float32 against the reference


def test_a_traced_rehearsal_leaves_out_what_it_cannot_price(any_device, capsys):
    """No TPU plane in a CPU capture: every device-trace reader finds nothing.
    The spans and counters are read, and the two accepted readers that cannot
    price this model are not even asked."""
    line = result_line(capsys, trace=1, seconds=6)
    got = set(line["metrics"])
    assert {"feed.wait_share", "feed.h2d_bytes_per_update"} <= got
    assert line["metrics"]["feed.h2d_bytes_per_update"]["value"] == 2 * 32 * (6 + 3 + 6) * 4
    assert not {"step.mfu", "attn_flash_roofline"} & got
    assert not NEW & got and "breakdown" not in line


def hand_made_trace(scoped: bool = True) -> trace.Trace:
    """Four executions of a 590 ms update program, 600 ms apart: a window of
    two periods. Each holds 60 + 30 ms under the scan's two scopes, 1 ms under
    ``opt_update`` and a 30 ms conditional with no name stack whose one inner
    fusion is named for the diagnostics."""
    ms = 1e6
    modules = [trace.Event("jit_train_step", i * 600 * ms, 590 * ms) for i in range(4)]
    ops = []
    for i in range(4):
        t0 = i * 600 * ms
        stack = "jit(train_step)/transpose(jvp(layer3))/mamba/" if scoped else "jit(train_step)/"
        ops += [
            trace.Event("fusion.1", t0, 60 * ms, stack + "ssd_scan/dot_general:" if scoped else stack),
            trace.Event("fusion.2", t0 + 100 * ms, 30 * ms, stack + "ssd_conv/mul:" if scoped else stack),
            trace.Event("fusion.3", t0 + 200 * ms, 1 * ms,
                        "jit(train_step)/opt_update/reduce_sum:" if scoped else stack),
        ]
        if scoped:
            ops += [
                trace.Event("cond.251", t0 + 300 * ms, 30 * ms, "", "conditional"),
                trace.Event("fusion.4", t0 + 301 * ms, 20 * ms, "jit(train_step)/reduce_sum:", "loop fusion"),
            ]
    return trace.Trace([trace.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)])


def test_the_new_readers_on_a_trace_made_by_hand():
    config = harness.load_json(f"{harness.HERE}/configs/granite-4.0-h-micro.json")
    spec = types.SimpleNamespace(params=config["params"], traffic={})

    def run_with(tr):
        return types.SimpleNamespace(trace=tr, spec=spec, transitions_per_update=8192,
                                     device={"kind": "TPU v5 lite"})

    tr = hand_made_trace()
    assert tr.n_steps == 2 and tr.window_s == pytest.approx(1.2)
    got = {}
    for name in NEW:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        reader = harness.load_module(f"{harness.HERE}/metrics/{name}.py")
        got[name] = reader.read(run_with(tr))
        assert reader.read(run_with(None)) is None  # an untraced run
        if "mfu" not in name:  # a program without the scope (the parent's): nothing to read
            assert reader.read(run_with(hand_made_trace(scoped=False))) is None
    assert got["kernel.ssd_ms_per_update"] == pytest.approx(90.0)
    assert got["step.opt_ms_per_update"] == pytest.approx(31.0)  # 1 ms scoped + the conditional
    share, extra = got["ssd_scan_roofline"]
    assert extra == {"bound": "memory"} and share == pytest.approx(100 * 13.758 / 90, rel=1e-3)
    # 38.05 TFLOP per update, 2 updates in 1.2 s, over 197 TFLOP/s
    assert got["step.hybrid_mfu"] == pytest.approx(100 * 38.047e12 * 2 / 1.2 / 197e12, rel=1e-3)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e: {e!r}")


def test_update_program_compiles_for_v5e_and_fits(v5e):
    """The published widths, batch 2 x 4096: about a minute of compiling.
    Built from shapes (``jax.eval_shape``): 0.75B parameters are never made."""
    from tpu_rl.algos.base import make_train_state
    from tpu_rl.algos.registry import get_algo
    from tpu_rl.config import Config
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.models.families import build_family
    from tpu_rl.parallel.dp import make_parallel_train_step
    from tpu_rl.types import Batch

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        config = harness.load_json(f"{harness.HERE}/configs/granite-4.0-h-micro.json")
        cfg = Config.from_dict({**config["params"], "mesh_data": 1})
        mesh = Mesh(np.asarray(v5e.devices[:1]), ("data",))
        family = build_family(cfg)
        state = jax.eval_shape(lambda k: make_train_state(cfg, family, k), jax.random.key(0))
        n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state.params))
        assert n_params == 746_621_897  # 9 x 76.2M + 60.8M + projection and heads
        step = get_algo(cfg.algo).make_train_step(cfg, family)
        lay = BatchLayout.from_config(cfg)
        batch = jax.eval_shape(lambda: Batch.zeros(
            cfg.batch_size, cfg.seq_len, cfg.obs_shape, cfg.action_space,
            cfg.hidden_size, hx_width=lay.hx, cx_width=lay.cx))
        rs, bs = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
        shaped = lambda tree, s: jax.tree.map(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree)
        compiled = make_parallel_train_step(step, mesh, cfg).lower(
            shaped(state, rs), shaped(batch, bs),
            shaped(jax.eval_shape(lambda: jax.random.key(1)), rs),
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    # one attention layer: flash forward, again when the layer is
    # rematerialised, then dq and dkv
    assert compiled.as_text().count("tpu_custom_call") == 4
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    hbm = flops.peaks("TPU v5 lite")["hbm_bytes"]
    assert 0.25 * hbm < used < 0.85 * 15.7 * 2**30  # fills the chip, and fits
