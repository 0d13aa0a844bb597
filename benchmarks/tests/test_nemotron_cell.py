"""The ``nemotron-3-nano-30b-a3b.learner`` cell rehearsed end to end on the
CPU at tiny widths, through ``run.main``, the real data files and the
``learner_feed_routed`` runner (the device check replaced, as in
``test_runners.py``); its readers on a trace made by hand; and its update
program compiled for a described TPU v5e at the published widths, with the
compiler's memory reading. What comes out is control flow, counts and a
compiler's verdict, never a device number."""

import json
import types

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks import flops, flops_nemotron_h, harness, run, trace

CELL = "nemotron-3-nano-30b-a3b.learner"
TINY_ARCH = dict(
    hidden_size=64, hybrid_override_pattern="ME*E", layer_norm_epsilon=1e-5,
    mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16, n_groups=2, conv_kernel=4,
    chunk_size=8, use_conv_bias=True, mamba_proj_bias=False, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, attention_bias=False, n_routed_experts=4,
    num_experts_per_tok=3, moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=2.5,
    expert_parallel=dict(published_n_routed_experts=16, chips=4, rank=0),
)
TINY = [f"params.arch={json.dumps(TINY_ARCH)}", "params.seq_len=32", "params.obs_shape=[6]",
        "params.action_space=3", 'params.compute_dtype="float32"',
        "windows.pool=8", "windows.episode_len_mean=9",
        "trace.start_update=4", "trace.updates=4"]
BENCH = harness.load_json(f"{harness.ROOT}/BENCHMARK.json")
CONFIG = harness.load_json(f"{harness.HERE}/configs/nemotron-3-nano-30b-a3b.json")
NEW = {"kernel.moe_ms_per_update", "kernel.moe_route_ms_per_update", "moe_gmm_roofline",
       "moe.rows_max_over_mean", "step.moe_hybrid_mfu",
       "moe_hybrid_ssd_scan_roofline", "moe_hybrid_attn_flash_roofline"}
EXTENDED = {"kernel.ssd_ms_per_update", "step.opt_ms_per_update"}


@pytest.fixture
def any_device(monkeypatch):
    monkeypatch.setattr(harness, "check_device", lambda *a: None)


def result_line(capsys, trace: int, seconds: float, extra=()) -> dict:
    argv = ["--workload", CELL, "--seed", str(2**31 + 11), "--seconds", str(seconds),
            "--trace", str(trace)]
    for item in [*TINY, *extra]:
        argv += ["--set", item]
    assert run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_rehearsal(any_device, capsys):
    line = result_line(capsys, trace=0, seconds=4)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"transitions_per_s", "setup_s", "peak_hbm_gib"} >= set(line["metrics"]) >= {
        "transitions_per_s", "setup_s"}
    checks = line["checks"]
    assert not checks["device"] and not line["correct"]  # a CPU is never correct
    assert checks["parity"] and checks["losses_finite"] and checks["no_compile_in_window"]
    assert checks["routed_parity"]
    assert not checks["kernel_path"]  # no Mosaic kernel on a CPU
    assert line["parity"]["err"]["logits"] < 1e-4  # float32 against the reference, free-running
    routed = line["parity"]["routed"]
    assert routed["err"]["logits"] < 1e-4 and routed["err"]["value"] < 1e-4
    assert routed["err"]["flip_share"] == 0 and routed["assignments"] == 4 * 2 * 32 * 3
    assert set(routed["tol"]) == {"logits", "value", "flip_share", "flip_margin"}


def test_the_next_precision_down_fails_the_routed_comparison(any_device, capsys):
    """The reference with float8 operands in every projection and expert
    product, on the system's choices: not correct, by the routed tolerances."""
    line = result_line(capsys, trace=0, seconds=2,
                       extra=['routed.operand_dtype="float8_e4m3fn"'])
    routed = line["parity"]["routed"]
    assert routed["operand_dtype"] == "float8_e4m3fn"
    assert routed["err"]["logits"] > routed["tol"]["logits"]
    assert not line["checks"]["routed_parity"] and line["checks"]["parity"]
    # ... and what the free-running limits read against that reference, beside them
    free = routed["free_control"]
    assert set(free["err"]) == set(free["tol"]) == {"logits", "value", "loss"}
    assert free["err"]["logits"] > 10 * line["parity"]["err"]["logits"]
    assert free["fails"] == sorted(k for k in free["tol"] if free["err"][k] > free["tol"][k])


def test_a_traced_rehearsal_reads_the_counters_and_leaves_the_rest(any_device, capsys):
    """No TPU plane in a CPU capture: every device-trace reader finds nothing
    and is left out. The routing counter is read from ``learn.jsonl``."""
    line = result_line(capsys, trace=1, seconds=6)
    got = set(line["metrics"])
    assert {"feed.wait_share", "feed.h2d_bytes_per_update", "moe.rows_max_over_mean"} <= got
    assert line["metrics"]["feed.h2d_bytes_per_update"]["value"] == 4 * 32 * (6 + 3 + 6) * 4
    assert 1.0 <= line["metrics"]["moe.rows_max_over_mean"]["value"] <= 4.0
    assert not {"step.mfu", "attn_flash_roofline", "ssd_scan_roofline", "step.hybrid_mfu"} & got
    assert not (NEW - {"moe.rows_max_over_mean"}) & got and "breakdown" not in line


def hand_made_trace(scoped: bool = True) -> trace.Trace:
    """Four executions of a 590 ms update program, 600 ms apart: a window of
    two periods. Each holds, under ``moe``: 2 ms route, 3 ms dispatch, 10 ms
    experts, 4 ms combine and 20 ms shared; 40 ms of scan, 20 ms of attention
    and 1 ms under ``opt_update``."""
    ms = 1e6
    modules = [trace.Event("jit_train_step", i * 600 * ms, 590 * ms) for i in range(4)]
    ops = []
    for i in range(4):
        t0 = i * 600 * ms
        top = "jit(train_step)/"
        layer = top + "transpose(jvp(layer3))/moe/experts/" if scoped else top
        spans = [("moe_route/top_k:", 2), ("moe_dispatch/sort:", 3),
                 ("moe_experts/moe_gmm_pallas/pallas_call:", 10), ("moe_combine/gather:", 4),
                 ("moe_shared/shared_in/dot_general:", 20)]
        at = t0
        for j, (tail, dur) in enumerate(spans):
            ops.append(trace.Event(f"fusion.{j}", at, dur * ms, layer + tail if scoped else top))
            at += (dur + 1) * ms
        ops += [
            trace.Event("fusion.8", t0 + 100 * ms, 40 * ms,
                        top + "layer0/mamba/ssd_scan/ssd_pallas/x:" if scoped else top),
            trace.Event("fusion.9", t0 + 200 * ms, 1 * ms,
                        top + "opt_update/reduce_sum:" if scoped else top),
            trace.Event("fusion.10", t0 + 300 * ms, 20 * ms,
                        top + "layer5/attention/attn_flash_pallas/pallas_call:" if scoped else top),
        ]
    return trace.Trace([trace.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)])


def learn_rows(counted: bool = True) -> list:
    row = {"idx": 0, "ts": 0.0}
    if counted:
        row.update({"moe-rows": 4 * 6144.0, "moe-rows-max-over-mean": 1.25})
    return [harness.Seen(0.0, dict(row)), harness.Seen(1.0, {**row, "moe-rows-max-over-mean": 1.35}
                                                        if counted else dict(row))]


def test_the_new_readers_on_a_trace_made_by_hand():
    spec = types.SimpleNamespace(params=CONFIG["params"], traffic={})

    def run_with(tr, counted=True):
        return types.SimpleNamespace(
            trace=tr, spec=spec, transitions_per_update=16384, device={"kind": "TPU v5 lite"},
            window=types.SimpleNamespace(rows=learn_rows(counted)))

    tr = hand_made_trace()
    assert tr.n_steps == 2 and tr.window_s == pytest.approx(1.2)
    got = {}
    for name in NEW | EXTENDED:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert CELL in entry["workloads"] and entry["moves"] == "transitions_per_s"
        reader = harness.load_module(f"{harness.HERE}/metrics/{name}.py")
        got[name] = reader.read(run_with(tr))
        if name != "moe.rows_max_over_mean":  # a counter: read in an untraced run too
            assert reader.read(run_with(None)) is None
        # the parent's program: no such scope, no such counter — nothing to read, no error
        assert reader.read(run_with(hand_made_trace(scoped=False), counted=False)) is None
    assert got["kernel.moe_ms_per_update"] == pytest.approx(39.0)
    assert got["kernel.moe_route_ms_per_update"] == pytest.approx(9.0)
    assert got["kernel.ssd_ms_per_update"] == pytest.approx(40.0)
    assert got["step.opt_ms_per_update"] == pytest.approx(1.0)
    assert got["moe.rows_max_over_mean"] == pytest.approx(1.3)
    share, extra = got["moe_gmm_roofline"]
    ops, nbytes = flops_nemotron_h.gmm_train(CONFIG["params"], 4 * 6144)
    assert extra == {"bound": "compute", "routed_rows": 4 * 6144.0}
    assert ops / 197e12 > nbytes / 819e9
    assert share == pytest.approx(100 * (ops / 197e12) / 10e-3, rel=1e-6) and share < 100
    # the scan is held to HBM's speed, attention to the MXU's, at this model's widths
    share, extra = got["moe_hybrid_ssd_scan_roofline"]
    assert extra == {"bound": "memory"}
    assert share == pytest.approx(100 * flops_nemotron_h.ssd_train(CONFIG["params"], 4)[1]
                                  / 819e9 / 40e-3) and 45 < share < 50
    share, extra = got["moe_hybrid_attn_flash_roofline"]
    assert extra == {"bound": "compute"}
    assert share == pytest.approx(100 * 3 * 4 * 2 * 4096**3 / 197e12 / 20e-3) and 40 < share < 45
    # 29.3 TFLOP per update, 2 updates in 1.2 s, over 197 TFLOP/s
    assert got["step.moe_hybrid_mfu"] == pytest.approx(
        100 * flops_nemotron_h.update(CONFIG["params"], 4, 4 * 6144) * 2 / 1.2 / 197e12)
    assert got["step.moe_hybrid_mfu"] == pytest.approx(24.8, abs=0.1)


def test_the_configuration_file_states_the_cut():
    arch = CONFIG["params"]["arch"]
    for key, value in arch.items():  # the program's arch is the file's top level
        assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["hybrid_override_pattern", "n_routed_experts", "vocab_size"]
    assert "vocab_size" not in CONFIG and CONFIG["published"]["n_routed_experts"] == 128
    assert arch["expert_parallel"] == {"published_n_routed_experts": 128, "chips": 16, "rank": 0}
    assert arch["hybrid_override_pattern"] == CONFIG["published"]["hybrid_override_pattern"][:9]
    for key in ("positions", "router_bias", "vocab_size", "batch_size", "act_mode"):
        assert key in CONFIG["assumed"], key
    routed = CONFIG["parity"]["routed"]
    assert set(routed) == {"rows", "tol", "delta", "flip_share"} and "GiB" in CONFIG["batch_choice"]


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e: {e!r}")


def test_update_program_compiles_for_v5e_and_fits(v5e):
    """The published widths, batch 4 x 4096: under a minute of compiling.
    Built from shapes (``jax.eval_shape``): 0.58B parameters are never made."""
    from tpu_rl.algos.base import make_train_state
    from tpu_rl.algos.registry import get_algo
    from tpu_rl.config import Config
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.models.families import build_family
    from tpu_rl.parallel.dp import make_parallel_train_step
    from tpu_rl.types import Batch
    from tpu_rl.utils.platform import program_paths

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        cfg = Config.from_dict({**CONFIG["params"], "mesh_data": 1})
        mesh = Mesh(np.asarray(v5e.devices[:1]), ("data",))
        family = build_family(cfg)
        state = jax.eval_shape(lambda k: make_train_state(cfg, family, k), jax.random.key(0))
        sizes = {jax.tree_util.keystr(p): int(np.prod(x.shape))
                 for p, x in jax.tree_util.tree_leaves_with_path(state.params)}
        assert sum(sizes.values()) == 579_081_993
        routed = sum(n for k, n in sizes.items() if "w_in" in k or "w_out" in k)
        assert routed == 4 * 8 * 9_977_856 and 0.55 < routed / 579_081_993 < 0.56
        step = get_algo(cfg.algo).make_train_step(cfg, family)
        lay = BatchLayout.from_config(cfg)
        batch = jax.eval_shape(lambda: Batch.zeros(
            cfg.batch_size, cfg.seq_len, cfg.obs_shape, cfg.action_space,
            cfg.hidden_size, hx_width=lay.hx, cx_width=lay.cx))
        rs, bs = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
        shaped = lambda tree, s: jax.tree.map(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree)
        lowered = make_parallel_train_step(step, mesh, cfg).lower(
            shaped(state, rs), shaped(batch, bs),
            shaped(jax.eval_shape(lambda: jax.random.key(1)), rs),
        )
        assert set(CONFIG["expect_paths"]) <= set(program_paths(lowered)["paths"])
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    hbm = flops.peaks("TPU v5 lite")["hbm_bytes"]
    assert 0.25 * hbm < used < 13.5 * 2**30  # fills the chip, and fits
    assert used / 2**30 == pytest.approx(10.9, abs=0.3)  # what batch_choice quotes
