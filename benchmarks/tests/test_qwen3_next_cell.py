"""The ``qwen3-next-80b-a3b.learner`` cell: its configuration file against the
contract (the published keys, the cut, what is assumed, the parameter count
from shapes), the cell rehearsed end to end on the CPU at tiny widths through
``run.main``, the real data files and the ``learner_feed_routed`` runner (the
device check replaced, as in ``test_runners.py``), and its readers on a trace
made by hand. What comes out is control flow and counts, never a device
number."""

import json
import types

import jax
import numpy as np
import pytest

from benchmarks import flops_qwen3_next, harness, run, trace

CELL = "qwen3-next-80b-a3b.learner"
TINY_ARCH = dict(
    hidden_size=64, num_hidden_layers=4, full_attention_interval=4, rms_norm_eps=1e-6,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, rope_theta=10000000, rope_scaling=None,
    partial_rotary_factor=0.25, moe_intermediate_size=48, shared_expert_intermediate_size=48,
    num_experts=4, num_experts_per_tok=3, norm_topk_prob=True, decoder_sparse_step=1,
    mlp_only_layers=[], expert_parallel=dict(published_n_routed_experts=16, chips=4, rank=0),
)
TINY = [f"params.arch={json.dumps(TINY_ARCH)}", "params.seq_len=32", "params.obs_shape=[6]",
        "params.action_space=3", 'params.compute_dtype="float32"',
        "windows.pool=8", "windows.episode_len_mean=16",
        "trace.start_update=4", "trace.updates=4"]
BENCH = harness.load_json(f"{harness.ROOT}/BENCHMARK.json")
CONFIG = harness.load_json(f"{harness.HERE}/configs/qwen3-next-80b-a3b.json")
NEW = {"kernel.gdn_ms_per_update", "gdn_scan_roofline", "gdn_attn_flash_roofline",
       "gdn_moe_gmm_roofline", "step.gdn_moe_mfu", "gdn_attn.tiles_run_share"}
EXTENDED = {"kernel.moe_ms_per_update", "kernel.moe_route_ms_per_update",
            "moe.rows_max_over_mean", "step.opt_ms_per_update"}
COUNTERS = {"gdn_attn.tiles_run_share", "moe.rows_max_over_mean"}  # read in an untraced run too


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


# ------------------------------------------------------------------ the contract
def test_the_configuration_file_states_the_cut():
    arch = CONFIG["params"]["arch"]
    for key, value in arch.items():  # the program's arch is the file's top level
        assert CONFIG[key] == value, key
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "qwen3-next-80b-a3b"]
    assert entry["file"] == "benchmarks/configs/qwen3-next-80b-a3b.json"
    assert CONFIG["reduced"] == entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert set(CONFIG["published"]) == set(CONFIG["reduced"]) and "vocab_size" not in CONFIG
    assert CONFIG["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    # one whole period (L L L F), every other key as published
    assert arch["num_hidden_layers"] == arch["full_attention_interval"] == 4
    assert arch["expert_parallel"] == {"published_n_routed_experts": 512, "chips": 16, "rank": 0}
    assert arch["num_experts"] * 16 == CONFIG["published"]["num_experts"]
    for key in CONFIG["reduced"]:
        assert key in CONFIG["assumed"], key
    for key in ("l2norm", "gate_placement", "zero_centered_norm", "rope", "chunk_size", "router",
                "shared_expert", "initialisation", "multi_token_prediction", "precision",
                "batch_size", "act_mode", "lr"):
        assert key in CONFIG["assumed"], key
    assert set(CONFIG["parity"]["routed"]) == {"rows", "tol", "delta", "flip_share"}
    assert CONFIG["parity"]["reference"] == "qwen3_next" and "GiB" in CONFIG["batch_choice"]
    assert "548,027,465" in CONFIG["assumed"]["num_experts"]
    assert "320" in CONFIG["assumed"]["num_experts"] and "5,120" in CONFIG["assumed"]["num_experts"]
    assert CONFIG["expect_paths"] == ["gdn_scan", "attn_flash_pallas", "moe_experts",
                                      "moe_gmm_pallas", "moe_row_add_pallas"]


def test_the_published_keys_are_the_catalogs():
    """Every key of the catalog's ``config`` for this model is in the file at
    its published value, unless ``reduced`` lists it."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(catalog)]
    except FileNotFoundError:
        pytest.skip("no catalog in this installation")
    (row,) = [r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "qwen3-next-80b-a3b"]
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_cell_and_its_traffic():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": "qwen3-next-80b-a3b", "traffic": "learner-packed", "chips": 1}
    assert "sixteenth" in cell["why"] and len(cell["why"]) <= 200
    mix = harness.load_json(f"{harness.HERE}/traffic/learner-packed.json")
    assert mix["runner"] == "learner_feed_routed"
    assert mix["windows"] == {"pool": 16, "episode_len_mean": 2048, "obs_scale": 1.0,
                              "rew_scale": 0.1, "carry_scale": 0.0}
    assert mix["trace"] == {"start_update": 8, "updates": 6}  # the issue's
    assert mix["warmup_pairs"] == 2 and CONFIG["params"]["loss_log_interval"] == 2
    params = CONFIG["params"]
    assert (params["seq_len"], params["batch_size"], params["obs_shape"],
            params["action_space"]) == (8192, 2, [64], 8)
    for name in NEW | EXTENDED:
        (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert CELL in m["workloads"] and m["moves"] == "transitions_per_s"
        assert (m["workloads"] == [CELL]) == (name in NEW)
    (tiles,) = [m for m in BENCH["per_layer"] if m["name"] == "attn.tiles_run_share"]
    assert CELL not in tiles["workloads"]  # its reader finds no window layer's counter here


def test_the_parameter_count_from_shapes():
    """Three linear layers of 138,582,208 and one full layer of 132,127,232
    (104,859,648 of each the expert block, 100,663,296 of that the 32 held
    experts) + the projection, the last norm and the heads. Built from shapes
    (``jax.eval_shape``): the weights are never made."""
    from tpu_rl.config import Config
    from tpu_rl.models.families import build_family

    family = build_family(Config.from_dict(CONFIG["params"]))
    tree = jax.eval_shape(lambda k: family.init_params(k), jax.random.key(0))
    sizes = {jax.tree_util.keystr(p): int(np.prod(x.shape))
             for p, x in jax.tree_util.tree_leaves_with_path(tree)}
    assert sum(sizes.values()) == 548_027_465
    layer = lambda i: sum(n for k, n in sizes.items() if f"'layer{i}'" in k)  # noqa: E731
    experts = 2048 * 512 + 3 * 2048 * 512 + 2048 + 32 * 3 * 2048 * 512
    mixer = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 64 + 128 + 4096 * 2048
    attention = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    assert experts == 104_859_648
    assert layer(0) == layer(1) == layer(2) == mixer + experts + 2 * 2048 == 138_582_208
    assert layer(3) == attention + experts + 2 * 2048 == 132_127_232
    routed = sum(n for k, n in sizes.items() if "w_gate" in k or "w_in" in k or "w_out" in k)
    assert routed == 4 * 100_663_296 and 0.73 < routed / 548_027_465 < 0.74
    assert sum(sizes.values()) * 16 / 1e9 == pytest.approx(8.77, abs=0.01)  # GB at 16 B each
    ctx = 8192
    assert family.carry_widths == (3 * (32 * 128 * 128 + 3 * 8192), 2 * ctx * 2 * 256 + 1)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e: {e!r}")


def test_update_program_compiles_for_v5e_and_fits(v5e):
    """The published widths, batch 2 x 8,192: a minute and a half of compiling.
    Built from shapes (``jax.eval_shape``): 0.55B parameters are never made."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from benchmarks import flops
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
    assert 0.25 * hbm < used < 12.0 * 2**30  # fills the chip, and fits beside the checks' buffers
    assert used / 2**30 == pytest.approx(9.5, abs=0.4)  # what batch_choice quotes


# ------------------------------------------------------------------ the rehearsal
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
    line = result_line(capsys, trace=0, seconds=2,
                       extra=['routed.operand_dtype="float8_e4m3fn"'])
    routed = line["parity"]["routed"]
    assert routed["operand_dtype"] == "float8_e4m3fn"
    assert routed["err"]["logits"] > routed["tol"]["logits"]
    assert not line["checks"]["routed_parity"] and line["checks"]["parity"]
    free = routed["free_control"]
    assert set(free["err"]) == set(free["tol"]) == {"logits", "value", "loss"}
    assert free["err"]["logits"] > 10 * line["parity"]["err"]["logits"]


def test_a_traced_rehearsal_reads_the_counters_and_leaves_the_rest(any_device, capsys):
    """No TPU plane in a CPU capture: every device-trace reader finds nothing
    and is left out. The counters are read from ``learn.jsonl``."""
    line = result_line(capsys, trace=1, seconds=6)
    got = set(line["metrics"])
    assert {"feed.wait_share", "feed.h2d_bytes_per_update"} | COUNTERS <= got
    assert line["metrics"]["feed.h2d_bytes_per_update"]["value"] == 2 * 32 * (6 + 3 + 6) * 4
    # a 32-step window is one tile: the grid is too small to read the seams
    assert line["metrics"]["gdn_attn.tiles_run_share"]["value"] == 100.0
    assert not ((NEW | EXTENDED) - COUNTERS) & got and "breakdown" not in line


# -------------------------------------------------------------------- the readers
def hand_made_trace(scoped: bool = True) -> trace.Trace:
    """Four executions of a 590 ms update program, 600 ms apart: a window of
    two periods. Each holds 200 ms under ``gdn`` (40 the projections, 20 the
    convolution, 140 the scan), 60 ms under ``attn_global`` (2 the rotation,
    40 the kernel); under ``moe``: 5 ms route, 20 ms dispatch, 50 ms experts,
    25 ms combine, 10 ms the shared expert; and 40 ms under ``opt_update``."""
    ms = 1e6
    modules = [trace.Event("jit_train_step", i * 600 * ms, 590 * ms) for i in range(4)]
    ops = []
    for i in range(4):
        top = "jit(train_step)/"
        spans = [
            ("transpose(jvp(layer0))/gdn/linear_attn/in_proj_qkvz/dot_general:", 40),
            ("transpose(jvp(layer0))/gdn/linear_attn/checkpoint/gdn_conv/mul:", 20),
            ("transpose(jvp(layer0))/gdn/linear_attn/gdn_scan/while/body/checkpoint/dot_general:", 140),
            ("transpose(jvp(layer3))/attn_global/attention/q_proj/dot_general:", 18),
            ("transpose(jvp(layer3))/attn_global/attention/attn_rope/mul:", 2),
            ("transpose(jvp(layer3))/attn_global/attention/attn_flash_pallas/pallas_call:", 40),
            ("transpose(jvp(layer3))/moe/experts/moe_route/top_k:", 5),
            ("transpose(jvp(layer3))/moe/experts/moe_dispatch/sort:", 20),
            ("transpose(jvp(layer3))/moe/experts/moe_experts/moe_gmm_pallas/pallas_call:", 50),
            ("transpose(jvp(layer3))/moe/experts/moe_combine/gather:", 25),
            ("transpose(jvp(layer3))/moe/experts/moe_shared/dot_general:", 10),
            ("opt_update/reduce_sum:", 40),
        ]
        at = i * 600 * ms
        for j, (tail, dur) in enumerate(spans):
            ops.append(trace.Event(f"fusion.{j}", at, dur * ms, top + tail if scoped else top))
            at += (dur + 1) * ms
    return trace.Trace([trace.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)])


PAIRS, ROUTED, TILES_RUN, TILES_BAND = 30e6, 40_960.0, 40.0, 72.0


def learn_rows(counted: bool = True) -> list:
    row = {"idx": 0, "ts": 0.0}
    if counted:
        row.update({"moe-rows": ROUTED, "moe-rows-max-over-mean": 1.5, "attn-pairs-global": PAIRS,
                    "attn-tiles-run-global": TILES_RUN, "attn-tiles-band-global": TILES_BAND})
    return [harness.Seen(0.0, dict(row)), harness.Seen(1.0, dict(row))]


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
        reader = harness.load_module(f"{harness.HERE}/metrics/{name}.py")
        got[name] = reader.read(run_with(tr))
        if name not in COUNTERS:
            assert reader.read(run_with(None)) is None
        # the parent's program: no such scope, no such counter — nothing to read, no error
        assert reader.read(run_with(hand_made_trace(scoped=False), counted=False)) is None
    assert got["kernel.gdn_ms_per_update"] == pytest.approx(160.0)
    assert got["kernel.moe_ms_per_update"] == pytest.approx(110.0)
    assert got["kernel.moe_route_ms_per_update"] == pytest.approx(50.0)
    assert got["step.opt_ms_per_update"] == pytest.approx(40.0)
    assert got["moe.rows_max_over_mean"] == pytest.approx(1.5)
    assert got["gdn_attn.tiles_run_share"] == pytest.approx(100 * 40 / 72)
    # the scan: memory-bound, 3 layers x 16,384 tokens x 3 passes x 90,368 B over 140 ms
    share, extra = got["gdn_scan_roofline"]
    ops, nbytes = flops_qwen3_next.gdn_train(CONFIG["params"], 2)
    assert extra == {"bound": "memory"} and nbytes / 819e9 > ops / 197e12
    assert nbytes == 3 * 16384 * 3 * 90_368
    assert share == pytest.approx(100 * (nbytes / 819e9) / 140e-3) and 11 < share < 12
    # 30M kept pairs x 4 x 4096 x 3 = 1.47 TFLOP, 7.5 ms at the peak, over 40 ms of kernel
    share, extra = got["gdn_attn_flash_roofline"]
    assert extra == {"bound": "compute", "pairs": PAIRS}
    assert share == pytest.approx(100 * 3 * 30e6 * 4 * 4096 / 197e12 / 40e-3) and 18 < share < 19
    share, extra = got["gdn_moe_gmm_roofline"]
    ops, nbytes = flops_qwen3_next.gmm_train(CONFIG["params"], ROUTED)
    assert extra == {"bound": "memory", "routed_rows": ROUTED} and nbytes / 819e9 > ops / 197e12
    assert share == pytest.approx(100 * (nbytes / 819e9) / 50e-3) and 0 < share < 100
    want = flops_qwen3_next.update(CONFIG["params"], 2, PAIRS, ROUTED) * 2 / 1.2 / 197e12
    assert got["step.gdn_moe_mfu"] == pytest.approx(100 * want) and 14 < 100 * want < 16
