"""The ``lfm2-24b-a2b.learner`` cell: its configuration file against the
contract (the published keys, the cut, what is assumed, the parameter count
from shapes), the cell rehearsed end to end on the CPU at tiny widths through
``run.main``, the real data files and the ``learner_feed_routed`` runner (the
device check replaced, as in ``test_runners.py``), its update program compiled
for a described v5e, and its readers on a trace made by hand. What comes out
is control flow and counts, never a device number."""

import json
import types

import jax
import numpy as np
import pytest

from benchmarks import flops_lfm2_moe, harness, run, trace

CELL = "lfm2-24b-a2b.learner"
TINY_ARCH = dict(
    hidden_size=64, num_hidden_layers=4, layer_types=["conv", "full_attention", "conv", "conv"],
    num_dense_layers=1, norm_eps=1e-5, conv_L_cache=3, conv_bias=False, num_attention_heads=4,
    num_key_value_heads=2, rope_parameters=dict(rope_theta=1000000, rope_type="default"),
    intermediate_size=160, moe_intermediate_size=48, num_experts=8, num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
    expert_parallel=dict(published_n_routed_experts=16, chips=2, rank=0),
)
TINY = [f"params.arch={json.dumps(TINY_ARCH)}", "params.seq_len=32", "params.obs_shape=[6]",
        "params.action_space=3", 'params.compute_dtype="float32"',
        "windows.pool=8", "windows.episode_len_mean=16",
        "trace.start_update=4", "trace.updates=4"]
BENCH = harness.load_json(f"{harness.ROOT}/BENCHMARK.json")
CONFIG = harness.load_json(f"{harness.HERE}/configs/lfm2-24b-a2b.json")
NEW = {"kernel.shortconv_ms_per_update", "kernel.shortconv_gate_ms_per_update",
       "shortconv_gate_roofline", "conv_attn_flash_roofline", "conv_moe_gmm_roofline",
       "step.conv_moe_mfu"}
EXTENDED = {"kernel.moe_ms_per_update", "kernel.moe_route_ms_per_update",
            "moe.rows_max_over_mean", "step.opt_ms_per_update", "gdn_attn.tiles_run_share",
            "attn.bwd_steps_run_share"}
# read in an untraced run too
COUNTERS = {"gdn_attn.tiles_run_share", "moe.rows_max_over_mean", "attn.bwd_steps_run_share"}
REDUCED = ["num_hidden_layers", "layer_types", "num_dense_layers", "num_experts", "vocab_size"]


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
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "lfm2-24b-a2b"]
    assert entry["file"] == "benchmarks/configs/lfm2-24b-a2b.json"
    assert CONFIG["reduced"] == entry["reduced"] == REDUCED
    assert set(CONFIG["published"]) == set(REDUCED) and "vocab_size" not in CONFIG
    published = CONFIG["published"]
    assert (published["num_hidden_layers"], published["num_dense_layers"],
            published["num_experts"], published["vocab_size"]) == (40, 2, 64, 65536)
    # layers 1-5 of the published list: the second leading dense layer and the first whole period
    assert arch["layer_types"] == published["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert published["layer_types"].count("conv") == 30 and len(published["layer_types"]) == 40
    assert (arch["num_hidden_layers"], arch["num_dense_layers"]) == (5, 1)
    assert arch["expert_parallel"] == {"published_n_routed_experts": 64, "chips": 8, "rank": 0}
    assert arch["num_experts"] * 8 == published["num_experts"]
    for key in REDUCED:
        assert key in CONFIG["assumed"], key
    for key in ("head_dim", "conv_seams", "rope", "norms", "intermediate_size", "expert_bias",
                "topk_epsilon", "precision", "initialisation", "obs_shape", "seq_len", "batch_size",
                "act_mode", "loss_log_interval", "K_epoch", "lr"):
        assert key in CONFIG["assumed"], key
    assert "eight" in CONFIG["deployment"] and "35" in CONFIG["deployment"]
    assert set(CONFIG["parity"]["routed"]) == {"rows", "tol", "delta", "flip_share"}
    assert CONFIG["parity"]["reference"] == "lfm2_moe" and "GiB" in CONFIG["batch_choice"]
    assert "452,659,593" in CONFIG["assumed"]["num_experts"]
    assert "2,048" in CONFIG["assumed"]["num_experts"] and "4,096" in CONFIG["assumed"]["num_experts"]
    assert CONFIG["expect_paths"] == ["shortconv", "attn_flash_pallas", "attn_bwd_pallas",
                                      "moe_experts", "moe_gmm_pallas", "moe_row_add_pallas"]


def test_the_published_keys_are_the_catalogs():
    """Every key of the catalog's ``config`` for this model is in the file at
    its published value, unless ``reduced`` lists it."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(catalog)]
    except FileNotFoundError:
        pytest.skip("no catalog in this installation")
    (row,) = [r for r in rows if r["name"] == "LFM2-24B-A2B"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "lfm2-24b-a2b"]
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_cell_and_its_traffic():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": "lfm2-24b-a2b", "traffic": "learner-packed", "chips": 1}
    assert "4 x 8,192" in cell["why"] and "half" in cell["why"] and len(cell["why"]) <= 200
    mix = harness.load_json(f"{harness.HERE}/traffic/learner-packed.json")
    assert mix["runner"] == "learner_feed_routed"
    assert mix["windows"]["episode_len_mean"] == 2048 and mix["windows"]["pool"] == 16
    assert mix["warmup_pairs"] == 2 and CONFIG["params"]["loss_log_interval"] == 2
    params = CONFIG["params"]
    assert (params["seq_len"], params["batch_size"], params["obs_shape"],
            params["action_space"]) == (8192, 4, [64], 8)
    assert CONFIG["parity"]["rows"] == CONFIG["parity"]["routed"]["rows"] == 4  # the timed batch
    for name in NEW | EXTENDED:
        (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert CELL in m["workloads"] and m["moves"] == "transitions_per_s"
        assert (m["workloads"] == [CELL]) == (name in NEW)
        assert m["workloads"][-1] == CELL  # appended, nothing else changed
    # additions only: the new entries are the last of their lists
    assert BENCH["configs"][-1]["name"] == "lfm2-24b-a2b" and BENCH["workloads"][-1] == cell
    assert {m["name"] for m in BENCH["per_layer"][-6:]} == NEW
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


def test_the_parameter_count_from_shapes():
    """A dense convolution layer of 89,139,200, an attention layer with
    experts of 86,118,592 and three convolution layers with experts of
    92,416,064 + the projection, the last norm and the heads. Built from
    shapes (``jax.eval_shape``): the weights are never made."""
    from tpu_rl.config import Config
    from tpu_rl.models.families import build_family

    family = build_family(Config.from_dict(CONFIG["params"]))
    tree = jax.eval_shape(lambda k: family.init_params(k), jax.random.key(0))
    sizes = {jax.tree_util.keystr(p): int(np.prod(x.shape))
             for p, x in jax.tree_util.tree_leaves_with_path(tree)}
    assert sum(sizes.values()) == 452_659_593
    layer = lambda i: sum(n for k, n in sizes.items() if f"'layer{i}'" in k)  # noqa: E731
    mixer = lambda i, name: sum(  # noqa: E731
        n for k, n in sizes.items() if f"'layer{i}'" in k and f"'{name}'" in k)
    params = CONFIG["params"]
    assert layer(0) == flops_lfm2_moe.layer_parameters(params, "conv", True) == 89_139_200
    assert layer(1) == flops_lfm2_moe.layer_parameters(params, "full_attention", False) == 86_118_592
    assert {layer(i) for i in (2, 3, 4)} == {
        flops_lfm2_moe.layer_parameters(params, "conv", False)} == {92_416_064}
    assert {mixer(i, "conv") for i in (0, 2, 3, 4)} == {16_783_360}
    assert mixer(1, "attention") == 10_485_888
    routed = sum(n for k, n in sizes.items() if "w_gate" in k or "w_in" in k or "w_out" in k)
    assert routed == 4 * 75_497_472 and 0.66 < routed / 452_659_593 < 0.68
    assert sum(sizes.values()) * 16 / 1e9 == pytest.approx(7.24, abs=0.01)  # GB at 16 B each
    assert not any("shared" in k or "bias" in k and "router" not in k and "layer" in k for k in sizes)
    ctx = 8192
    # four two-row tails; one ring of keys and values at 8 heads of 64, and the counter
    assert family.carry_widths == (4 * 2 * 2048, 2 * ctx * 8 * 64 + 1)
    assert sum(sizes.values()) * 4 / 2**30 > 1  # the actor tree is over the broadcast's frame


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e: {e!r}")


def test_update_program_compiles_for_v5e_and_fits(v5e, monkeypatch):
    """The published widths, batch 4 x 8,192: about a minute of compiling, as
    a cell without ``mesh_data`` runs it (a plain ``jax.jit``), with the
    chip's own VMEM reading steered into the tracing process. The engaged
    attention path lowers at 32 : 8 heads of 64: the repo's own backward is in
    the program. Built from shapes: 0.45B parameters are never made."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import SingleDeviceSharding

    from benchmarks import flops
    from tpu_rl.algos.base import make_train_state
    from tpu_rl.algos.registry import get_algo
    from tpu_rl.config import Config
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.models import cells
    from tpu_rl.models.families import build_family
    from tpu_rl.types import Batch
    from tpu_rl.utils.platform import program_paths

    monkeypatch.setattr(cells, "_program_devices", lambda: ("tpu", 1))
    monkeypatch.setattr(
        pltpu, "get_tpu_info", lambda: types.SimpleNamespace(vmem_capacity_bytes=128 * 2**20))
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        cfg = Config.from_dict(CONFIG["params"])
        one_chip = SingleDeviceSharding(v5e.devices[0])
        family = build_family(cfg)
        state = jax.eval_shape(lambda k: make_train_state(cfg, family, k), jax.random.key(0))
        step = get_algo(cfg.algo).make_train_step(cfg, family)
        lay = BatchLayout.from_config(cfg)
        batch = jax.eval_shape(lambda: Batch.zeros(
            cfg.batch_size, cfg.seq_len, cfg.obs_shape, cfg.action_space,
            cfg.hidden_size, hx_width=lay.hx, cx_width=lay.cx))
        shaped = lambda tree: jax.tree.map(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
        lowered = jax.jit(step, donate_argnums=(0,)).lower(
            shaped(state), shaped(batch), shaped(jax.eval_shape(lambda: jax.random.key(1))))
        paths = program_paths(lowered)
        assert set(CONFIG["expect_paths"]) <= set(paths["paths"]) and paths["mosaic_calls"] > 0
        assert "attn_bwd_band" in lowered.as_text(debug_info=True)
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    mem = compiled.memory_analysis()
    hbm = flops.peaks("TPU v5 lite")["hbm_bytes"]
    assert 0.25 * hbm < mem.peak_memory_in_bytes < 12.0 * 2**30  # fills the chip, and fits
    assert mem.peak_memory_in_bytes / 2**30 == pytest.approx(8.36, abs=0.3)  # batch_choice's


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
    # three expert layers of four (the dense layer has no assignment), the batch's four windows
    assert routed["err"]["flip_share"] == 0 and routed["assignments"] == 3 * 4 * 32 * 4
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
    assert line["metrics"]["feed.h2d_bytes_per_update"]["value"] == 4 * 32 * (6 + 3 + 6) * 4
    # a 32-step window is one tile: the grid is too small to read the seams
    assert line["metrics"]["gdn_attn.tiles_run_share"]["value"] == 100.0
    assert line["metrics"]["attn.bwd_steps_run_share"]["value"] == 100.0
    assert not ((NEW | EXTENDED) - COUNTERS) & got and "breakdown" not in line


# -------------------------------------------------------------------- the readers
def hand_made_trace(scoped: bool = True) -> trace.Trace:
    """Four executions of a 590 ms update program, 600 ms apart: a window of
    two periods. Each holds 230 ms under ``shortconv`` (120 ``in_proj``, 50
    the gates and taps — 20 of it the taps' own scope —, 60 ``out_proj``),
    60 ms under ``attn_global`` (10 the rotation, 40 the kernels), 100 ms
    under ``mlp``; under ``moe``: 5 ms route, 15 ms dispatch, 40 ms experts,
    20 ms combine; and 80 ms under ``opt_update``."""
    ms = 1e6
    modules = [trace.Event("jit_train_step", i * 600 * ms, 590 * ms) for i in range(4)]
    ops = []
    for i in range(4):
        top = "jit(train_step)/"
        conv = "transpose(jvp(layer2))/shortconv/conv/"
        spans = [
            (conv + "shortconv_in/in_proj/dot_general:", 120),
            (conv + "shortconv_gate/checkpoint/mul:", 30),
            (conv + "shortconv_gate/checkpoint/shortconv_conv/reduce_sum:", 20),
            (conv + "shortconv_out/out_proj/dot_general:", 60),
            ("transpose(jvp(layer1))/attn_global/attention/attn_rope/mul:", 10),
            ("transpose(jvp(layer1))/attn_global/attention/attn_flash_pallas/pallas_call:", 25),
            ("transpose(jvp(layer1))/attn_global/attention/attn_flash_pallas/attn_bwd_pallas/"
             "pallas_call:", 15),
            ("transpose(jvp(layer1))/attn_global/attention/o_proj/dot_general:", 10),
            ("transpose(jvp(layer0))/mlp/w1/dot_general:", 100),
            ("transpose(jvp(layer1))/moe/experts/moe_route/top_k:", 5),
            ("transpose(jvp(layer1))/moe/experts/moe_dispatch/sort:", 15),
            ("transpose(jvp(layer1))/moe/experts/moe_experts/moe_gmm_pallas/pallas_call:", 40),
            ("transpose(jvp(layer1))/moe/experts/moe_combine/gather:", 20),
            ("opt_update/reduce_sum:", 80),
        ]
        at = i * 600 * ms
        for j, (tail, dur) in enumerate(spans):
            ops.append(trace.Event(f"fusion.{j}", at, dur * ms, top + tail if scoped else top))
            at += (dur + 1) * ms
    return trace.Trace([trace.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)])


PAIRS, ROUTED, TILES_RUN, TILES_BAND = 52e6, 65_536.0, 70.0, 144.0


def learn_rows(counted: bool = True) -> list:
    row = {"idx": 0, "ts": 0.0}
    if counted:
        row.update({"moe-rows": ROUTED, "moe-rows-max-over-mean": 1.5, "attn-pairs-global": PAIRS,
                    "attn-tiles-run-global": TILES_RUN, "attn-tiles-band-global": TILES_BAND,
                    "attn-bwd-steps-global": TILES_BAND})
    return [harness.Seen(0.0, dict(row)), harness.Seen(1.0, dict(row))]


def test_the_new_readers_on_a_trace_made_by_hand():
    spec = types.SimpleNamespace(params=CONFIG["params"], traffic={})

    def run_with(tr, counted=True, params=None):
        return types.SimpleNamespace(
            trace=tr, spec=spec if params is None else types.SimpleNamespace(params=params),
            transitions_per_update=32768, device={"kind": "TPU v5 lite"},
            window=types.SimpleNamespace(rows=learn_rows(counted)))

    tr = hand_made_trace()
    assert tr.n_steps == 2 and tr.window_s == pytest.approx(1.2)
    got = {}
    for name in NEW | EXTENDED:
        reader = harness.load_module(f"{harness.HERE}/metrics/{name}.py")
        got[name] = reader.read(run_with(tr))
        if name not in COUNTERS:
            assert reader.read(run_with(None)) is None
        # a program without the scopes and the counters (the parent's): nothing to read, no error
        assert reader.read(run_with(hand_made_trace(scoped=False), counted=False)) is None
    other = harness.load_json(f"{harness.HERE}/configs/glm-4.7-flash.json")["params"]
    for name in ("shortconv_gate_roofline", "conv_attn_flash_roofline", "conv_moe_gmm_roofline",
                 "step.conv_moe_mfu"):
        reader = harness.load_module(f"{harness.HERE}/metrics/{name}.py")
        assert reader.read(run_with(tr, params=other)) is None  # another family's cell
    assert got["kernel.shortconv_ms_per_update"] == pytest.approx(230.0)
    assert got["kernel.shortconv_gate_ms_per_update"] == pytest.approx(50.0)
    assert got["kernel.shortconv_ms_per_update"] >= got["kernel.shortconv_gate_ms_per_update"]
    assert got["kernel.moe_ms_per_update"] == pytest.approx(80.0)
    assert got["kernel.moe_route_ms_per_update"] == pytest.approx(40.0)
    assert got["step.opt_ms_per_update"] == pytest.approx(80.0)
    assert got["moe.rows_max_over_mean"] == pytest.approx(1.5)
    assert got["gdn_attn.tiles_run_share"] == pytest.approx(100 * 70 / 144)
    assert got["attn.bwd_steps_run_share"] == pytest.approx(100 * 70 / 144)
    attn = harness.load_module(f"{harness.HERE}/metrics/kernel.attn_ms_per_update.py")
    assert attn.read(run_with(tr)) == pytest.approx(40.0)  # both kernels' scope
    # 4 x 8,192 tokens x 4 layers x 61,440 B = 8.05 GB: 9.8 ms at the peak, over 50 ms
    share, extra = got["shortconv_gate_roofline"]
    assert extra == {"bound": "memory"}
    want = flops_lfm2_moe.gate_train(CONFIG["params"], 4) / 819e9 / 50e-3
    assert share == pytest.approx(100 * want) and 19 < share < 20
    # 52M kept pairs x 8,192 x 3 = 1.28 TFLOP, 6.5 ms at the peak, over 40 ms of kernels
    share, extra = got["conv_attn_flash_roofline"]
    assert extra == {"bound": "compute", "pairs": PAIRS}
    assert share == pytest.approx(100 * 3 * 52e6 * 8_192 / 197e12 / 40e-3) and 16 < share < 17
    share, extra = got["conv_moe_gmm_roofline"]
    ops, nbytes = flops_lfm2_moe.gmm_train(CONFIG["params"], ROUTED)
    assert extra == {"bound": "compute", "routed_rows": ROUTED} and ops / 197e12 > nbytes / 819e9
    assert share == pytest.approx(100 * (ops / 197e12) / 40e-3) and 0 < share < 100
    share, extra = got["step.conv_moe_mfu"]
    want = flops_lfm2_moe.update(CONFIG["params"], 4, PAIRS, ROUTED) * 2 / 1.2 / 197e12
    assert extra == {"bound": "compute"}
    assert share == pytest.approx(100 * want) and 0 < share < 100
