"""The ``ling-3.0-flash-vl.learner`` cell: its configuration file against the
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

from benchmarks import flops_ling_flash, harness, run, trace

CELL = "ling-3.0-flash-vl.learner"
TINY_ARCH = dict(
    hidden_size=64, num_hidden_layers=7, layer_group_size=6, layer_offset=1,
    first_k_dense_replace=1, rms_norm_eps=1e-6, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, q_lora_rank=None, kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_theta=6000000, short_conv_kernel_size=4, kda_safe_gate=True,
    kda_lower_bound=-5, intermediate_size=96, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=48, num_experts=4, num_experts_per_tok=3, n_group=4,
    topk_group=2, score_function="sigmoid", moe_router_enable_expert_bias=True,
    routed_scaling_factor=2.5, norm_topk_prob=True,
    expert_parallel=dict(published_n_routed_experts=16, chips=4, rank=0),
)
TINY = [f"params.arch={json.dumps(TINY_ARCH)}", "params.seq_len=64", "params.obs_shape=[6]",
        "params.action_space=3", 'params.compute_dtype="float32"', "params.batch_size=2",
        "windows.pool=8", "windows.episode_len_mean=16",
        "trace.start_update=4", "trace.updates=4"]
BENCH = harness.load_json(f"{harness.ROOT}/BENCHMARK.json")
CONFIG = harness.load_json(f"{harness.HERE}/configs/ling-3.0-flash-vl.json")
NEW = {"kernel.kda_ms_per_update", "kda_scan_roofline", "kda_attn_flash_roofline",
       "kda_moe_gmm_roofline", "step.kda_moe_mfu", "moe.group_hit_share"}
EXTENDED = {"step.opt_ms_per_update", "kernel.moe_ms_per_update", "kernel.moe_route_ms_per_update",
            "moe.rows_max_over_mean", "kernel.mla_ms_per_update", "kernel.mla_latent_ms_per_update",
            "gdn_attn.tiles_run_share", "attn.bwd_steps_run_share"}
COUNTERS = {"moe.group_hit_share", "moe.rows_max_over_mean", "gdn_attn.tiles_run_share",
            "attn.bwd_steps_run_share"}  # read in an untraced run's record too
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size"]


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
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "ling-3.0-flash-vl"]
    assert entry["file"] == "benchmarks/configs/ling-3.0-flash-vl.json"
    assert CONFIG["reduced"] == entry["reduced"] == REDUCED
    assert set(CONFIG["published"]) == set(REDUCED) and "vocab_size" not in CONFIG
    assert CONFIG["published"] == {"num_hidden_layers": 42, "first_k_dense_replace": 2,
                                   "num_experts": 512, "vocab_size": 157184}
    # the second dense layer, then one whole period: D k k k M k k
    from tpu_rl.models.ling_flash import layer_kinds
    assert (arch["num_hidden_layers"], arch["layer_offset"], arch["layer_group_size"]) == (7, 1, 6)
    assert ["DE"[not dense] + mixer[0] for mixer, dense in layer_kinds(arch)] == [
        "Dk", "Ek", "Ek", "Ek", "Em", "Ek", "Ek"]
    assert arch["expert_parallel"] == {"published_n_routed_experts": 512, "chips": 64, "rank": 0}
    assert arch["num_experts"] * 64 == CONFIG["published"]["num_experts"]
    assert (arch["n_group"], arch["topk_group"], arch["num_experts_per_tok"]) == (8, 4, 8)
    assert not any(arch["expert_swiglu_limit_list"][1:8] + arch["share_expert_swiglu_limit_list"][1:8])
    for key in REDUCED:
        assert key in CONFIG["assumed"], key
    for key in ("vision_tower", "multi_token_prediction", "kda_gate", "beta_and_head_gates", "l2norm",
                "convolution", "chunk_size", "latent_attention", "rope", "router", "shared_expert",
                "swiglu_limits", "initialisation", "precision", "batch_size", "act_mode", "lr"):
        assert key in CONFIG["assumed"], key
    assert set(CONFIG["parity"]["routed"]) == {"rows", "tol", "delta", "flip_share"}
    assert CONFIG["parity"]["reference"] == "ling_flash" and "GiB" in CONFIG["batch_choice"]
    assert "721,628,105" in CONFIG["assumed"]["num_experts"]
    assert "128 rows" in CONFIG["assumed"]["num_experts"] and "8,192" in CONFIG["assumed"]["num_experts"]
    assert CONFIG["expect_paths"] == ["kda", "kda_scan", "mla", "attn_flash_pallas",
                                      "attn_bwd_pallas", "moe_experts", "moe_gmm_pallas",
                                      "moe_row_add_pallas"]


def test_the_published_keys_are_the_catalogs():
    """Every key of the catalog's ``config`` for this model is in the file at
    its published value, unless ``reduced`` lists it."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(catalog)]
    except FileNotFoundError:
        pytest.skip("no catalog in this installation")
    (row,) = [r for r in rows if r["name"] == "Ling-3.0-flash-VL"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "ling-3.0-flash-vl"]
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_cell_and_its_traffic():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": "ling-3.0-flash-vl", "traffic": "learner-packed", "chips": 1}
    assert "64th" in cell["why"] and "14.3 GiB" in cell["why"] and len(cell["why"]) <= 200
    assert BENCH["workloads"][-1] == cell and BENCH["configs"][-1]["name"] == "ling-3.0-flash-vl"
    mix = harness.load_json(f"{harness.HERE}/traffic/learner-packed.json")
    assert mix["runner"] == "learner_feed_routed" and mix["warmup_pairs"] == 2
    params = CONFIG["params"]
    assert (params["seq_len"], params["batch_size"], params["obs_shape"],
            params["action_space"], params["loss_log_interval"]) == (8192, 1, [64], 8, 2)
    for name in NEW | EXTENDED:
        (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert m["workloads"][-1] == CELL and m["moves"] == "transitions_per_s"
        assert (m["workloads"] == [CELL]) == (name in NEW)
    assert [m["name"] for m in BENCH["per_layer"][-6:]] == [
        "kernel.kda_ms_per_update", "kda_scan_roofline", "kda_attn_flash_roofline",
        "kda_moe_gmm_roofline", "step.kda_moe_mfu", "moe.group_hit_share"]
    (tiles,) = [m for m in BENCH["per_layer"] if m["name"] == "attn.tiles_run_share"]
    assert CELL not in tiles["workloads"]  # its reader finds no window layer's counter here


def test_the_parameter_count_from_shapes():
    """Layer 1 (KDA + the dense MLP) 99,837,088, five layers of KDA + experts
    107,046,560 each, the latent layer 86,366,208, + the projection, the last
    norm and the heads. Built from shapes (``jax.eval_shape``): the weights are
    never made."""
    from tpu_rl.config import Config
    from tpu_rl.models.families import build_family

    family = build_family(Config.from_dict(CONFIG["params"]))
    tree = jax.eval_shape(lambda k: family.init_params(k), jax.random.key(0))
    sizes = {jax.tree_util.keystr(p): int(np.prod(x.shape))
             for p, x in jax.tree_util.tree_leaves_with_path(tree)}
    assert sum(sizes.values()) == 721_628_105
    layer = lambda i: sum(n for k, n in sizes.items() if f"'layer{i}'" in k)  # noqa: E731
    d = 2560
    kda = d * 12288 + d * 4096 + 4 * 12288 + 32 + 4096 + d * 64 + 128 + 4096 * d
    latent = d * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + d * 32 + 4096 * d
    experts = d * 512 + 512 + 3 * d * 768 + 8 * 3 * d * 768
    assert (kda, latent, experts) == (52_646_048, 31_965_696, 54_395_392)
    assert layer(0) == kda + 3 * d * 6144 + 2 * d == 99_837_088
    assert [layer(i) for i in (1, 2, 3, 5, 6)] == [kda + experts + 2 * d] * 5 == [107_046_560] * 5
    assert layer(4) == latent + experts + 2 * d == 86_366_208
    assert sum(layer(i) for i in range(7)) == 721_436_096
    assert sum(sizes.values()) * 12 / 1e9 == pytest.approx(8.66, abs=0.01)  # GB at 12 B each
    actor_gb = sum(sizes.values()) * 4 / 1e9
    assert actor_gb == pytest.approx(2.89, abs=0.01) and actor_gb * 1e9 > 2**30  # over the frame
    ctx = 8192
    assert family.carry_widths == (6 * (32 * 128 * 128 + 3 * 12288), ctx * 576 + 1)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e: {e!r}")


def test_update_program_compiles_for_v5e_and_fits(v5e, monkeypatch):
    """The published widths, batch 1 x 8,192: two and a half minutes of
    compiling, as a cell without ``mesh_data`` runs it (a plain ``jax.jit``),
    with the chip's own VMEM reading steered into the tracing process. Built
    from shapes: 0.72B parameters are never made."""
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
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    mem = compiled.memory_analysis()
    hbm = flops.peaks("TPU v5 lite")["hbm_bytes"]
    assert 0.25 * hbm < mem.peak_memory_in_bytes < 13.0 * 2**30  # fills the chip, and fits
    assert mem.peak_memory_in_bytes / 2**30 == pytest.approx(10.13, abs=0.3)  # batch_choice's
    # float32 weights and RMSprop's nu come in: 8 B a parameter
    assert mem.argument_size_in_bytes / 721_628_105 == pytest.approx(8.0, abs=0.01)


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
    assert routed["err"]["flip_share"] == 0 and routed["assignments"] == 6 * 1 * 64 * 3
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
    assert line["metrics"]["feed.h2d_bytes_per_update"]["value"] == 2 * 64 * (6 + 3 + 6) * 4
    assert 0 < line["metrics"]["moe.group_hit_share"]["value"] < 100
    # a 64-step window is one tile: the grid is too small to read the seams
    assert line["metrics"]["gdn_attn.tiles_run_share"]["value"] == 100.0
    assert not ((NEW | EXTENDED) - COUNTERS) & got and "breakdown" not in line


# -------------------------------------------------------------------- the readers
def hand_made_trace(scoped: bool = True) -> trace.Trace:
    """Four executions of a 590 ms update program, 600 ms apart: a window of
    two periods. Each holds 300 ms under ``kda`` (100 the projections, 30 the
    convolution, 10 the gate, 140 the scan, 20 the output), 60 ms under ``mla``
    (8 ``mla_down``, 10 ``mla_up``, 2 the rotation, 30 the kernels, 10
    ``mla_o``), 30 ms under ``mlp``; under ``moe``: 5 ms route, 10 ms dispatch,
    25 ms experts, 10 ms combine, 10 ms the shared expert; and 40 ms under
    ``opt_update``."""
    ms = 1e6
    modules = [trace.Event("jit_train_step", i * 600 * ms, 590 * ms) for i in range(4)]
    ops = []
    for i in range(4):
        top = "jit(train_step)/"
        spans = [
            ("transpose(jvp(layer1))/kda/linear_attn/kda_in/in_proj_qkv/dot_general:", 100),
            ("transpose(jvp(layer1))/kda/linear_attn/checkpoint/kda_conv/mul:", 30),
            ("transpose(jvp(layer1))/kda/linear_attn/kda_gate/logistic:", 10),
            ("transpose(jvp(layer1))/kda/linear_attn/kda_scan/while/body/checkpoint/dot_general:", 140),
            ("transpose(jvp(layer1))/kda/linear_attn/kda_out/o_proj/dot_general:", 20),
            ("transpose(jvp(layer4))/mla/attention/mla_down/kv_a_proj/dot_general:", 8),
            ("transpose(jvp(layer4))/mla/attention/mla_up/q_proj/dot_general:", 10),
            ("transpose(jvp(layer4))/mla/attention/attn_rope/mul:", 2),
            ("transpose(jvp(layer4))/mla/attention/attn_flash_pallas/pallas_call:", 30),
            ("transpose(jvp(layer4))/mla/attention/mla_o/o_proj/dot_general:", 10),
            ("transpose(jvp(layer0))/mlp/gate_proj/dot_general:", 30),
            ("transpose(jvp(layer3))/moe/experts/moe_route/top_k:", 5),
            ("transpose(jvp(layer3))/moe/experts/moe_dispatch/sort:", 10),
            ("transpose(jvp(layer3))/moe/experts/moe_experts/moe_gmm_pallas/pallas_call:", 25),
            ("transpose(jvp(layer3))/moe/experts/moe_combine/gather:", 10),
            ("transpose(jvp(layer3))/moe/experts/moe_shared/dot_general:", 10),
            ("opt_update/reduce_sum:", 40),
        ]
        at = i * 600 * ms
        for j, (tail, dur) in enumerate(spans):
            ops.append(trace.Event(f"fusion.{j}", at, dur * ms, top + tail if scoped else top))
            at += (dur + 1) * ms
    return trace.Trace([trace.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)])


PAIRS, ROUTED, TILES_RUN, TILES_BAND = 13e6, 6_144.0, 20.0, 36.0


def learn_rows(counted: bool = True) -> list:
    row = {"idx": 0, "ts": 0.0}
    if counted:
        row.update({"moe-rows": ROUTED, "moe-rows-max-over-mean": 1.5, "moe-group-hit-share": 0.5,
                    "attn-pairs-global": PAIRS, "attn-tiles-run-global": TILES_RUN,
                    "attn-tiles-band-global": TILES_BAND, "attn-bwd-steps-global": TILES_BAND})
    return [harness.Seen(0.0, dict(row)), harness.Seen(1.0, dict(row))]


def test_the_new_readers_on_a_trace_made_by_hand():
    spec = types.SimpleNamespace(params=CONFIG["params"], traffic={})

    def run_with(tr, counted=True):
        return types.SimpleNamespace(
            trace=tr, spec=spec, transitions_per_update=8192, device={"kind": "TPU v5 lite"},
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
    assert got["kernel.kda_ms_per_update"] == pytest.approx(180.0)
    assert got["kernel.mla_ms_per_update"] == pytest.approx(60.0)
    assert got["kernel.mla_latent_ms_per_update"] == pytest.approx(18.0)
    assert got["kernel.moe_ms_per_update"] == pytest.approx(60.0)
    assert got["kernel.moe_route_ms_per_update"] == pytest.approx(25.0)
    assert got["step.opt_ms_per_update"] == pytest.approx(40.0)
    assert got["moe.rows_max_over_mean"] == pytest.approx(1.5)
    assert got["moe.group_hit_share"] == pytest.approx(50.0)
    assert got["gdn_attn.tiles_run_share"] == pytest.approx(100 * 20 / 36)
    assert got["attn.bwd_steps_run_share"] == pytest.approx(100 * 20 / 36)
    # the scan: memory-bound, 6 layers x 8,192 tokens x 3 passes x 114,816 B over 140 ms
    share, extra = got["kda_scan_roofline"]
    ops, nbytes = flops_ling_flash.kda_train(CONFIG["params"], 1)
    assert extra == {"bound": "memory"} and nbytes / 819e9 > ops / 197e12
    assert nbytes == 6 * 8192 * 3 * 114_816
    assert share == pytest.approx(100 * (nbytes / 819e9) / 140e-3) and 14 < share < 15
    # 13M kept pairs x 32 x 640 x 3 = 0.80 TFLOP, 4.1 ms at the peak, over 30 ms of kernel
    share, extra = got["kda_attn_flash_roofline"]
    assert extra == {"bound": "compute", "pairs": PAIRS}
    assert share == pytest.approx(100 * 3 * 13e6 * 32 * 640 / 197e12 / 30e-3) and 13 < share < 14
    share, extra = got["kda_moe_gmm_roofline"]
    ops, nbytes = flops_ling_flash.gmm_train(CONFIG["params"], ROUTED)
    assert extra == {"bound": "memory", "routed_rows": ROUTED} and nbytes / 819e9 > ops / 197e12
    assert share == pytest.approx(100 * (nbytes / 819e9) / 25e-3) and 0 < share < 100
    want = flops_ling_flash.update(CONFIG["params"], 1, PAIRS, ROUTED) * 2 / 1.2 / 197e12
    share, extra = got["step.kda_moe_mfu"]
    assert extra == {"bound": "compute", "pairs": PAIRS, "routed_rows": ROUTED}
    assert share == pytest.approx(100 * want) and 15 < share < 25
