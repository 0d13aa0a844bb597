"""The ``glm-4.7-flash.learner`` cell: its configuration file against the
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

from benchmarks import flops_glm4_moe_lite, harness, run, trace

CELL = "glm-4.7-flash.learner"
TINY_ARCH = dict(
    hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1, rms_norm_eps=1e-5,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32, rope_theta=1000000,
    rope_scaling=None, partial_rotary_factor=1, intermediate_size=160,
    moe_intermediate_size=48, n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=1.8, topk_method="noaux_tc", n_group=1,
    topk_group=1, expert_parallel=dict(published_n_routed_experts=16, chips=2, rank=0),
)
TINY = [f"params.arch={json.dumps(TINY_ARCH)}", "params.seq_len=32", "params.obs_shape=[6]",
        "params.action_space=3", 'params.compute_dtype="float32"',
        "windows.pool=8", "windows.episode_len_mean=16",
        "trace.start_update=4", "trace.updates=4"]
BENCH = harness.load_json(f"{harness.ROOT}/BENCHMARK.json")
CONFIG = harness.load_json(f"{harness.HERE}/configs/glm-4.7-flash.json")
NEW = {"kernel.mla_ms_per_update", "kernel.mla_latent_ms_per_update", "mla_attn_flash_roofline",
       "mla_moe_gmm_roofline", "step.mla_moe_mfu"}
EXTENDED = {"kernel.moe_ms_per_update", "kernel.moe_route_ms_per_update",
            "moe.rows_max_over_mean", "step.opt_ms_per_update", "gdn_attn.tiles_run_share"}
COUNTERS = {"gdn_attn.tiles_run_share", "moe.rows_max_over_mean"}  # read in an untraced run too
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]


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
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "glm-4.7-flash"]
    assert entry["file"] == "benchmarks/configs/glm-4.7-flash.json"
    assert CONFIG["reduced"] == entry["reduced"] == REDUCED
    assert set(CONFIG["published"]) == set(REDUCED) and "vocab_size" not in CONFIG
    assert CONFIG["published"] == {"num_hidden_layers": 47, "n_routed_experts": 64,
                                   "vocab_size": 154880, "num_nextn_predict_layers": 1}
    # the leading dense layer and four expert layers (the floor), every width as published
    assert (arch["num_hidden_layers"], arch["first_k_dense_replace"]) == (5, 1)
    assert arch["num_nextn_predict_layers"] == 0
    assert arch["expert_parallel"] == {"published_n_routed_experts": 64, "chips": 8, "rank": 0}
    assert arch["n_routed_experts"] * 8 == CONFIG["published"]["n_routed_experts"]
    for key in REDUCED:
        assert key in CONFIG["assumed"], key
    for key in ("rope", "norms", "group_limit", "correction_bias", "precision", "initialisation",
                "obs_shape", "seq_len", "batch_size", "act_mode", "loss_log_interval", "K_epoch",
                "lr"):
        assert key in CONFIG["assumed"], key
    assert "eight" in CONFIG["deployment"] and "42" in CONFIG["deployment"]
    assert set(CONFIG["parity"]["routed"]) == {"rows", "tol", "delta", "flip_share"}
    assert CONFIG["parity"]["reference"] == "glm4_moe_lite" and "GiB" in CONFIG["batch_choice"]
    assert "512,147,977" in CONFIG["assumed"]["n_routed_experts"]
    assert "1,024" in CONFIG["assumed"]["n_routed_experts"]
    assert "8,192" in CONFIG["assumed"]["n_routed_experts"]
    assert CONFIG["expect_paths"] == ["mla", "attn_flash_pallas", "moe_experts",
                                      "moe_gmm_pallas", "moe_row_add_pallas"]


def test_the_published_keys_are_the_catalogs():
    """Every key of the catalog's ``config`` for this model is in the file at
    its published value, unless ``reduced`` lists it."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(catalog)]
    except FileNotFoundError:
        pytest.skip("no catalog in this installation")
    (row,) = [r for r in rows if r["name"] == "GLM-4.7-Flash"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "glm-4.7-flash"]
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_cell_and_its_traffic():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": "glm-4.7-flash", "traffic": "learner-long", "chips": 1}
    assert "1 x 16,384" in cell["why"] and "eighth" in cell["why"] and len(cell["why"]) <= 200
    mix = harness.load_json(f"{harness.HERE}/traffic/learner-long.json")
    assert mix["runner"] == "learner_feed_routed"
    assert mix["windows"]["episode_len_mean"] == 8192 and mix["windows"]["pool"] == 16
    assert mix["warmup_pairs"] == 1 and CONFIG["params"]["loss_log_interval"] == 2
    params = CONFIG["params"]
    assert (params["seq_len"], params["batch_size"], params["obs_shape"],
            params["action_space"]) == (16384, 1, [64], 8)
    for name in NEW | EXTENDED:
        (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert CELL in m["workloads"] and m["moves"] == "transitions_per_s"
        assert (m["workloads"] == [CELL]) == (name in NEW)
    # additions only: the new entries are the last of their lists
    assert BENCH["configs"][-1]["name"] == "glm-4.7-flash" and BENCH["workloads"][-1] == cell
    assert {m["name"] for m in BENCH["per_layer"][-5:]} == NEW


def test_the_parameter_count_from_shapes():
    """One dense layer of 84,677,888 and four expert layers of 106,829,120
    (21,759,232 of each latent attention) + the projection, the last norm and
    the heads. Built from shapes (``jax.eval_shape``): the weights are never
    made."""
    from tpu_rl.config import Config
    from tpu_rl.models.families import build_family

    family = build_family(Config.from_dict(CONFIG["params"]))
    tree = jax.eval_shape(lambda k: family.init_params(k), jax.random.key(0))
    sizes = {jax.tree_util.keystr(p): int(np.prod(x.shape))
             for p, x in jax.tree_util.tree_leaves_with_path(tree)}
    assert sum(sizes.values()) == 512_147_977
    layer = lambda i: sum(n for k, n in sizes.items() if f"'layer{i}'" in k)  # noqa: E731
    attention = lambda i: sum(  # noqa: E731
        n for k, n in sizes.items() if f"'layer{i}'" in k and "'attention'" in k)
    assert layer(0) == flops_glm4_moe_lite.layer_parameters(CONFIG["params"], True) == 84_677_888
    assert {layer(i) for i in (1, 2, 3, 4)} == {106_829_120}
    assert {attention(i) for i in range(5)} == {21_759_232}
    routed = sum(n for k, n in sizes.items() if "w_gate" in k or "w_in" in k or "w_out" in k)
    assert routed == 4 * 75_497_472 and 0.58 < routed / 512_147_977 < 0.60
    assert sum(sizes.values()) * 16 / 1e9 == pytest.approx(8.19, abs=0.01)  # GB at 16 B each
    ctx = 16384
    assert family.carry_widths == (0, 5 * ctx * (512 + 64) + 1)  # 47M floats an env
    assert 5 * ctx * 2 * 20 * 256 == 838_860_800  # a full-width K/V ring's


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e: {e!r}")


def test_update_program_compiles_for_v5e_and_fits(v5e):
    """The published widths, batch 1 x 16,384: under a minute of compiling.
    Built from shapes (``jax.eval_shape``): 0.51B parameters are never made."""
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
        paths = program_paths(lowered)
        assert set(CONFIG["expect_paths"]) <= set(paths["paths"]) and paths["mosaic_calls"] > 0
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    hbm = flops.peaks("TPU v5 lite")["hbm_bytes"]
    assert 0.25 * hbm < used < 12.0 * 2**30  # fills the chip, and fits beside the checks' buffers
    assert used / 2**30 == pytest.approx(10.0, abs=0.4)  # what batch_choice quotes


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
    # two expert layers of three: the dense layer has no assignment
    assert routed["err"]["flip_share"] == 0 and routed["assignments"] == 2 * 1 * 32 * 4
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
    assert line["metrics"]["feed.h2d_bytes_per_update"]["value"] == 1 * 32 * (6 + 3 + 6) * 4
    # a 32-step window is one tile: the grid is too small to read the seams
    assert line["metrics"]["gdn_attn.tiles_run_share"]["value"] == 100.0
    assert not ((NEW | EXTENDED) - COUNTERS) & got and "breakdown" not in line


# -------------------------------------------------------------------- the readers
def hand_made_trace(scoped: bool = True) -> trace.Trace:
    """Four executions of a 590 ms update program, 600 ms apart: a window of
    two periods. Each holds 360 ms under ``mla`` (30 the down projections, 50
    the up projections and the assembly, 10 the rotation, 240 the kernels, 30
    the output projection), 40 ms under ``mlp``; under ``moe``: 5 ms route,
    15 ms dispatch, 40 ms experts, 20 ms combine, 10 ms the shared expert; and
    40 ms under ``opt_update``."""
    ms = 1e6
    modules = [trace.Event("jit_train_step", i * 600 * ms, 590 * ms) for i in range(4)]
    ops = []
    for i in range(4):
        top = "jit(train_step)/"
        spans = [
            ("transpose(jvp(layer1))/mla/attention/mla_down/q_a_proj/dot_general:", 30),
            ("transpose(jvp(layer1))/mla/attention/mla_up/kv_b_proj/dot_general:", 50),
            ("transpose(jvp(layer1))/mla/attention/attn_rope/mul:", 10),
            ("transpose(jvp(layer1))/mla/attention/attn_flash_pallas/pallas_call:", 240),
            ("transpose(jvp(layer1))/mla/attention/mla_o/o_proj/dot_general:", 30),
            ("transpose(jvp(layer0))/mlp/gate_proj/dot_general:", 40),
            ("transpose(jvp(layer1))/moe/experts/moe_route/top_k:", 5),
            ("transpose(jvp(layer1))/moe/experts/moe_dispatch/sort:", 15),
            ("transpose(jvp(layer1))/moe/experts/moe_experts/moe_gmm_pallas/pallas_call:", 40),
            ("transpose(jvp(layer1))/moe/experts/moe_combine/gather:", 20),
            ("transpose(jvp(layer1))/moe/experts/moe_shared/dot_general:", 10),
            ("opt_update/reduce_sum:", 40),
        ]
        at = i * 600 * ms
        for j, (tail, dur) in enumerate(spans):
            ops.append(trace.Event(f"fusion.{j}", at, dur * ms, top + tail if scoped else top))
            at += (dur + 1) * ms
    return trace.Trace([trace.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)])


PAIRS, ROUTED, TILES_RUN, TILES_BAND = 375e6, 32_768.0, 540.0, 680.0


def learn_rows(counted: bool = True) -> list:
    row = {"idx": 0, "ts": 0.0}
    if counted:
        row.update({"moe-rows": ROUTED, "moe-rows-max-over-mean": 1.5, "attn-pairs-global": PAIRS,
                    "attn-tiles-run-global": TILES_RUN, "attn-tiles-band-global": TILES_BAND})
    return [harness.Seen(0.0, dict(row)), harness.Seen(1.0, dict(row))]


def test_the_new_readers_on_a_trace_made_by_hand():
    spec = types.SimpleNamespace(params=CONFIG["params"], traffic={})

    def run_with(tr, counted=True, params=None):
        return types.SimpleNamespace(
            trace=tr, spec=spec if params is None else types.SimpleNamespace(params=params),
            transitions_per_update=16384, device={"kind": "TPU v5 lite"},
            window=types.SimpleNamespace(rows=learn_rows(counted)))

    tr = hand_made_trace()
    assert tr.n_steps == 2 and tr.window_s == pytest.approx(1.2)
    got = {}
    for name in NEW | EXTENDED:
        reader = harness.load_module(f"{harness.HERE}/metrics/{name}.py")
        got[name] = reader.read(run_with(tr))
        if name not in COUNTERS:
            assert reader.read(run_with(None)) is None
        # a program without the scopes and the counters: nothing to read, no error
        assert reader.read(run_with(hand_made_trace(scoped=False), counted=False)) is None
    other = harness.load_json(f"{harness.HERE}/configs/smallthinker-21b-a3b.json")["params"]
    for name in ("mla_attn_flash_roofline", "mla_moe_gmm_roofline", "step.mla_moe_mfu"):
        reader = harness.load_module(f"{harness.HERE}/metrics/{name}.py")
        assert reader.read(run_with(tr, params=other)) is None  # another family's cell
    assert got["kernel.mla_ms_per_update"] == pytest.approx(360.0)
    assert got["kernel.mla_latent_ms_per_update"] == pytest.approx(80.0)
    assert got["kernel.moe_ms_per_update"] == pytest.approx(90.0)
    assert got["kernel.moe_route_ms_per_update"] == pytest.approx(40.0)
    assert got["step.opt_ms_per_update"] == pytest.approx(40.0)
    assert got["moe.rows_max_over_mean"] == pytest.approx(1.5)
    assert got["gdn_attn.tiles_run_share"] == pytest.approx(100 * 540 / 680)
    attn = harness.load_module(f"{harness.HERE}/metrics/kernel.attn_ms_per_update.py")
    inside = attn.read(run_with(tr))  # the kernels' scope lies inside the mixer's
    assert inside == pytest.approx(240.0) and inside <= got["kernel.mla_ms_per_update"]
    # 375M kept pairs x 20,480 x 3 = 23 TFLOP, 117 ms at the peak, over 240 ms of kernel
    share, extra = got["mla_attn_flash_roofline"]
    assert extra == {"bound": "compute", "pairs": PAIRS}
    assert share == pytest.approx(100 * 3 * 375e6 * 20_480 / 197e12 / 240e-3) and 48 < share < 49
    share, extra = got["mla_moe_gmm_roofline"]
    ops, nbytes = flops_glm4_moe_lite.gmm_train(CONFIG["params"], ROUTED)
    assert extra == {"bound": "compute", "routed_rows": ROUTED} and ops / 197e12 > nbytes / 819e9
    assert share == pytest.approx(100 * (ops / 197e12) / 40e-3) and 0 < share < 100
    share, extra = got["step.mla_moe_mfu"]
    want = flops_glm4_moe_lite.update(CONFIG["params"], 1, PAIRS, ROUTED) * 2 / 1.2 / 197e12
    assert extra == {"bound": "compute"}
    assert share == pytest.approx(100 * want) and 38 < share < 39
