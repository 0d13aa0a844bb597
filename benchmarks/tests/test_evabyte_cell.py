"""The ``evabyte.learner`` cell: its configuration file against the contract
(the published keys, the cut, what is assumed, the parameter count from
shapes), the cell rehearsed end to end on the CPU at tiny widths through
``run.main``, the real data files and the ``learner_feed`` runner (the device
check replaced, as in ``test_runners.py``), its update program compiled for a
described v5e, and its readers on a trace made by hand. What comes out is
control flow and counts, never a device number."""

import json
import types

import jax
import numpy as np
import pytest

from benchmarks import flops_evabyte, harness, run, trace

CELL = "evabyte.learner"
TINY_ARCH = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
    window_size=32, chunk_size=4, intermediate_size=96, rms_norm_eps=1e-5, rope_theta=100000,
    norm_add_unit_offset=True, init_std=0.05, attention_class="eva",
)
TINY = [f"params.arch={json.dumps(TINY_ARCH)}", "params.seq_len=128", "params.obs_shape=[6]",
        "params.action_space=3", 'params.compute_dtype="float32"',
        "windows.pool=8", "windows.episode_len_mean=64",
        "trace.start_update=4", "trace.updates=4"]
BENCH = harness.load_json(f"{harness.ROOT}/BENCHMARK.json")
CONFIG = harness.load_json(f"{harness.HERE}/configs/evabyte.json")
NEW = {"kernel.eva_ms_per_update", "kernel.eva_pool_ms_per_update", "eva_attn_flash_roofline",
       "eva_pool_roofline", "attn.summary_pair_share", "step.eva_mfu"}
EXTENDED = {"step.opt_ms_per_update"}
COUNTERS = {"attn.summary_pair_share"}  # read in a CPU rehearsal's traced run too
REDUCED = ["num_hidden_layers", "vocab_size", "num_pred_heads"]


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
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "evabyte"]
    assert entry["file"] == "benchmarks/configs/evabyte.json"
    assert CONFIG["reduced"] == entry["reduced"] == REDUCED
    assert CONFIG["published"] == {"num_hidden_layers": 32, "vocab_size": 320, "num_pred_heads": 8}
    assert "vocab_size" not in CONFIG and "num_pred_heads" not in CONFIG
    assert (arch["num_hidden_layers"], arch["num_attention_heads"], arch["num_key_value_heads"],
            arch["hidden_size"], arch["intermediate_size"], arch["window_size"],
            arch["chunk_size"]) == (4, 32, 32, 4096, 11008, 2048, 16)
    for key in ("num_hidden_layers", "vocab_size", "head_dim", "pooling",
                "summary_from_the_next_block", "one_normaliser", "episode_grid", "rope", "mlp",
                "precision", "init", "obs_shape", "seq_len", "batch_size", "act_mode",
                "loss_log_interval", "K_epoch", "lr"):
        assert key in CONFIG["assumed"], key
    assert "eight pipeline stages" in CONFIG["deployment"]
    assert "no layer is shared between chips" in CONFIG["deployment"]
    assert "809,873,417" in CONFIG["assumed"]["num_hidden_layers"]
    assert CONFIG["parity"]["reference"] == "evabyte" and "routed" not in CONFIG["parity"]
    assert set(CONFIG["parity"]["tol"]) == {"logits", "value", "loss"}
    assert "GiB" in CONFIG["batch_choice"] and "B a parameter" in CONFIG["batch_choice"]
    assert CONFIG["expect_paths"] == ["eva", "eva_pool", "attn_flash_pallas", "attn_bwd_pallas"]


def test_the_published_keys_are_the_catalogs():
    """Every key of the catalog's ``config`` for this model is in the file at
    its published value, unless ``reduced`` lists it."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(catalog)]
    except FileNotFoundError:
        pytest.skip("no catalog in this installation")
    (row,) = [r for r in rows if r["name"] == "EvaByte"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "evabyte"]
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_cell_and_its_traffic():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": "evabyte", "traffic": "learner-long-dense", "chips": 1}
    assert "1 x 16,384" in cell["why"] and len(cell["why"]) <= 200
    mix = harness.load_json(f"{harness.HERE}/traffic/learner-long-dense.json")
    long = harness.load_json(f"{harness.HERE}/traffic/learner-long.json")
    assert mix["runner"] == "learner_feed" and long["runner"] == "learner_feed_routed"
    # learner-long's in all but the runner and the episodes' length
    assert mix["windows"] == {**long["windows"], "episode_len_mean": 16384}
    for key in ("warmup_pairs", "warmup_timeout_s", "trace"):
        assert mix[key] == long[key], key
    params = CONFIG["params"]
    assert (params["seq_len"], params["batch_size"], params["obs_shape"],
            params["action_space"], params["loss_log_interval"]) == (16384, 1, [64], 8, 2)
    assert CONFIG["parity"]["rows"] == 1  # the timed batch
    for name in NEW | EXTENDED:
        (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert CELL in m["workloads"] and m["moves"] == "transitions_per_s"
        assert (m["workloads"] == [CELL]) == (name in NEW)
        assert m["workloads"][-1] == CELL  # appended, nothing else changed
    # additions only: the new entries are the last of their lists
    assert BENCH["configs"][-1]["name"] == "evabyte" and BENCH["workloads"][-1] == cell
    assert {m["name"] for m in BENCH["per_layer"][-6:]} == NEW
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


def test_the_parameter_count_from_shapes():
    """Four layers of 202,391,552 + the projection, the last norm and the
    heads. Built from shapes (``jax.eval_shape``): the weights are never made."""
    from tpu_rl.config import Config
    from tpu_rl.models.families import build_family

    family = build_family(Config.from_dict(CONFIG["params"]))
    tree = jax.eval_shape(lambda k: family.init_params(k), jax.random.key(0))
    sizes = {jax.tree_util.keystr(p): int(np.prod(x.shape))
             for p, x in jax.tree_util.tree_leaves_with_path(tree)}
    assert sum(sizes.values()) == flops_evabyte.parameters(CONFIG["params"]) == 809_873_417
    layer = lambda i: sum(n for k, n in sizes.items() if f"'layer{i}'" in k)  # noqa: E731
    assert {layer(i) for i in range(4)} == {
        flops_evabyte.layer_parameters(CONFIG["params"])} == {202_391_552}
    assert sum(n for k, n in sizes.items() if "pool_" in k) == 4 * 2 * 32 * 128
    assert not any("bias" in k and "layer" in k for k in sizes)
    ctx = 16384
    # per layer an exact ring of 2,048 and 1,024 summaries, keys and values; the counter
    assert family.carry_widths == (0, 4 * 2 * (2048 + ctx // 16) * 4096 + 1)
    assert sum(sizes.values()) * 4 / 2**30 > 1  # the actor tree is over the broadcast's frame


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e: {e!r}")


def test_update_program_compiles_for_v5e_and_fits(v5e, monkeypatch):
    """The published widths, batch 1 x 16,384: about a minute of compiling, as
    a cell without ``mesh_data`` runs it (a plain ``jax.jit``), with the chip's
    own VMEM reading steered into the tracing process. The rows' walk with the
    logsumexp as an output lowers at 32 heads of 128 and the repo's own
    backward is in the program. Built from shapes: 0.81B parameters are never
    made."""
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
    assert 0.25 * hbm < mem.peak_memory_in_bytes < 13.0 * 2**30  # fills the chip, and fits
    assert mem.peak_memory_in_bytes / 2**30 == pytest.approx(10.58, abs=0.3)  # batch_choice's
    # float32 weights and RMSprop's nu come in: 8 B a parameter
    assert mem.argument_size_in_bytes / 809_873_417 == pytest.approx(8.0, abs=0.01)


# ------------------------------------------------------------------ the rehearsal
def test_rehearsal(any_device, capsys):
    line = result_line(capsys, trace=0, seconds=4)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"transitions_per_s", "setup_s", "peak_hbm_gib"} >= set(line["metrics"]) >= {
        "transitions_per_s", "setup_s"}
    checks = line["checks"]
    assert not checks["device"] and not line["correct"]  # a CPU is never correct
    assert checks["parity"] and checks["losses_finite"] and checks["no_compile_in_window"]
    assert not checks["kernel_path"]  # no Mosaic kernel on a CPU
    assert line["parity"]["err"]["logits"] < 1e-4  # float32 against the reference, free-running
    assert line["parity"]["err"]["value"] < 1e-4


def test_a_traced_rehearsal_reads_the_counters_and_leaves_the_rest(any_device, capsys):
    """No TPU plane in a CPU capture: every device-trace reader finds nothing
    and is left out. The counters are read from ``learn.jsonl``."""
    line = result_line(capsys, trace=1, seconds=6)
    got = set(line["metrics"])
    assert {"feed.wait_share", "feed.h2d_bytes_per_update"} | COUNTERS <= got
    assert line["metrics"]["feed.h2d_bytes_per_update"]["value"] == 128 * (6 + 3 + 6) * 4
    assert 0 < line["metrics"]["attn.summary_pair_share"]["value"] < 100
    assert not ((NEW | EXTENDED) - COUNTERS) & got and "breakdown" not in line


# -------------------------------------------------------------------- the readers
def hand_made_trace(scoped: bool = True) -> trace.Trace:
    """Four executions of a 990 ms update program, 1,000 ms apart: a window of
    two periods. Each holds 360 ms under ``eva`` (150 the three projections,
    10 the rotation, 40 the pooling, 60 the kernels — 25 of it the backward's
    own —, 50 the summaries' read, 50 ``o_proj``), 500 ms under ``mlp`` and
    100 ms under ``opt_update``."""
    ms = 1e6
    modules = [trace.Event("jit_train_step", i * 1000 * ms, 990 * ms) for i in range(4)]
    ops = []
    for i in range(4):
        top = "jit(train_step)/"
        eva = "transpose(jvp(layer2))/eva/attention/"
        spans = [
            (eva + "eva_qkv/q_proj/dot_general:", 150),
            (eva + "attn_rope/mul:", 10),
            (eva + "eva_pool/checkpoint/gather:", 40),
            (eva + "attn_flash_pallas/pallas_call:", 35),
            (eva + "attn_flash_pallas/attn_bwd_pallas/pallas_call:", 25),
            (eva + "eva_summary/checkpoint/dot_general:", 50),
            (eva + "eva_o/o_proj/dot_general:", 50),
            ("transpose(jvp(layer2))/mlp/gate_proj/dot_general:", 500),
            ("opt_update/reduce_sum:", 100),
        ]
        at = i * 1000 * ms
        for j, (tail, dur) in enumerate(spans):
            ops.append(trace.Event(f"fusion.{j}", at, dur * ms, top + tail if scoped else top))
            at += (dur + 1) * ms
    return trace.Trace([trace.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)])


BLOCK, SUMMARY = 60e6, 20e6


def learn_rows(counted: bool = True) -> list:
    row = {"idx": 0, "ts": 0.0}
    if counted:
        row.update({"attn-pairs-block": BLOCK, "attn-pairs-summary": SUMMARY})
    return [harness.Seen(0.0, dict(row)), harness.Seen(1.0, dict(row))]


def test_the_new_readers_on_a_trace_made_by_hand():
    spec = types.SimpleNamespace(params=CONFIG["params"], traffic={})

    def run_with(tr, counted=True, params=None):
        return types.SimpleNamespace(
            trace=tr, spec=spec if params is None else types.SimpleNamespace(params=params),
            transitions_per_update=16384, device={"kind": "TPU v5 lite"},
            window=types.SimpleNamespace(rows=learn_rows(counted)))

    tr = hand_made_trace()
    assert tr.n_steps == 2 and tr.window_s == pytest.approx(2.0)
    got = {}
    for name in NEW | EXTENDED:
        reader = harness.load_module(f"{harness.HERE}/metrics/{name}.py")
        got[name] = reader.read(run_with(tr))
        if name not in COUNTERS:
            assert reader.read(run_with(None)) is None
        # a program without the scopes and the counters (the parent's): nothing to read, no error
        assert reader.read(run_with(hand_made_trace(scoped=False), counted=False)) is None
    other = harness.load_json(f"{harness.HERE}/configs/glm-4.7-flash.json")["params"]
    for name in ("eva_attn_flash_roofline", "eva_pool_roofline", "step.eva_mfu"):
        reader = harness.load_module(f"{harness.HERE}/metrics/{name}.py")
        assert reader.read(run_with(tr, params=other)) is None  # another family's cell
    assert got["kernel.eva_ms_per_update"] == pytest.approx(360.0)
    assert got["kernel.eva_pool_ms_per_update"] == pytest.approx(40.0)
    assert got["step.opt_ms_per_update"] == pytest.approx(100.0)
    assert got["attn.summary_pair_share"] == pytest.approx(25.0)
    attn = harness.load_module(f"{harness.HERE}/metrics/kernel.attn_ms_per_update.py")
    assert attn.read(run_with(tr)) == pytest.approx(60.0)  # both kernels' scope
    assert got["kernel.eva_ms_per_update"] >= got["kernel.eva_pool_ms_per_update"] + 60.0
    # 80M counted pairs x 16,384 x 3 = 3.9 TFLOP, 20 ms at the peak, over 60 + 50 ms
    share, extra = got["eva_attn_flash_roofline"]
    assert extra == {"bound": "compute", "pairs": BLOCK + SUMMARY}
    assert share == pytest.approx(100 * 3 * 80e6 * 16_384 / 197e12 / 110e-3) and 18 < share < 19
    # 4 layers x 8.375 arrays of 134 MB = 4.5 GB: 5.5 ms at the peak, over 40 ms
    share, extra = got["eva_pool_roofline"]
    assert extra == {"bound": "memory"}
    want = flops_evabyte.pool_train(CONFIG["params"], 1) / 819e9 / 40e-3
    assert share == pytest.approx(100 * want) and 13 < share < 14
    share, extra = got["step.eva_mfu"]
    want = flops_evabyte.update(CONFIG["params"], 1, BLOCK + SUMMARY) * 2 / 2.0 / 197e12
    assert extra == {"bound": "compute"}
    assert share == pytest.approx(100 * want) and 0 < share < 100
