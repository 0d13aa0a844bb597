"""The ``smallthinker-21b-a3b.learner`` cell: its configuration file against
the contract (the published keys, the cut, what is assumed, the parameter
count from shapes), the cell rehearsed end to end on the CPU at tiny widths
through ``run.main``, the real data files and the ``learner_feed_routed``
runner (the device check replaced, as in ``test_runners.py``), and its readers
on a trace made by hand. What comes out is control flow and counts, never a
device number."""

import json
import types

import jax
import numpy as np
import pytest

from benchmarks import flops_smallthinker, harness, run, trace

CELL = "smallthinker-21b-a3b.learner"
TINY_ARCH = dict(
    hidden_size=64, num_hidden_layers=4, rms_norm_eps=1e-6, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, rope_theta=1500000, rope_scaling=None,
    rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1], sliding_window_size=8,
    moe_ffn_hidden_size=48, moe_num_primary_experts=4, moe_num_active_primary_experts=3,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    expert_parallel=dict(published_n_routed_experts=16, chips=4, rank=0),
)
TINY = [f"params.arch={json.dumps(TINY_ARCH)}", "params.seq_len=32", "params.obs_shape=[6]",
        "params.action_space=3", 'params.compute_dtype="float32"',
        "windows.pool=8", "windows.episode_len_mean=16",
        "trace.start_update=4", "trace.updates=4"]
BENCH = harness.load_json(f"{harness.ROOT}/BENCHMARK.json")
CONFIG = harness.load_json(f"{harness.HERE}/configs/smallthinker-21b-a3b.json")
NEW = {"kernel.attn_window_ms_per_update", "kernel.attn_global_ms_per_update",
       "swa_attn_flash_roofline", "moe_glu_gmm_roofline", "attn.window_kept_share",
       "step.swa_moe_mfu"}
EXTENDED = {"kernel.moe_ms_per_update", "kernel.moe_route_ms_per_update",
            "moe.rows_max_over_mean", "step.opt_ms_per_update"}
COUNTERS = {"attn.window_kept_share", "moe.rows_max_over_mean"}  # read in an untraced run too


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
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "smallthinker-21b-a3b"]
    assert entry["file"] == "benchmarks/configs/smallthinker-21b-a3b.json"
    assert CONFIG["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout",
        "moe_num_primary_experts", "vocab_size"]
    assert set(CONFIG["published"]) == set(CONFIG["reduced"]) and "vocab_size" not in CONFIG
    published = CONFIG["published"]
    assert published["num_hidden_layers"] == 52 == len(published["rope_layout"])
    assert published["rope_layout"] == published["sliding_window_layout"] == [0, 1, 1, 1] * 13
    # one whole period of both layouts, every other key as published
    assert arch["rope_layout"] == arch["sliding_window_layout"] == published["rope_layout"][:4]
    assert arch["num_hidden_layers"] == 4
    assert arch["expert_parallel"] == {"published_n_routed_experts": 64, "chips": 4, "rank": 0}
    assert arch["moe_num_primary_experts"] * 4 == published["moe_num_primary_experts"]
    for key in CONFIG["reduced"]:
        assert key in CONFIG["assumed"], key
    for key in ("expert_activation", "router_input", "router", "rope", "sliding_window_size",
                "attention_bias", "precision", "batch_size", "act_mode", "lr"):
        assert key in CONFIG["assumed"], key
    assert CONFIG["params"]["seq_len"] == arch["max_position_embeddings"] == 16384
    assert set(CONFIG["parity"]["routed"]) == {"rows", "tol", "delta", "flip_share"}
    assert CONFIG["parity"]["reference"] == "smallthinker" and "GiB" in CONFIG["batch_choice"]
    assert "462,241,289" in CONFIG["assumed"]["moe_num_primary_experts"]


def test_the_published_keys_are_the_catalogs():
    """Every key of the catalog's ``config`` for this model is in the file at
    its published value, unless ``reduced`` lists it."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(catalog)]
    except FileNotFoundError:
        pytest.skip("no catalog in this installation")
    (row,) = [r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "smallthinker-21b-a3b"]
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def params_of(mix: dict) -> dict:
    return {**CONFIG["params"], **mix.get("params", {})}


def test_the_cell_and_its_traffic():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": "smallthinker-21b-a3b", "traffic": "learner-long", "chips": 1}
    mix = harness.load_json(f"{harness.HERE}/traffic/learner-long.json")
    assert mix["runner"] == "learner_feed_routed"
    assert mix["windows"] == {"pool": 16, "episode_len_mean": 8192, "obs_scale": 1.0,
                              "rew_scale": 0.1, "carry_scale": 0.0}
    assert mix["trace"] == {"start_update": 6, "updates": 4}  # the issue's
    # one line of the window before the capture: lines fall on updates 2, 4, 6, ...
    assert mix["warmup_pairs"] == 1 and params_of(mix)["loss_log_interval"] == 2
    params = CONFIG["params"]
    assert (params["seq_len"], params["batch_size"], params["obs_shape"],
            params["action_space"]) == (16384, 2, [64], 8)
    for name in NEW | EXTENDED:
        (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert CELL in m["workloads"] and m["moves"] == "transitions_per_s"
        assert (m["workloads"] == [CELL]) == (name in NEW)


def test_the_parameter_count_from_shapes():
    """462,049,280 in the four layers (115,512,320 each, 94,371,840 of them
    the 16 held experts) + the projection, the last norm and the heads. Built
    from shapes (``jax.eval_shape``): the weights are never made."""
    from tpu_rl.config import Config
    from tpu_rl.models.families import build_family

    family = build_family(Config.from_dict(CONFIG["params"]))
    tree = jax.eval_shape(lambda k: family.init_params(k), jax.random.key(0))
    sizes = {jax.tree_util.keystr(p): int(np.prod(x.shape))
             for p, x in jax.tree_util.tree_leaves_with_path(tree)}
    assert sum(sizes.values()) == 462_241_289
    layer0 = sum(n for k, n in sizes.items() if "'layer0'" in k)
    assert layer0 == 115_512_320 == 20_971_520 + 163_840 + 5_120 + 16 * 5_898_240
    routed = sum(n for k, n in sizes.items() if "w_gate" in k or "w_in" in k or "w_out" in k)
    assert routed == 4 * 94_371_840 and 0.81 < routed / 462_241_289 < 0.82
    assert sum(sizes.values()) * 16 / 1e9 == pytest.approx(7.40, abs=0.01)  # GB at 16 B each
    assert family.carry_widths == (0, (16384 + 3 * 4096) * 2 * 4 * 128 + 1)


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
    # 32-step windows, episodes of ~16, a window of 8: a window layer keeps about half
    assert 30 < line["metrics"]["attn.window_kept_share"]["value"] < 80
    assert not ((NEW | EXTENDED) - COUNTERS) & got and "breakdown" not in line


# -------------------------------------------------------------------- the readers
def hand_made_trace(scoped: bool = True) -> trace.Trace:
    """Four executions of a 790 ms update program, 800 ms apart: a window of
    two periods. Each holds 90 ms under ``attn_global`` (80 of them the
    kernel) and 150 ms under ``attn_window`` (4 the rotation, 120 the kernel);
    under ``moe``: 5 ms route, 20 ms dispatch, 100 ms experts, 25 ms combine;
    and 40 ms under ``opt_update``."""
    ms = 1e6
    modules = [trace.Event("jit_train_step", i * 800 * ms, 790 * ms) for i in range(4)]
    ops = []
    for i in range(4):
        top = "jit(train_step)/"
        spans = [
            ("transpose(jvp(layer0))/attn_global/attention/q_proj/dot_general:", 10),
            ("transpose(jvp(layer0))/attn_global/attention/attn_flash_pallas/pallas_call:", 80),
            ("transpose(jvp(layer1))/attn_window/attention/q_proj/dot_general:", 26),
            ("transpose(jvp(layer1))/attn_window/attention/attn_rope/mul:", 4),
            ("transpose(jvp(layer1))/attn_window/attention/attn_flash_pallas/pallas_call:", 120),
            ("transpose(jvp(layer1))/moe/experts/moe_route/top_k:", 5),
            ("transpose(jvp(layer1))/moe/experts/moe_dispatch/sort:", 20),
            ("transpose(jvp(layer1))/moe/experts/moe_experts/moe_gmm_pallas/pallas_call:", 100),
            ("transpose(jvp(layer1))/moe/experts/moe_combine/gather:", 25),
            ("opt_update/reduce_sum:", 40),
        ]
        at = i * 800 * ms
        for j, (tail, dur) in enumerate(spans):
            ops.append(trace.Event(f"fusion.{j}", at, dur * ms, top + tail if scoped else top))
            at += (dur + 1) * ms
    return trace.Trace([trace.DeviceTrace("/device:TPU:0", ops=ops, modules=modules)])


PAIRS_GLOBAL, PAIRS_WINDOW, ROUTED = 150e6, 270e6, 196_608.0


def learn_rows(counted: bool = True) -> list:
    row = {"idx": 0, "ts": 0.0}
    if counted:
        row.update({"moe-rows": ROUTED, "moe-rows-max-over-mean": 1.5,
                    "attn-pairs-global": PAIRS_GLOBAL, "attn-pairs-window": PAIRS_WINDOW})
    return [harness.Seen(0.0, dict(row)), harness.Seen(1.0, dict(row))]


def test_the_new_readers_on_a_trace_made_by_hand():
    spec = types.SimpleNamespace(params=CONFIG["params"], traffic={})

    def run_with(tr, counted=True):
        return types.SimpleNamespace(
            trace=tr, spec=spec, transitions_per_update=32768, device={"kind": "TPU v5 lite"},
            window=types.SimpleNamespace(rows=learn_rows(counted)))

    tr = hand_made_trace()
    assert tr.n_steps == 2 and tr.window_s == pytest.approx(1.6)
    got = {}
    for name in NEW | EXTENDED:
        reader = harness.load_module(f"{harness.HERE}/metrics/{name}.py")
        got[name] = reader.read(run_with(tr))
        if name not in COUNTERS:
            assert reader.read(run_with(None)) is None
        # the parent's program: no such scope, no such counter — nothing to read, no error
        assert reader.read(run_with(hand_made_trace(scoped=False), counted=False)) is None
    assert got["kernel.attn_global_ms_per_update"] == pytest.approx(90.0)
    assert got["kernel.attn_window_ms_per_update"] == pytest.approx(150.0)
    assert got["kernel.moe_ms_per_update"] == pytest.approx(150.0)
    assert got["kernel.moe_route_ms_per_update"] == pytest.approx(50.0)
    assert got["step.opt_ms_per_update"] == pytest.approx(40.0)
    assert got["attn.window_kept_share"] == pytest.approx(60.0)
    assert got["moe.rows_max_over_mean"] == pytest.approx(1.5)
    # 420M kept pairs x 4 x 3584 x 3 = 18.1 TFLOP, 91.7 ms at the peak, over 200 ms of kernel
    share, extra = got["swa_attn_flash_roofline"]
    assert extra == {"bound": "compute", "pairs": PAIRS_GLOBAL + PAIRS_WINDOW}
    assert share == pytest.approx(100 * 3 * 420e6 * 4 * 3584 / 197e12 / 200e-3) and 45 < share < 47
    share, extra = got["moe_glu_gmm_roofline"]
    ops, nbytes = flops_smallthinker.gmm_train(CONFIG["params"], ROUTED)
    assert extra == {"bound": "compute", "routed_rows": ROUTED} and ops / 197e12 > nbytes / 819e9
    assert share == pytest.approx(100 * (ops / 197e12) / 100e-3) and 35 < share < 36
    want = flops_smallthinker.update(CONFIG["params"], 2, 420e6, ROUTED) * 2 / 1.6 / 197e12
    assert got["step.swa_moe_mfu"] == pytest.approx(100 * want) and 25 < 100 * want < 28


@pytest.mark.parametrize("warmup_pairs, reads", [(1, True), (2, False)], ids=["one-line", "two-lines"])
def test_the_capture_leaves_one_interval_outside_it_whatever_the_flush_takes(warmup_pairs, reads):
    """``trace.observer_slowdown`` must be in every traced line. The lines of a
    traced run whose ``profiler-window`` took 16.2 s (chiprun_out/pr32/traced2,
    PR 32): 2, 4, 6, 8 at 1.89 s, then 10 as the window closes. With the mix's
    one warm-up line the interval 2-4 lies before the capture of updates 6-9;
    with two, nothing does."""
    mix = harness.load_json(f"{harness.HERE}/traffic/learner-long.json")
    seen = [harness.Seen(t, {"idx": i}) for t, i in
            ((0.0, 2), (1.89, 4), (3.78, 6), (5.67, 8), (21.87, 10))]
    run = types.SimpleNamespace(
        spec=types.SimpleNamespace(traffic=mix),
        trace=types.SimpleNamespace(window_s=3.9, n_steps=4),
        window=types.SimpleNamespace(start=seen[warmup_pairs - 1], rows=seen[warmup_pairs:]))
    got = harness.load_module(f"{harness.HERE}/metrics/trace.observer_slowdown.py").read(run)
    assert (got == pytest.approx(100 * (0.975 / 0.945 - 1))) if reads else got is None
    assert (warmup_pairs == mix["warmup_pairs"]) == reads
