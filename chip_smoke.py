"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py                 # every phase, on a machine with a TPU
    python3 chip_smoke.py --only kernels  # a subset, while debugging

Drives the main paths once through the entry points a user calls, at the full
width of the models the repo supports, with random weights made from a seed:

- ``ppo`` / ``ppo-resume`` / ``impala``: ``python -m tpu_rl local`` — learner
  on the chip, storage/manager/workers on the CPU — at the reference's
  published width (batch 128 x seq 5 x hidden 64, CartPole-v1), unthrottled
  workers; then the same command on the same ``--result-dir``, which must
  resume from the committed checkpoint. The first phase run twice is also
  the cold vs warm reading of the compile cache.
- ``colocated``: ``python -m tpu_rl local --env-mode colocated``.
- ``kernels``: every Pallas kernel compiled (not interpreted) against its
  plain-jnp reference at a stated tolerance.
- ``wide-lstm-f32`` / ``wide-lstm-bf16`` / ``longctx-flash``: the widest
  models, through ``LearnerService`` fed by the real shm store.
- ``multichip``: with >= 4 devices, the data-parallel, colocated, sebulba and
  ring-attention paths over four chips; otherwise "skipped: N device(s)".

One process owns the chip, so this parent never imports jax: every phase that
needs the chip is a child process, run one after another. Each phase reports
the platform, device kind and count it ran on, wall and compile seconds, and
asserts on what the run recorded (``result_dir/backend-<role>.json``,
telemetry, checkpoints) instead of trusting an exit code. With no TPU the
script fails at once, prints no result, and exits nonzero.

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 21

# The reference's published width (configs/params.example.json), unthrottled:
# 2 workers x 64 envs fill a 128-window batch every 5 ticks.
REF = dict(
    env="CartPole-v1", hidden_size=64, seq_len=5, batch_size=128,
    worker_step_sleep=0.0, worker_num_envs=64, loss_log_interval=2,
    model_save_interval=4, telemetry_interval_s=0.5,
)
# The widest LSTM learner the repo supports.
WIDE = dict(
    algo="IMPALA", batch_size=1024, seq_len=16, hidden_size=1024,
    obs_shape=(64,), action_space=8,
)
LONGCTX = dict(
    algo="PPO", model="transformer", compute_dtype="bfloat16",
    attention_impl="flash", batch_size=16, seq_len=2048, hidden_size=512,
    n_heads=8, n_layers=4, obs_shape=(64,), action_space=8,
)
# name -> (LearnerService config, kernel path its train step must take on TPU)
LEARNER_PHASES = {
    # Multi-tile shape: the measured-win gate keeps the scan (models/cells.py).
    "wide-lstm-f32": (WIDE, "lstm_scan"),
    "wide-lstm-bf16": ({**WIDE, "compute_dtype": "bfloat16"}, "lstm_scan"),
    "longctx-flash": (LONGCTX, "attn_flash_pallas"),
}
PHASES = (
    "native", "ppo", "ppo-resume", "impala", "colocated", "kernels",
    *LEARNER_PHASES, "multichip",
)


class PhaseFailed(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# ------------------------------------------------------------ child plumbing
def run_child(argv: list[str], timeout: float, log_path: str) -> int:
    """Run one child in its own process group, output to ``log_path``; the
    whole group is killed on timeout so nothing this script starts outlives
    it."""
    env = dict(
        os.environ,
        PYTHONPATH=HERE + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            argv, stdout=log, stderr=subprocess.STDOUT, cwd=HERE, env=env,
            start_new_session=True,
        )
        try:
            return proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return -1
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def device_of(rec: dict) -> dict:
    return {
        "platform": rec["platform"], "kind": rec["device_kind"],
        "count": rec["device_count"],
    }


def all_finite(obj) -> bool:
    if isinstance(obj, dict):
        return all(all_finite(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return all(all_finite(v) for v in obj)
    return not isinstance(obj, float) or math.isfinite(obj)


def check_backend(
    rec: dict, expect_path: str | None, require_tpu: bool
) -> dict:
    """A role's ``backend-<role>.json``: it ran on the TPU, its main program
    took the expected kernel path and — where that path is a kernel — holds
    Mosaic custom calls (interpreted kernels, in the CPU tests, lower to
    plain ops). Returns the fields every phase reports."""
    who = rec["role"]
    if require_tpu:
        check(rec["platform"] == "tpu", f"{who} ran on {rec['platform']}")
    if expect_path is not None:
        want_mosaic = rec["platform"] == "tpu" and expect_path.endswith("_pallas")
        check(
            expect_path in rec.get("paths", ())
            and (rec.get("mosaic_calls", 0) > 0 or not want_mosaic),
            f"{who} program took {rec.get('paths')} with "
            f"{rec.get('mosaic_calls')} Mosaic calls; expected {expect_path}",
        )
    return {
        **device_of(rec),
        **{k: rec.get(k) for k in (
            "compile_s", "cache_hits", "cache_misses", "paths", "mosaic_calls",
        )},
    }


def gauge_values(telemetry: dict, name: str) -> list[float]:
    """Every value of one gauge/counter across telemetry.json's sources."""
    out = []
    for src in telemetry.get("sources", []):
        for kind in ("gauges", "counters"):
            out += [v for n, _labels, v in src.get(kind, []) if n == name]
    return out


# ------------------------------------------------------------------- phases
def phase_native() -> dict:
    """Build native/codec.cpp from the tracked source (native/build/ is
    ignored by git), or say that the Python codec is in use."""
    from tpu_rl.runtime import native

    if native.available():
        return {"codec": "native", "lib": native.LIB._name}
    print("[native] g++ build unavailable: the Python codec is in use")
    return {"codec": "python"}


def phase_cli(
    algo: str,
    result_dir: str,
    updates: int,
    params: dict,
    cli: tuple[str, ...] = (),
    role: str = "learner",
    expect_path: str | None = "lstm_pallas",
    expect_mesh: dict | None = None,
    resume: bool = False,
    require_tpu: bool = True,
    timeout: float = 420.0,
) -> dict:
    """One ``python -m tpu_rl local`` run, checked from what it left in
    ``result_dir``. Runs in this (jax-free) process: the CLI spawns the
    chip-owning child itself."""
    os.makedirs(result_dir, exist_ok=True)
    p_path = os.path.join(result_dir, f"params-{algo}.json")
    with open(p_path, "w") as f:
        json.dump({**params, "algo": algo}, f)
    log = os.path.join(result_dir, f"cli-{time.time_ns()}.log")
    argv = [
        sys.executable, "-m", "tpu_rl", "local", "--params", p_path,
        "--result-dir", result_dir, "--max-updates", str(updates),
        "--seed", str(SEED), *cli,
    ]
    t0 = time.time()
    rc = run_child(argv, timeout, log)
    wall = time.time() - t0
    check(rc == 0, f"{' '.join(argv)} exited {rc}\n{tail(log)}")
    with open(log, errors="replace") as f:
        out = f.read()

    rec = read_json(os.path.join(result_dir, f"backend-{role}.json"))
    res = {"wall_s": round(wall, 1), **check_backend(rec, expect_path, require_tpu)}
    if expect_mesh is not None:
        check(rec["mesh"] == expect_mesh, f"mesh {rec['mesh']}")
    telem = read_json(os.path.join(result_dir, "telemetry.json"))
    want = updates * (2 if resume else 1)
    if role == "learner":
        done = max(gauge_values(telem, "learner-update-index"), default=0)
        check(done >= want, f"learner reached update {done} < {want}")
        check(
            max(gauge_values(telem, "worker-policy-version"), default=-1) > 0,
            "no worker acted on a broadcast policy (worker-policy-version)",
        )
        check(
            sum(gauge_values(telem, "learner-nonfinite-updates")) == 0,
            "non-finite learner updates",
        )
        check("[learner] update" in out, "no loss line from the learner")
        res["updates"] = int(done)
    else:
        done = max(gauge_values(telem, "colocated-updates"), default=0)
        check(done >= want, f"{role} reached update {done} < {want}")
        check(f"[{role}] done:" in out, f"no done line\n{tail(log)}")
        res["updates"] = int(done)
    with open(os.path.join(result_dir, "learn.jsonl")) as f:
        diag = [json.loads(line) for line in f if line.strip()]
    check(diag and all_finite(diag), "learn.jsonl empty or non-finite")
    marks = glob.glob(os.path.join(result_dir, "models", f"{algo}_*", "COMMITTED"))
    check(marks, "no committed checkpoint")
    res["checkpoints"] = len(marks)
    if resume:
        with open(os.path.join(result_dir, "learner_resume.jsonl")) as f:
            resumed = [json.loads(line) for line in f if line.strip()]
        check(
            resumed and resumed[-1]["idx"] >= updates,
            f"no resume from a committed checkpoint: {resumed}",
        )
        res["resumed_from"] = resumed[-1]["idx"]
    return res


def phase_learner(
    cfg_kw: dict,
    updates: int = 5,
    expect_path: str | None = None,
    require_tpu: bool = True,
    pallas_mode: str | None = None,
) -> dict:
    """The production ``LearnerService`` in THIS process, fed through the
    real shm store by a feeder thread (no children): lease -> assemble ->
    H2D -> train step, ``updates`` times (the first carries the compile)."""
    import numpy as np

    from tpu_rl.config import Config
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.data.shm_ring import OnPolicyStore, alloc_handles
    from tpu_rl.runtime.learner_service import LearnerService
    from tpu_rl.types import BATCH_FIELDS

    if pallas_mode is not None:
        from tpu_rl.models import cells

        cells.set_pallas_mode(pallas_mode)
    result_dir = tempfile.mkdtemp(prefix="chip_smoke_learner_")
    cfg = Config.from_dict(
        dict(cfg_kw, loss_log_interval=1, result_dir=result_dir)
    )
    layout = BatchLayout.from_config(cfg)
    handles = alloc_handles(layout, capacity=cfg.batch_size)
    rng = np.random.default_rng(SEED)
    n_act = cfg.action_space
    pool = []
    for _ in range(8):
        w = {}
        for f in BATCH_FIELDS:
            shape = (layout.seq_len, layout.width(f))
            if f == "act":
                w[f] = rng.integers(0, n_act, size=shape).astype(np.float32)
            elif f == "is_fir":
                w[f] = np.zeros(shape, np.float32)
                w[f][0] = 1.0
            elif f == "log_prob":
                w[f] = np.full(shape, -math.log(n_act), np.float32)
            else:
                w[f] = rng.standard_normal(shape).astype(np.float32) * 0.1
        pool.append(w)
    stop = threading.Event()

    def feed() -> None:
        store = OnPolicyStore(handles, layout)
        i = 0
        while not stop.is_set():
            if store.put(pool[i % len(pool)]):
                i += 1
            else:
                time.sleep(0.001)  # store full: the learner is consuming

    feeder = threading.Thread(target=feed, daemon=True)
    feeder.start()
    svc = LearnerService(
        cfg, handles, model_port=29970, stop_event=stop, max_updates=updates,
        publish_interval=10**9, seed=SEED,
    )
    t0 = time.time()
    try:
        svc.run()
    finally:
        stop.set()
        feeder.join(timeout=10)
    wall = time.time() - t0
    rec = read_json(os.path.join(result_dir, "backend-learner.json"))
    shutil.rmtree(result_dir, ignore_errors=True)
    res = {"wall_s": round(wall, 1), **check_backend(rec, expect_path, require_tpu)}
    check(
        svc.last_losses and all_finite(svc.last_losses),
        f"losses {svc.last_losses}",
    )
    check(svc.n_nonfinite_updates == 0, "non-finite updates")
    steps = svc.timer.elapsed.get("learner-step-time", ())
    check(len(steps) == updates, f"{len(steps)} dispatches, wanted {updates}")
    # on-policy and unchained: every batch placed from the store's memory
    check(
        svc.n_feed["leased"] >= updates and svc.n_feed["copied"] == 0,
        f"feed took {svc.n_feed}, wanted every batch leased",
    )
    return {**res, "updates": updates, "loss": svc.last_losses.get("loss")}


def _rel_err(got, want) -> float:
    """Max abs error over the reference's max abs value, over a pytree."""
    import jax
    import numpy as np

    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if not np.isfinite(g).all():
            return float("inf")
        worst = max(worst, float(np.abs(g - w).max() / (np.abs(w).max() + 1e-9)))
    return worst


# Tolerances (max abs error / max abs reference). The f32 kernels run their
# matmuls at the TPU's default precision — one bf16 pass with f32
# accumulation, exactly as the XLA scan / act paths they replace do — so each
# case is held to two references:
# - ``err``: the jnp reference at precision="highest". Each operand carries
#   a 2^-8 rounding into contractions up to 1024 deep and 16 recurrent steps
#   (measured on a TPU v5e: 2.4e-3..9.6e-3). A wrong gate order, mask or
#   carry is an O(1) error.
# - ``err_vs_default``: the same reference at the default precision, i.e.
#   what XLA computes on this chip with the same operand rounding. Measured:
#   fused act <= 2e-7 (one step, same arithmetic), LSTM 6e-4..1.0e-3 (the
#   recurrence amplifies accumulation-order and transcendental differences).
#   Computing anything in a lower precision than the XLA path would fail it.
# bf16 flash attention is compared with an f32 reference on the same bf16
# inputs; the kernel rounds the probabilities and the output to bf16
# (measured 3.4e-3..5.3e-3).
TOL_F32 = 2e-2
TOL_LSTM_SAME = 5e-3
TOL_ACT_SAME = 1e-5
TOL_BF16 = 3e-2
# a whole mixer in bf16 against the float32 reference: projections, the kernel
# or the scan, and projections again, every gradient through all three
TOL_MIXER_BF16 = 6e-2
# The widths of Qwen3-Next-80B-A3B-Instruct's two mixers as published
# (benchmarks/configs/qwen3-next-80b-a3b.json holds the whole configuration).
QWEN3_NEXT_MIXERS = dict(
    hidden_size=2048, rms_norm_eps=1e-6, linear_num_key_heads=16, linear_num_value_heads=32,
    linear_key_head_dim=128, linear_value_head_dim=128, linear_conv_kernel_dim=4,
    num_attention_heads=16, num_key_value_heads=2, head_dim=256, rope_theta=10000000,
    partial_rotary_factor=0.25,
)
# The widths of GLM-4.7-Flash's latent attention as published
# (benchmarks/configs/glm-4.7-flash.json holds the whole configuration).
GLM4_MOE_LITE_MIXER = dict(
    hidden_size=2048, rms_norm_eps=1e-5, num_attention_heads=20, q_lora_rank=768,
    kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
    rope_theta=1000000,
)
# The widths of Ling-3.0-flash-VL's two mixers and its router as published
# (benchmarks/configs/ling-3.0-flash-vl.json holds the whole configuration).
LING_FLASH_MIXERS = dict(
    hidden_size=2560, rms_norm_eps=1e-6, num_attention_heads=32, head_dim=128, q_lora_rank=None,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=6000000, short_conv_kernel_size=4, kda_lower_bound=-5, num_experts=512,
    num_experts_per_tok=8, n_group=8, topk_group=4, routed_scaling_factor=2.5,
)
# The widths of LFM2-24B-A2B's gated short convolution as published
# (benchmarks/configs/lfm2-24b-a2b.json holds the whole configuration).
LFM2_MOE_MIXER = dict(hidden_size=2048, conv_L_cache=3)
# The widths of EvaByte's EVA attention as published
# (benchmarks/configs/evabyte.json holds the whole configuration).
EVABYTE_MIXER = dict(
    hidden_size=4096, num_attention_heads=32, window_size=2048, chunk_size=16, rope_theta=100000,
    init_std=0.01275,
)


def kernel_checks(
    lstm_shapes=((128, 5, 64, "auto"), (256, 16, 256, "auto"), (1024, 16, 1024, "force")),
    act_shapes=((8, 4, 64, 2), (256, 4, 64, 2), (8, 64, 1024, 8), (256, 64, 1024, 8)),
    # (B, T, heads, key/value heads, head dim, softmax scale, with gradients,
    # sliding window): tf-longctx's layer, a forward-only row,
    # granite-4.0-h-micro's layer, smallthinker-21b-a3b's window layer (one of
    # its two rows: the reference's scores are 1.9 GB a block of 1,024 queries)
    flash_shapes=(
        (16, 2048, 8, 8, 64, None, True, None),
        (1, 512, 8, 8, 64, None, False, None),
        (2, 4096, 32, 8, 64, 1.0 / 64, True, None),
        (1, 16384, 28, 4, 128, 128**-0.5, True, 4096),
    ),
    # (B, T, heads, head dim, groups, state, chunk): granite-4.0-h-micro's
    # scan, then nemotron-3-nano-30b-a3b's (8 B/C groups, chunks of 128; two of
    # its four rows: the kernels' grid runs over rows, and the row's operands
    # are compiled into the case as constants, 0.6 GB a row)
    ssd_shapes=((2, 4096, 64, 64, 1, 128, 256), (2, 4096, 64, 64, 8, 128, 128)),
    # (rows, experts held, in, out): one projection of nemotron-3-nano-30b-a3b's
    # routed experts, ragged groups of ~768 rows an expert
    gmm_shapes=((6144, 8, 2688, 1856),),
    # (tokens, choices, experts held, experts, in, out, share of the tokens
    # that choose among the held experts only, gated): nemotron-3-nano-30b-a3b's
    # expert block at a quarter of its tokens, once under a fair routing (one
    # trip of the walk) and once with most assignments held (several); then
    # smallthinker-21b-a3b's gated block at an eighth of its tokens, likewise,
    # and the walk its cell times: a layer's 49,152 held rows (a quarter of the
    # tokens, every assignment held) in the cell's own trips of ``WALK_ROWS``,
    # forward and backward.
    moe_shapes=(
        (4096, 6, 8, 128, 2688, 1856, 0.0, False), (4096, 6, 8, 128, 2688, 1856, 0.75, False),
        (4096, 6, 16, 64, 2560, 768, 0.0, True), (4096, 6, 16, 64, 2560, 768, 0.75, True),
        (8192, 6, 16, 64, 2560, 768, 1.0, True),
    ),
    # (rows of a trip, tokens, width, experts held): the walk's row-add at the
    # two expert cells' shapes (smallthinker-21b-a3b's result at a 4,096-row
    # trip, nemotron-3-nano-30b-a3b's one trip), ragged groups, a dead tail
    row_add_shapes=((4096, 32768, 2560, 16), (12288, 16384, 2688, 8)),
    # (T, heads, key/value heads, head dim, softmax scale, sliding window, mean
    # episode length, tile edge or None for the rule's): one row of
    # smallthinker-21b-a3b's window at its window layer and its global one, seams
    # drawn as its traffic draws them, the block masks read from them against
    # the static call
    # then one row of qwen3-next-80b-a3b's window at its full-attention layer: the
    # same skip at heads of 256
    seam_shapes=(
        (16384, 28, 4, 128, 128**-0.5, 4096, 8192, None),
        (16384, 28, 4, 128, 128**-0.5, None, 8192, None),
        (8192, 16, 2, 256, 256**-0.5, None, 2048, None),
    ),
    # (T, heads, key/value heads, head dim, softmax scale, sliding window, mean
    # episode length, tile edge or None for the rule's): one row of
    # smallthinker-21b-a3b's window at its global layer and its window layer and
    # one of glm-4.7-flash's, seams drawn as their traffic draws them: the
    # repo's own backward over the band's tiles (ops/pallas_attn_bwd.py) against
    # the plain reference, and timed beside the library's on the same masks
    attn_bwd_shapes=(
        (16384, 28, 4, 128, 128**-0.5, None, 8192, None),
        (16384, 28, 4, 128, 128**-0.5, 4096, 8192, None),
        (16384, 20, 20, 256, 256**-0.5, None, 8192, None),
    ),
    # (mixer, B, T): qwen3-next-80b-a3b's Gated-DeltaNet mixer (a window of one
    # span of sixteen chunks and a ragged second one) and its gated attention
    # mixer (heads of 256, q/k norm, partial RoPE, the output gate) at the widths
    # of ``qwen3_next_widths``, forward and every gradient against
    # benchmarks/reference/qwen3_next.py (the step recurrence; dense masked
    # attention, whose scores at T 8,192 would not fit beside their gradients)
    qwen3_next_shapes=(("linear", 1, 1536), ("attention", 1, 4096)),
    qwen3_next_widths=QWEN3_NEXT_MIXERS,
    # (B, T, key heads, value heads, key size, value size, chunk): the delta
    # rule's scan alone at qwen3-next-80b-a3b's widths and the cell's batch,
    # ~4 seams a window: the Pallas pair against the jax.numpy body, forward
    # and every gradient, both forms timed (forward + backward, host clock)
    gdn_shapes=((2, 8192, 16, 32, 128, 128, 64),),
    # (row, B, T): glm-4.7-flash's latent attention at ``glm_widths``. "mixer":
    # the training form (low-rank projections, the shared key's broadcast, the
    # splash kernels at 20 : 20 heads of 256 on the cell's 16 x 16 grid), forward
    # and every gradient against benchmarks/reference/glm4_moe_lite.py (dense
    # masked attention, 1,024 queries at a time). "step": the absorbed acting
    # form stepped over a latent ring of T slots against the training form
    glm_shapes=(("mixer", 1, 16384), ("step", 2, 1024)),
    glm_widths=GLM4_MOE_LITE_MIXER,
    # (row, B, T): lfm2-24b-a2b's gated short convolution at ``lfm2_widths``.
    # "mixer": the training form at the cell's batch (in_proj, the gates'
    # product, the seam-stopped taps, out_proj), forward and every gradient
    # against benchmarks/reference/lfm2_moe.py (three shifted, seam-masked
    # products). "step": the acting form stepped over its two-row tail against
    # the training form
    lfm2_shapes=(("mixer", 4, 8192), ("step", 2, 1024)),
    lfm2_widths=LFM2_MOE_MIXER,
    # as ``attn_bwd_shapes``: one row of lfm2-24b-a2b's window at its attention
    # layer (heads of 64: the three gradients leave the kernel head-major);
    # run last, so that the rows above keep the inputs they were drawn
    lfm2_attn_bwd_shapes=((8192, 32, 8, 64, 64**-0.5, None, 2048, None),),
    # (row, B, T): evabyte's EVA attention at ``evabyte_widths``. "mixer": the
    # training form at the cell's window (projections, rotation, the chunk
    # pooling, the kernels over the blocks' exact keys with the logsumexp out,
    # the summaries' read, the merge, o_proj), forward and every gradient
    # against benchmarks/reference/evabyte.py (a summary row for every step,
    # every query against [K ; all rows] under the mask from the definitions,
    # 256 queries at a time). "pool": the gather and the two poolings alone
    # against the reference's shifted products at the chunks' last steps, both
    # timed. "step": the acting form stepped over its exact ring and summary
    # store, across a block boundary, against the training form. "pool-seams":
    # "pool" on a window with three seams off every grid line, so that several
    # candidates are absent and a group of ``chunk_size`` steps meets chunks of
    # two episodes. Run last, so that the rows above keep the inputs they were
    # drawn ("pool-seams" after "step", for the same reason)
    evabyte_shapes=(
        ("mixer", 1, 16384), ("pool", 1, 16384), ("step", 1, 4096), ("pool-seams", 1, 16384)),
    evabyte_widths=EVABYTE_MIXER,
    # (row, B, T): ling-3.0-flash-vl's new paths at ``ling_flash_widths``. "kda-seams" /
    # "kda-bound" / "kda-one-episode": the per-channel delta rule's chunked scan
    # (ops/kda.py) alone at 32 heads of 128, forward and every gradient against the step
    # recurrence (``kda_step`` under a scan whose 64-step blocks are rematerialised; the
    # first row timed: the Pallas pair the gate takes on the chip as ``ms``, the recurrence
    # as ``ms_ref``, the jax.numpy body as ``ms_jnp``) — with ~4 seams a window and two in one chunk, with every gate at the bound of
    # -5 for the whole window (the sub-blocks' operands at e^75), and with the whole row one
    # episode. "mla": latent attention without a query latent at 192-wide queries and
    # 128-wide values (the kernels on heads padded to 256), forward and every gradient
    # against benchmarks/reference/ling_flash.py. "route": the group-limited choice
    # (ops/moe.route: 8 groups of 64, 4 kept, top-8) on T tokens against the reference's
    # sort, the chosen sets and the weights. Run last, so that the rows above keep the
    # inputs they were drawn
    ling_flash_shapes=(
        ("kda-seams", 1, 8192), ("kda-bound", 1, 8192), ("kda-one-episode", 1, 8192),
        ("mla", 1, 8192), ("route", 1, 8192)),
    ling_flash_widths=LING_FLASH_MIXERS,
    ling_flash_chunk: int = 64,
    interpret: bool = False,
) -> list[dict]:
    """Each kernel against its plain-jnp reference; one result row per case,
    failures recorded (not raised) so one chip call reports every kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_rl.models import cells
    from tpu_rl.ops import pallas_lstm as pk
    from tpu_rl.utils.platform import program_paths

    rng = np.random.default_rng(SEED)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))  # noqa: E731
    rows = []

    def best_ms(f, args, repeats=5):
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            times.append(time.perf_counter() - t0)
        return round(1e3 * min(times), 3)

    def case(name, fn, ref, args, tol, tol_same, mosaic=True, timed=False, ref_is_kernel=False,
             timed_too=None):
        """``timed_too``: {name: another form of ``fn``}, each timed as ``ms_<name>``."""
        row = {"kernel": name, "tol": tol, "tol_vs_default": tol_same}
        t0 = time.time()
        try:
            jfn = jax.jit(fn)
            n_mosaic = program_paths(jfn.lower(*args))["mosaic_calls"]
            got = jax.block_until_ready(jfn(*args))
            # a Mosaic kernel as the reference keeps its own precision: the
            # compiler refuses bf16 operands under "highest"
            with jax.default_matmul_precision(None if ref_is_kernel else "highest"):
                want = jax.block_until_ready(jax.jit(ref)(*args))
            row.update(
                err=_rel_err(got, want),
                err_vs_default=_rel_err(got, jax.jit(ref)(*args)),
                mosaic_calls=n_mosaic,
            )
            if timed:  # host clock over a device round trip: the two beside each other
                row.update(ms=best_ms(jfn, args), ms_ref=best_ms(jax.jit(ref), args))
                for also, form in (timed_too or {}).items():
                    row[f"ms_{also}"] = best_ms(jax.jit(form), args)
            row["ok"] = (
                row["err"] <= tol
                and row["err_vs_default"] <= tol_same
                and (n_mosaic > 0 or interpret or not mosaic)
            )
        except Exception as e:  # noqa: BLE001 — report every kernel
            row.update(ok=False, error=f"{type(e).__name__}: {str(e)[:1500]}")
        row["wall_s"] = round(time.time() - t0, 1)
        rows.append(row)
        print(f"[kernels] {json.dumps(row)}", flush=True)

    # ---- LSTM forward + fused backward vs the scan
    for B, S, H, mode in lstm_shapes:
        xp, wh = f32(B, S, 4 * H) * 0.5, f32(H, 4 * H) / np.sqrt(H)
        h0, c0 = f32(B, H) * 0.5, f32(B, H) * 0.5
        keep = jnp.asarray((rng.random((B, S)) > 0.1).astype(np.float32))
        w_h, w_c = f32(B, S, H), f32(B, S, H)

        def loss(unroll, xp, wh, h0, c0):
            hs, cs = unroll(xp, wh, h0, c0)
            return (hs * w_h).sum() + (cs * w_c).sum()

        def kern(xp, wh, h0, c0):
            return pk.lstm_unroll(xp, wh, h0, c0, keep, interpret)

        def scan(xp, wh, h0, c0):
            return pk._scan_forward(xp, wh, h0, c0, keep, want_cs=True)

        every = (0, 1, 2, 3)
        cells.set_pallas_mode(mode)  # "force": multi-tile fused backward too
        try:
            case(
                f"lstm fwd+bwd B{B}/S{S}/H{H} ({mode}, tiles "
                f"{pk.batch_tile(B, S, H)}/{pk.bwd_batch_tile(B, S, H)})",
                jax.value_and_grad(lambda *a: loss(kern, *a), argnums=every),
                jax.value_and_grad(lambda *a: loss(scan, *a), argnums=every),
                (xp, wh, h0, c0), TOL_F32, TOL_LSTM_SAME,
            )
        finally:
            cells.set_pallas_mode("auto")

    # ---- fused act step vs the flax actor
    from tpu_rl.config import Config
    from tpu_rl.models.families import build_family
    from tpu_rl.ops.pallas_act import fused_act_step

    for rows_n, D, H, A in act_shapes:
        cfg = Config.from_dict(dict(
            algo="PPO", hidden_size=H, obs_shape=(D,), action_space=A,
        ))
        family = build_family(cfg)
        params = family.init_params(jax.random.key(SEED), seq_len=cfg.seq_len)

        def ref(actor_params, obs, h, c, _actor=family.actor):
            logits, _v, (h2, c2) = _actor.apply(
                actor_params, obs, (h, c), method="act"
            )
            return logits, h2, c2

        case(
            f"fused act rows{rows_n}/obs{D}/H{H}/A{A}",
            lambda p, o, h, c: fused_act_step(p, o, h, c, interpret), ref,
            (params["actor"], f32(rows_n, D), f32(rows_n, H) * 0.5, f32(rows_n, H) * 0.5),
            TOL_F32, TOL_ACT_SAME,
        )

    # ---- the library's splash kernel with the dispatch's tiles vs full attention
    from tpu_rl.parallel.sequence import (
        _masked_block_scores,
        _splash_block_sizes,
        flash_attention_tpu,
        full_attention,
    )

    for B, T, NH, NKV, D, sm_scale, grad, window in flash_shapes:
        q = f32(B, T, NH, D).astype(jnp.bfloat16)
        k, v = (f32(B, T, NKV, D).astype(jnp.bfloat16) for _ in range(2))
        firsts = np.zeros((B, T), np.int32)
        firsts[:, 0] = 1
        firsts[:, T // 3] = 1  # an episode seam inside the window
        seg = jnp.asarray(np.cumsum(firsts, axis=1))
        pos = jnp.asarray(np.tile(np.arange(T), (B, 1)))
        w_o = f32(B, T, NH, D)
        n_ref = min(B, 2)  # the f32 reference holds (n, H, T, T) scores

        def flash(q, k, v, n=B):
            return flash_attention_tpu(
                q[:n], k[:n], v[:n], pos[:n], seg[:n], causal=True, sm_scale=sm_scale,
                window=window,
            )

        def full(q, k, v, n=B):  # f32 reference on the same bf16 inputs
            q, k, v = (x[:n].astype(jnp.float32) for x in (q, k, v))
            k, v = (jnp.repeat(x, NH // NKV, axis=2) for x in (k, v))
            if T <= 4096:
                return full_attention(
                    q, k, v, pos[:n], seg[:n], causal=True, sm_scale=sm_scale, window=window)

            # (n, H, T, T) scores do not fit: full_attention's own masked
            # scores, 1,024 queries at a time against every key
            @jax.checkpoint
            def queries(at):
                cut = lambda x: jax.lax.dynamic_slice_in_dim(x[:n], at, 1024, axis=1)  # noqa: E731
                scores = _masked_block_scores(
                    cut(q), k, cut(pos), pos[:n], cut(seg), seg[:n], sm_scale, True, window)
                return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

            out = jax.lax.map(queries, jnp.arange(0, T, 1024))  # (T / 1024, n, 1024, H, D)
            return out.transpose(1, 0, 2, 3, 4).reshape(n, T, NH, D)

        def grads(impl, n):
            def loss(q, k, v):
                return (impl(q, k, v, n).astype(jnp.float32) * w_o[:n]).sum()

            # rows are independent: compare the first n_ref rows' gradients
            return lambda q, k, v: tuple(
                g[:n_ref] for g in jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            )

        got_fn, ref_fn = (grads(flash, B), grads(full, n_ref)) if grad else (flash, full)
        bs = _splash_block_sizes(T)
        case(
            f"flash {'fwd+bwd' if grad else 'fwd'} B{B}/T{T}/H{NH}:{NKV}/D{D}"
            f"{f'/window{window}' if window else ''} bf16 "
            f"(tiles {bs and (bs.block_q, bs.block_kv, bs.block_q_dkv, bs.block_kv_dkv)})",
            got_fn, ref_fn, (q, k, v), TOL_BF16, TOL_BF16,
            # off-TPU the dispatch substitutes full attention by design
            mosaic=jax.default_backend() == "tpu",
        )

    # ---- the splash kernels stepping over seam-empty tiles vs the same call
    # on the static masks: output and the three gradients equal to the bit, both timed
    import dataclasses

    from jax.experimental.pallas.ops.tpu.splash_attention import SegmentIds

    from tpu_rl.parallel import sequence

    def drawn_row(T, episode, edge, window):
        """One row's segment ids with seams drawn as benchmarks/traffic.firsts
        draws them (and one whatever the draw), the tiles, and of the static
        band's tiles how many run."""
        firsts = rng.random((1, T)) < 1.0 / episode
        firsts[0, [0, T // 3]] = True
        seg = jnp.asarray(np.cumsum(firsts, axis=1), jnp.int32)
        bs = _splash_block_sizes(T)
        if edge is not None:
            bs = dataclasses.replace(bs, **{
                f.name: edge for f in dataclasses.fields(bs)
                if f.name.startswith("block_") and getattr(bs, f.name) is not None})
        band = sequence.band_tiles(T, bs.block_q, window)
        n_run = int((band & ~np.asarray(sequence.seam_empty_tiles(seg, bs.block_q))[0]).sum())
        return seg, bs, n_run, int(band.sum())

    for T, NH, NKV, D, sm_scale, window, episode, edge in seam_shapes:
        q = (f32(NH, T, D) * sm_scale).astype(jnp.bfloat16)  # the kernel's layout, one row
        k, v = (f32(NKV, T, D).astype(jnp.bfloat16) for _ in range(2))
        seg, bs, n_run, n_band = drawn_row(T, episode, edge, window)
        w_o = f32(NH, T, D)

        def one_row(skip):
            def fn(q, k, v, seg):
                splash = sequence._splash_kernel(
                    T, NH, causal=True, window=window, block_sizes=bs, interpret=interpret)
                if skip:  # the masks traced from seg, as in the update program
                    splash = sequence._skip_seams(
                        splash, sequence.seam_empty_tiles(seg, bs.block_q)[0])
                ids = SegmentIds(q=seg[0], kv=seg[0])

                def loss(q, k, v):
                    out = splash(q, k, v, segment_ids=ids)
                    return (out.astype(jnp.float32) * w_o).sum(), out

                grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
                return (out, *grads)

            return fn

        case(
            f"flash seam-skipping fwd+bwd T{T}/H{NH}:{NKV}/D{D}"
            f"{f'/window{window}' if window else ''} bf16 vs the static masks "
            f"(tiles of {bs.block_q}: {n_run} of the band's {n_band} run)",
            one_row(True), one_row(False), (q, k, v, seg), 0.0, 0.0, timed=True,
            ref_is_kernel=True,
        )

    # ---- the repo's own backward over the band's tiles vs the plain reference
    # (output and the three gradients), its dq no farther from it than the
    # library's fused backward on the same masks, the two timed side by side
    def attn_bwd_rows(shapes, family=""):
        for T, NH, NKV, D, sm_scale, window, episode, edge in shapes:
            q = (f32(T, NH, D) * sm_scale).astype(jnp.bfloat16)  # one row
            k, v = (f32(T, NKV, D).astype(jnp.bfloat16) for _ in range(2))
            seg, bs, n_run, n_band = drawn_row(T, episode, edge, window)
            pos = jnp.arange(T)[None]
            w_o = f32(T, NH, D)

            def with_grads(attend):
                def fn(q, k, v, seg):
                    def loss(q, k, v):
                        out = attend(q, k, v, seg)
                        return (out.astype(jnp.float32) * w_o).sum(), out

                    grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
                    return (out, *grads)

                return jax.jit(fn)

            def one_row(row):
                def attend(q, k, v, seg):
                    splash = sequence._splash_kernel(
                        T, NH, causal=True, window=window, block_sizes=bs, interpret=interpret)
                    empty = sequence.seam_empty_tiles(seg, bs.block_q)[0]
                    return row((True, window, 0), splash, q, k, v, seg[0], empty)

                return with_grads(attend)

            def plain(q, k, v, seg):  # float32 on the same bf16 inputs, 1,024 queries at a time
                q, k, v = (x.astype(jnp.float32)[None] for x in (q, k, v))
                k, v = (jnp.repeat(x, NH // NKV, axis=2) for x in (k, v))

                @jax.checkpoint
                def queries(at):
                    cut = lambda x: jax.lax.dynamic_slice_in_dim(x, at, min(T, 1024), axis=1)  # noqa: E731
                    scores = _masked_block_scores(
                        cut(q), k, cut(pos), pos, cut(seg), seg, 1.0, True, window)
                    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

                out = jax.lax.map(queries, jnp.arange(0, T, min(T, 1024)))  # (chunks, 1, 1024, H, D)
                return out.reshape(T, NH, D)

            row = {"kernel": f"{family}attn bwd over the band T{T}/H{NH}:{NKV}/D{D}"
                             f"{f'/window{window}' if window else ''} bf16 vs the plain reference "
                             f"(tiles of {bs.block_q}: {n_run} of the band's {n_band} run)",
                   "tol": TOL_BF16}
            t0 = time.time()
            try:
                own, lib = one_row(sequence._seam_row), one_row(sequence._seam_row_forward)
                paths = program_paths(own.lower(q, k, v, seg))
                got, theirs = (jax.block_until_ready(f(q, k, v, seg)) for f in (own, lib))
                with jax.default_matmul_precision("highest"):
                    want = jax.block_until_ready(with_grads(plain)(q, k, v, seg))
                mean = lambda a, w: float(np.abs(np.asarray(a, np.float32) - np.asarray(w)).mean())  # noqa: E731
                row.update(
                    err=_rel_err(got, want), err_lib=_rel_err(theirs, want),
                    err_dq=_rel_err(got[1], want[1]), err_dq_lib=_rel_err(theirs[1], want[1]),
                    mean_dq=mean(got[1], want[1]), mean_dq_lib=mean(theirs[1], want[1]),
                    # forward and dk, dv walk the tiles in the library's order (recorded, not
                    # required: an earlier form of the kernel held it in the interpreter alone)
                    same_out_dk_dv=all(
                        bool((g == t).all()) for g, t in zip(got[:1] + got[2:], theirs[:1] + theirs[2:])),
                    mosaic_calls=paths["mosaic_calls"],
                    ms=best_ms(own, (q, k, v, seg)), ms_ref=best_ms(lib, (q, k, v, seg)),
                )
                row["ok"] = (
                    row["err"] <= TOL_BF16 and row["mean_dq"] <= row["mean_dq_lib"]
                    and "attn_bwd_pallas" in paths["paths"]
                )
            except Exception as e:  # noqa: BLE001 — report every kernel
                row.update(ok=False, error=f"{type(e).__name__}: {str(e)[:1500]}")
            row["wall_s"] = round(time.time() - t0, 1)
            rows.append(row)
            print(f"[kernels] {json.dumps(row)}", flush=True)

    attn_bwd_rows(attn_bwd_shapes)

    # ---- the Pallas scan pair vs the jnp body of ssd_chunked, every gradient
    from tpu_rl.models.mamba2 import ssd_chunked
    from tpu_rl.ops.pallas_ssd import head_block

    for B, T, H, P, G, N, Q in ssd_shapes:
        x = f32(B, T, H, P)
        dt = jax.nn.softplus(f32(B, T, H) - 3.0)
        A = -jnp.exp(f32(H) * 0.5)
        Bm, Cm, D = f32(B, T, G, N) * N**-0.5, f32(B, T, G, N), f32(H)
        state0 = f32(B, H, P, N)
        firsts = rng.random((B, T)) < 8.0 / T  # ~8 episode seams a window
        seg = jnp.asarray(np.cumsum(firsts, axis=1).astype(np.int32))
        w_y, w_last = f32(B, T, H, P), f32(B, H, P, N)
        hb = H if interpret else head_block(H, P, G, N, Q)

        def loss(kernel, x, dt, A, Bm, Cm, D, state0):
            y, last = ssd_chunked(
                x, dt, A, Bm, Cm, D, seg, state0, Q, jnp.bfloat16, kernel=kernel)
            return (y * w_y).sum() + (last * w_last).sum(), (y, last)

        def grads(kernel):
            return jax.value_and_grad(
                lambda *a: loss(kernel, *a), argnums=tuple(range(7)), has_aux=True)

        case(
            f"ssd fwd+bwd B{B}/T{T}/H{H}x{P}/G{G}/N{N}/Q{Q} bf16 (head block {hb})",
            grads((hb, interpret)), grads((None, False)),
            (x, dt, A, Bm, Cm, D, state0), TOL_BF16, TOL_BF16,
        )

    # ---- the grouped matmul's Pallas kernels vs ragged_dot, both gradients
    from tpu_rl.ops.moe import grouped_matmul

    for M, G, K, N in gmm_shapes:
        lhs = (f32(M, K)).astype(jnp.bfloat16)
        rhs = (f32(G, K, N) * K**-0.5).astype(jnp.bfloat16)
        cuts = np.sort(rng.integers(0, M + 1, G - 1))  # ragged groups that fill the rows
        sizes = jnp.asarray(np.diff(np.concatenate([[0], cuts, [M]])), jnp.int32)
        w_out = f32(M, N).astype(jnp.bfloat16)

        def gmm_grads(kernel, dtype):
            # ragged_dot is a Mosaic kernel on a TPU too, and that one takes no
            # bf16 operands at "highest" precision: the reference gets float32
            return jax.value_and_grad(
                lambda a, b: (grouped_matmul(a.astype(dtype), b.astype(dtype), sizes, kernel)
                              * w_out).astype(jnp.float32).sum(), argnums=(0, 1))

        case(
            f"gmm fwd+bwd M{M}/G{G}/K{K}/N{N} bf16",
            gmm_grads((True, interpret), jnp.bfloat16), gmm_grads((False, False), jnp.float32),
            (lhs, rhs), TOL_BF16, TOL_BF16,
        )
    # ---- the walk's row-add vs XLA's scatter-add, eight trips into one result
    from tpu_rl.ops import moe, pallas_moe

    for C, n, d, G in row_add_shapes:
        held = C - C // 7  # the rest of the last chunk is dead and holds NaN
        cuts = np.sort(rng.integers(0, held + 1, G - 1))
        part = np.diff(np.concatenate([[0], cuts, [held]]))
        tok = np.concatenate([np.sort(rng.permutation(n)[:p]) for p in part] + [np.zeros(C - held)])
        tok, part = jnp.asarray(tok, jnp.int32), jnp.asarray(part, jnp.int32)
        live = jnp.arange(C) < held
        add = jnp.where(live[:, None], f32(C, d), jnp.nan)

        def added(adder, add):
            def trip(c, y):
                rows = add * (1.0 + c)
                if not adder[0]:  # the scatter-add reads every row: the walk selects for it
                    rows = jnp.where(live[:, None], rows, 0.0)
                return moe.add_rows(y, rows, tok, part, live, adder)
            return jax.lax.fori_loop(0, 8, trip, moe._result((n, d), adder)).reshape(n, d)

        case(
            f"row_add {C} rows ({held} live) into {n}x{d} f32, {G} groups, 8 trips "
            f"(tile {pallas_moe.tile_rows(C, d)})",
            functools.partial(added, (True, interpret)), functools.partial(added, (False, False)),
            (add,), TOL_ACT_SAME, TOL_ACT_SAME, timed=True,
        )
    # ---- the walk over the held assignments vs every held expert under a mask

    for n, k, held, total, d, f, held_only, gated in moe_shapes:
        fair = np.stack([rng.permutation(total)[:k] for _ in range(n)])
        here = np.stack([rng.permutation(held)[:k] for _ in range(n)])
        choice = jnp.asarray(np.where(rng.random((n, 1)) < held_only, here, fair), jnp.int32)
        chunk = moe.chunk_rows(n, k, held, total)
        trips = int(moe.route_stats(choice, 0, held, chunk)["chunks"])
        mix = f32(n, d)
        # The gate's relu has a kink: where rounding an operand to bf16 moves a
        # pre-activation across zero, that element's gradient takes the other
        # branch, an O(1) change no tolerance holds (a fifth of the largest
        # gradient at 300 rows). The gated rows hand both sides operands that
        # bf16 holds exactly, so what is compared is the walk's arithmetic.
        as_run = (lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)) if gated else (lambda x: x)

        def block(dense, u, weight, w_in, w_out, *w_gate):
            """The block's output and, for a fixed cotangent, every gradient
            (four; five with the gated form's third leaf)."""
            def weighted(u, weight, w_in, w_out, *w_gate):
                gate = dict(w_gate=w_gate[0], form="reglu") if w_gate else {}
                if dense:
                    y = moe.routed_experts_dense(u, choice, weight, w_in, w_out, 0, **gate)
                else:
                    y = moe.routed_experts(
                        u, choice, weight, w_in, w_out, 0, jnp.bfloat16, (True, interpret),
                        chunk, **gate)
                return (y * mix).sum(), y
            (_, y), grads = jax.value_and_grad(
                weighted, argnums=tuple(range(4 + len(w_gate))), has_aux=True,
            )(u, weight, w_in, w_out, *w_gate)
            return y, grads

        case(
            f"moe {'gated ' * gated}walk fwd+bwd N{n}/k{k}/held{held}of{total}/d{d}/f{f} bf16 "
            f"(chunk {chunk}, {trips} trip{'s' * (trips != 1)})",
            functools.partial(block, False), functools.partial(block, True),
            (as_run(f32(n, d)), jnp.asarray(rng.random((n, k)) + 0.1, jnp.float32),
             as_run(f32(held, d, f) * d**-0.5), as_run(f32(held, f, d) * f**-0.5),
             *([as_run(f32(held, d, f) * d**-0.5)] if gated else [])), TOL_BF16, TOL_BF16,
        )

    # ---- qwen3_next's two mixers in bf16 vs the plain float32 reference
    from benchmarks.reference import qwen3_next as plain
    from tpu_rl.models.qwen3_next import Qwen3NextActorCritic, build_mixer

    arch = dict(qwen3_next_widths)
    for kind, B, T in qwen3_next_shapes:
        mixer = build_mixer(arch, kind, jnp.bfloat16).clone(name=None)
        u = f32(B, T, arch["hidden_size"])
        firsts = rng.random((B, T)) < 4.0 / T  # ~4 episode seams a window
        firsts[:, [T // 3, T // 3 + 1]] = True  # and two in one chunk whatever the draw
        seg = jnp.asarray(np.cumsum(firsts, axis=1).astype(np.int32))
        first = jnp.asarray(firsts)
        carry = ()
        if kind == "linear":  # the state and the tail the family says such a layer carries
            _, shapes = Qwen3NextActorCritic.acting_state(
                {**arch, "num_hidden_layers": 1, "full_attention_interval": 2}, T)[0]
            carry = tuple(jnp.zeros((B, *shape)) for shape in shapes)

        params = jax.jit(lambda key: mixer.init(key, u, seg, *carry)["params"])(
            jax.random.key(SEED))
        w_y = f32(B, T, arch["hidden_size"])

        def system(p, u):
            y = mixer.apply({"params": p}, u, seg, *carry)
            y = y[0] if kind == "linear" else y
            return (y * w_y).sum(), y

        def reference(p, u):
            y = (plain.linear_attention if kind == "linear" else plain.attention)(
                u, first, p, arch)
            return (y * w_y).sum(), y

        case(
            f"qwen3_next {kind} mixer fwd+bwd B{B}/T{T} bf16 vs the plain reference "
            f"({int(firsts.sum())} seams)",
            jax.value_and_grad(system, argnums=(0, 1), has_aux=True),
            jax.value_and_grad(reference, argnums=(0, 1), has_aux=True),
            (params, u), TOL_MIXER_BF16, TOL_MIXER_BF16,
            mosaic=jax.default_backend() == "tpu",  # the splash kernels; the delta rule's pair
        )

    # ---- the Pallas delta-rule pair vs the jax.numpy body, every gradient
    from tpu_rl.ops.gated_delta import gated_delta_chunked
    from tpu_rl.ops.pallas_gdn import head_block as gdn_head_block

    for B, T, HK, HV, DK, DV, Q in gdn_shapes:
        q, k = f32(B, T, HK, DK).astype(jnp.bfloat16), f32(B, T, HK, DK).astype(jnp.bfloat16)
        v = f32(B, T, HV, DV).astype(jnp.bfloat16)
        g, beta = -jax.nn.softplus(f32(B, T, HV) - 3.0), jax.nn.sigmoid(f32(B, T, HV))
        state0 = f32(B, HV, DK, DV) * DK**-0.5
        firsts = rng.random((B, T)) < 4.0 / T  # ~4 episode seams a window
        firsts[:, [T // 3, T // 3 + 1]] = True  # and two in one chunk whatever the draw
        seg = jnp.asarray(np.cumsum(firsts, axis=1).astype(np.int32))
        w_o, w_last = f32(B, T, HV, DV), f32(B, HV, DK, DV)
        hb = HV if interpret else gdn_head_block(HV, HK, DK, DV, Q)

        def delta_loss(kernel, q, k, v, g, beta, state0):
            o, last = gated_delta_chunked(
                q, k, v, g, beta, seg, state0, Q, jnp.bfloat16, kernel=kernel)
            return (o * w_o).sum() + (last * w_last).sum(), (o, last)

        def delta_grads(kernel):
            return jax.value_and_grad(
                lambda *a: delta_loss(kernel, *a), argnums=tuple(range(6)), has_aux=True)

        case(
            f"gdn fwd+bwd B{B}/T{T}/H{HK}:{HV}x{DK}:{DV}/Q{Q} bf16 ({int(firsts.sum())} seams, "
            f"head block {hb}) vs the jax.numpy body",
            delta_grads((hb, interpret)), delta_grads((None, False)),
            (q, k, v, g, beta, state0), TOL_BF16, TOL_BF16, timed=True,
        )

    # ---- glm4_moe_lite's latent attention: the training form in bf16 vs the
    # plain float32 reference, and the absorbed acting form vs the training form
    from benchmarks.reference import glm4_moe_lite as plain_mla
    from tpu_rl.models.glm4_moe_lite import build_mixer as build_mla, ring_width

    mla_arch = dict(glm_widths)
    for row, B, T in glm_shapes:
        mixer = build_mla(mla_arch, jnp.bfloat16)
        u = f32(B, T, mla_arch["hidden_size"])
        firsts = rng.random((B, T)) < 2.0 / T  # ~2 episode seams a window, as the cell's mix
        firsts[:, [T // 3, T // 3 + 1]] = True  # and two in one tile whatever the draw
        firsts[1:] = firsts[:1]  # the rows of a stepped batch start their episodes together
        seg = jnp.asarray(np.cumsum(firsts, axis=1).astype(np.int32))
        first = jnp.asarray(firsts)
        params = jax.jit(lambda key: mixer.init(key, u, seg)["params"])(jax.random.key(SEED))
        w_y = f32(B, T, mla_arch["hidden_size"])
        if row == "mixer":
            def system(p, u):
                y = mixer.apply({"params": p}, u, seg)
                return (y * w_y).sum(), y

            def reference(p, u):
                y = plain_mla.latent_attention(u, first, p, mla_arch)
                return (y * w_y).sum(), y

            case(
                f"glm4_moe_lite mla mixer fwd+bwd B{B}/T{T} bf16 vs the plain reference "
                f"({int(firsts.sum())} seams)",
                jax.value_and_grad(system, argnums=(0, 1), has_aux=True),
                jax.value_and_grad(reference, argnums=(0, 1), has_aux=True),
                (params, u), TOL_MIXER_BF16, TOL_MIXER_BF16,
                mosaic=jax.default_backend() == "tpu",  # the splash kernels
            )
            continue

        def stepped(p, u):
            """``step`` over the window, ring and counter zeroed at episode
            starts as the worker zeroes the carry."""
            def one(carry, at):
                ring, count = carry
                u_t, first_t = at
                ring = jnp.where(first_t, 0.0, ring)
                count = jnp.where(first_t, 0, count)
                y, ring = mixer.apply({"params": p}, u_t, ring, count, method="step")
                return (ring, count + 1), y

            ring0 = jnp.zeros((B, T, ring_width(mla_arch)))
            _, y = jax.lax.scan(
                one, (ring0, jnp.zeros((B,), jnp.int32)), (u.swapaxes(0, 1), first[0]))
            return y.swapaxes(0, 1)

        case(
            f"glm4_moe_lite mla step B{B}/T{T} bf16 over a latent ring of "
            f"{ring_width(mla_arch)} a slot vs the unroll ({int(firsts[0].sum())} seams)",
            stepped, lambda p, u: mixer.apply({"params": p}, u, seg), (params, u),
            TOL_MIXER_BF16, TOL_MIXER_BF16, mosaic=False,
            ref_is_kernel=jax.default_backend() == "tpu",
        )

    # ---- lfm2_moe's gated short convolution: the training form in bf16 vs the
    # plain float32 reference, and the acting form vs the training form
    from benchmarks.reference import lfm2_moe as plain_conv
    from tpu_rl.models.lfm2_moe import ShortConv

    hidden, taps = lfm2_widths["hidden_size"], lfm2_widths["conv_L_cache"]
    for row, B, T in lfm2_shapes:
        mixer = ShortConv(hidden, taps, jnp.bfloat16)
        u = f32(B, T, hidden)
        firsts = rng.random((B, T)) < 4.0 / T  # ~4 episode seams a window, as the cell's mix
        firsts[:, [T // 3, T // 3 + 1, T - 1]] = True  # two in a row and one at the last step
        firsts[1:] = firsts[:1]  # the rows of a stepped batch start their episodes together
        seg = jnp.asarray(np.cumsum(firsts, axis=1).astype(np.int32))
        first = jnp.asarray(firsts)
        tail0 = jnp.zeros((B, taps - 1, hidden))
        params = jax.jit(lambda key: mixer.init(key, u, seg, tail0)["params"])(jax.random.key(SEED))
        w_y = f32(B, T, hidden)
        if row == "mixer":
            def system(p, u):
                y, _ = mixer.apply({"params": p}, u, seg, tail0)
                return (y * w_y).sum(), y

            def reference(p, u):
                y = plain_conv.short_conv(u, first, p, lfm2_widths)
                return (y * w_y).sum(), y

            case(
                f"lfm2_moe shortconv mixer fwd+bwd B{B}/T{T} bf16 vs the plain reference "
                f"({int(firsts.sum())} seams)",
                jax.value_and_grad(system, argnums=(0, 1), has_aux=True),
                jax.value_and_grad(reference, argnums=(0, 1), has_aux=True),
                (params, u), TOL_MIXER_BF16, TOL_MIXER_BF16, mosaic=False,
            )
            continue

        def stepped(p, u):
            """``step`` over the window, the tail zeroed at episode starts as
            the worker zeroes the carry; the last tail beside the outputs."""
            def one(tail, at):
                u_t, first_t = at
                y, tail = mixer.apply(
                    {"params": p}, u_t, jnp.where(first_t, 0.0, tail), method="step")
                return tail, y

            tail, y = jax.lax.scan(one, tail0, (u.swapaxes(0, 1), first[0]))
            return y.swapaxes(0, 1), tail

        case(
            f"lfm2_moe step B{B}/T{T} bf16 over a tail of {taps - 1} rows vs the unroll "
            f"({int(firsts[0].sum())} seams)",
            stepped, lambda p, u: mixer.apply({"params": p}, u, seg, tail0), (params, u),
            TOL_MIXER_BF16, TOL_MIXER_BF16, mosaic=False,
        )
    attn_bwd_rows(lfm2_attn_bwd_shapes, "lfm2_moe ")

    # ---- evabyte's EVA attention: the training form in bf16 vs the plain float32
    # reference, the pooling alone, and the acting form vs the training form
    from benchmarks.reference import evabyte as plain_eva
    from tpu_rl.models.evabyte import EvaAttention, episode_grid

    ew = evabyte_widths
    hidden, n_heads, W, C = (ew[k] for k in (
        "hidden_size", "num_attention_heads", "window_size", "chunk_size"))
    eva_arch = dict(ew, num_key_value_heads=n_heads)
    for row, B, T in evabyte_shapes:
        mixer = EvaAttention(
            hidden=hidden, heads=n_heads, block=W, chunk=C, rope_theta=float(ew["rope_theta"]),
            init_std=ew["init_std"], dtype=jnp.bfloat16)
        u = f32(B, T, hidden)
        firsts = rng.random((B, T)) < 1.0 / T  # ~1 episode seam a window, as the cell's mix
        firsts[:, T // 3 + 5] = True  # off every grid line of the window, inside a chunk
        if row == "pool-seams":
            firsts[:, [T // 5 + 3, T - T // 7 + 1]] = True
        firsts[1:] = firsts[:1]  # the rows of a stepped batch start their episodes together
        seg = jnp.asarray(np.cumsum(firsts, axis=1).astype(np.int32))
        first = jnp.asarray(firsts)
        params = jax.jit(lambda key: mixer.init(key, u[:, :8], seg[:, :8])["params"])(
            jax.random.key(SEED))
        seams = int(firsts[0].sum())
        if row == "mixer":
            w_y = f32(B, T, hidden)

            def system(p, u):
                y = mixer.apply({"params": p}, u, seg, interpret and T % 128 == 0)
                return (y * w_y).sum(), y

            def reference(p, u):
                # eight heads at a time: o_proj sums over heads, and all 32 at once need
                # 14.5 GiB for the gradient at T 16,384 (compiled for a described v5e)
                y, D, G = 0.0, hidden // n_heads, min(8, n_heads)
                for g in range(0, n_heads, G):
                    cols = slice(g * D, (g + G) * D)
                    part = {
                        "pool_k": p["pool_k"][g:g + G], "pool_v": p["pool_v"][g:g + G],
                        **{n: {"kernel": p[n]["kernel"][:, cols]}
                           for n in ("q_proj", "k_proj", "v_proj")},
                        "o_proj": {"kernel": p["o_proj"]["kernel"][cols]}}
                    y = y + plain_eva.eva_attention(
                        u, first, part, dict(eva_arch, hidden_size=G * D, num_attention_heads=G))
                return (y * w_y).sum(), y

            # the output and every gradient, not the loss itself: a sum of 67M signed
            # terms cancels to where bf16's rounding of y is a tenth of it (PERF.md, PR 46)
            case(
                f"evabyte eva mixer fwd+bwd B{B}/T{T} bf16 vs the plain reference ({seams} seams)",
                jax.grad(system, argnums=(0, 1), has_aux=True),
                jax.grad(reference, argnums=(0, 1), has_aux=True),
                (params, u), TOL_MIXER_BF16, TOL_MIXER_BF16,
            )
            continue
        if row in ("pool", "pool-seams"):
            D = hidden // n_heads
            k, v = (f32(B, T, n_heads, D).astype(jnp.bfloat16) for _ in range(2))
            _, blk, _, ends = episode_grid(seg, W, C)
            last = [np.flatnonzero(np.asarray(e)) for e in ends]
            w_k, w_v = f32(B, T // C, n_heads, D), f32(B, T // C, n_heads, D)

            def pool_system(p, k, v):
                ks, vs, _, _ = mixer.apply({"params": p}, k, v, seg, blk, ends, method="summaries")
                ks, vs = ks.astype(jnp.float32), vs.astype(jnp.float32)
                return (ks * w_k).sum() + (vs * w_v).sum(), (ks, vs)

            def pool_reference(p, k, v):
                k, v = k.astype(jnp.float32), v.astype(jnp.float32)
                pooled = [plain_eva.pooled_rows(k, x, p[w], C, D ** -0.5)
                          for x, w in ((k, "pool_k"), (v, "pool_v"))]
                ks, vs = (jnp.stack([
                    jnp.zeros((T // C, n_heads, D)).at[: len(at)].set(x[b][at])
                    for b, at in enumerate(last)]) for x in pooled)
                return (ks * w_k).sum() + (vs * w_v).sum(), (ks, vs)

            case(
                f"evabyte eva pool fwd+bwd B{B}/T{T}/H{n_heads}x{D}/C{C} bf16 vs shifted products "
                f"({len(last[0])} complete chunks of {T // C}, {seams} seams)",
                jax.value_and_grad(pool_system, argnums=(0, 1, 2), has_aux=True),
                jax.value_and_grad(pool_reference, argnums=(0, 1, 2), has_aux=True),
                (params, k, v), TOL_BF16, TOL_BF16, mosaic=False, timed=True,
            )
            continue

        def stepped(p, u):
            """``step`` over the window, the ring, the store and the counter
            zeroed at episode starts as the worker zeroes the carry."""
            D = hidden // n_heads
            exact, pooled = jnp.zeros((B, W, n_heads, D)), jnp.zeros((B, T // C, n_heads, D))

            def one(carry, at):
                u_t, first_t = at
                *carry, count = jax.tree.map(lambda x: jnp.where(first_t, 0, x), carry)
                y, *carry = mixer.apply({"params": p}, u_t, *carry, count, method="step")
                return (*carry, count + 1), y

            _, y = jax.lax.scan(
                one, (exact, exact, pooled, pooled, jnp.zeros((B,), jnp.int32)),
                (u.swapaxes(0, 1), first[0]))
            return y.swapaxes(0, 1)

        case(
            f"evabyte step B{B}/T{T} bf16 over a ring of {W} and {T // C} summaries vs the unroll "
            f"({seams} seams, {T // W} blocks)",
            stepped, lambda p, u: mixer.apply({"params": p}, u, seg), (params, u),
            TOL_MIXER_BF16, TOL_MIXER_BF16, mosaic=False,
            ref_is_kernel=jax.default_backend() == "tpu",
        )

    # ---- ling_flash: the per-channel delta rule's scan vs the step recurrence, latent
    # attention at unequal head sizes vs the plain reference, the group-limited choice
    from benchmarks.reference import ling_flash as plain_ling
    from tpu_rl.models.ling_flash import build_mixer as build_ling
    from tpu_rl.ops import kda, moe

    lw = dict(ling_flash_widths)
    H, D, bound, Q = lw["num_attention_heads"], lw["head_dim"], lw["kda_lower_bound"], ling_flash_chunk
    for row, B, T in ling_flash_shapes:
        firsts = rng.random((B, T)) < 4.0 / T  # ~4 episode seams a window, as the cell's mix
        firsts[:, [T // 3, T // 3 + 1]] = True  # and two in one chunk whatever the draw
        if row == "kda-one-episode":
            firsts[:] = False
        seg = jnp.asarray(np.cumsum(firsts, axis=1).astype(np.int32))
        first = jnp.asarray(firsts)
        if row.startswith("kda"):
            q, k, v = (f32(B, T, H, D).astype(jnp.bfloat16) for _ in range(3))
            g = bound * jax.nn.sigmoid(3.0 * f32(B, T, H, D) - 3.0)
            if row == "kda-bound":
                g = jnp.full_like(g, bound)
            beta, state0 = jax.nn.sigmoid(f32(B, T, H)), f32(B, H, D, D) * D**-0.5
            w_o, w_last = f32(B, T, H, D), f32(B, H, D, D)

            def chunked(q, k, v, g, beta, state0, kernel=(H, True) if interpret else None):
                """The Pallas pair where the gate takes it (on the chip; here the
                interpreter's), or with ``kernel=(None, False)`` the jax.numpy body."""
                return kda.kda_chunked(
                    q, k, v, g, beta, seg, state0, Q, jnp.bfloat16, kernel=kernel)

            def stepped(q, k, v, g, beta, state0):
                """``kda_step`` over the window in rematerialised blocks of ``Q`` steps."""
                @jax.checkpoint
                def block(S, xs):
                    def one(S, at):
                        *at, first_t = at
                        o, S = kda.kda_step(*at, jnp.where(first_t[:, None, None, None], 0.0, S))
                        return S, o
                    return jax.lax.scan(one, S, xs)

                blocks = tuple(
                    jnp.moveaxis(a, 1, 0).reshape(T // Q, Q, *a.shape[:1], *a.shape[2:])
                    for a in (q, k, v, g, beta, first))
                last, o = jax.lax.scan(block, state0, blocks)
                return jnp.moveaxis(o.reshape(T, *o.shape[2:]), 0, 1), last

            def graded(rule):
                def loss(*a):
                    o, last = rule(*a)
                    return (o * w_o).sum() + (last * w_last).sum(), (o, last)
                return jax.value_and_grad(loss, argnums=tuple(range(6)), has_aux=True)

            case(
                f"kda fwd+bwd B{B}/T{T}/H{H}x{D}/Q{Q} bf16 ({row[4:]}: {int(firsts.sum())} seams, "
                f"mean decay a step {float(jnp.exp(g).mean()):.3f}) vs the step recurrence",
                graded(chunked), graded(stepped), (q, k, v, g, beta, state0), TOL_BF16, TOL_BF16,
                timed=row == "kda-seams",
                timed_too={"jnp": graded(functools.partial(chunked, kernel=(None, False)))},
            )
        elif row == "mla":
            mixer = build_ling(lw, "mla", jnp.bfloat16).clone(name=None)
            u = f32(B, T, lw["hidden_size"])
            params = jax.jit(lambda key: mixer.init(key, u, seg)["params"])(jax.random.key(SEED))
            w_y = f32(B, T, lw["hidden_size"])

            def system(p, u):
                y = mixer.apply({"params": p}, u, seg)
                return (y * w_y).sum(), y

            def reference(p, u):
                y = plain_ling.latent_attention(u, first, p, lw)
                return (y * w_y).sum(), y

            case(
                f"ling_flash mla mixer fwd+bwd B{B}/T{T} bf16 at {H} heads of "
                f"{lw['qk_nope_head_dim'] + lw['qk_rope_head_dim']}:{lw['v_head_dim']} vs the "
                f"plain reference ({int(firsts.sum())} seams)",
                jax.value_and_grad(system, argnums=(0, 1), has_aux=True),
                jax.value_and_grad(reference, argnums=(0, 1), has_aux=True),
                (params, u), TOL_MIXER_BF16, TOL_MIXER_BF16,
                mosaic=jax.default_backend() == "tpu",  # the splash kernels
            )
        else:
            E, top_k = lw["num_experts"], lw["num_experts_per_tok"]
            groups = lw["n_group"], lw["topk_group"]
            u, kernel = f32(B * T, lw["hidden_size"]), f32(lw["hidden_size"], E) * lw["hidden_size"]**-0.5
            bias = 0.05 * f32(E)

            def routed(u, kernel, bias):
                choice, weight = moe.route(
                    u, kernel, bias, top_k, lw["routed_scaling_factor"], "sigmoid", *groups)
                order = jnp.argsort(choice, axis=-1)
                return (jnp.take_along_axis(choice, order, -1).astype(jnp.float32),
                        jnp.take_along_axis(weight, order, -1))

            def sorted_choice(u, kernel, bias):
                s = jax.nn.sigmoid(jnp.dot(u, kernel, precision=jax.lax.Precision.HIGHEST))
                choice, _ = plain_ling.group_limited_choice(s + bias, top_k, *groups)
                choice = jnp.sort(choice, axis=-1)
                chosen = jnp.take_along_axis(s, choice, -1)
                weight = lw["routed_scaling_factor"] * chosen / chosen.sum(-1, keepdims=True)
                return choice.astype(jnp.float32), weight

            case(
                f"ling_flash group-limited route N{B * T}/E{E}/k{top_k}/groups {groups[1]} of "
                f"{groups[0]} vs the reference's sort",
                routed, sorted_choice, (u, kernel, bias), 1e-5, 1e-5, mosaic=False,  # one id: 2e-3
            )
    return rows


def phase_kernels(require_tpu: bool = True) -> dict:
    import jax

    from tpu_rl.utils.platform import CompileClock, backend_info, enable_compile_cache

    enable_compile_cache()
    clock = CompileClock()
    info = backend_info()
    if require_tpu:
        check(info["platform"] == "tpu", f"kernels ran on {info['platform']}")
        from jax.experimental.pallas import tpu as pltpu

        tpu = pltpu.get_tpu_info()
        print(
            f"[kernels] {info['device_kind']}: VMEM "
            f"{tpu.vmem_capacity_bytes / 2**20:.0f} MiB per core "
            "(pltpu.get_tpu_info)", flush=True,
        )
    t0 = time.time()
    rows = kernel_checks(interpret=jax.default_backend() != "tpu")
    bad = [r for r in rows if not r["ok"]]
    check(not bad, f"{len(bad)} kernel case(s) failed: {[r['kernel'] for r in bad]}")
    return {
        **device_of(info), "wall_s": round(time.time() - t0, 1),
        **clock.stats(), "cases": len(rows),
        "max_err": max(r["err"] for r in rows),
    }


# name -> (config, kernel path expected on TPU, (data, seq) mesh or None=DP)
MULTICHIP_CASES = {
    "dp-lstm-island": (
        dict(algo="PPO", hidden_size=64, seq_len=5, batch_size=128,
             obs_shape=(4,), action_space=2),
        "lstm_pallas", None,
    ),
    "dp-flash-island": (LONGCTX, "attn_flash_pallas", None),
    "ring-2x2": (
        {**LONGCTX, "attention_impl": "ring", "mesh_data": 2, "mesh_seq": 2},
        None, (2, 2),
    ),
}


def multichip_steps(
    n: int = 4, cases: dict | None = None, require_tpu: bool = True
) -> dict:
    """In one process over ``n`` chips: the tiny-shape DP / ring steps of
    ``__graft_entry__`` on the real devices, then the shard_map kernel
    islands (LSTM, flash) and ring attention at real widths, with the batch
    and the parameters checked to live on every device, not device 0."""
    import jax

    import __graft_entry__ as graft
    from tpu_rl.utils.platform import CompileClock, backend_info, enable_compile_cache

    enable_compile_cache()
    clock = CompileClock()
    info = backend_info()
    check(info["device_count"] >= n, str(info))
    if require_tpu:
        check(info["platform"] == "tpu", str(info))
    t0 = time.time()
    graft.multichip_steps(n)

    import numpy as np

    from tpu_rl.algos.registry import get_algo
    from tpu_rl.config import Config
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.parallel import (
        make_mesh, make_parallel_train_step, make_sp_mesh, make_sp_train_step,
        replicate, shard_batch,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_rl.types import Batch
    from tpu_rl.utils.platform import program_paths

    def batch_for(cfg, family):
        lay = BatchLayout.from_config(cfg)
        rng = np.random.default_rng(SEED)
        zb = Batch.zeros(
            cfg.batch_size, cfg.seq_len, cfg.obs_shape, cfg.action_space,
            cfg.hidden_size, continuous=family.continuous,
            hx_width=lay.hx, cx_width=lay.cx,
        )
        firsts = np.zeros(zb.is_fir.shape, np.float32)
        firsts[:, 0] = 1.0
        return zb.replace(
            obs=rng.standard_normal(zb.obs.shape).astype(np.float32),
            act=rng.integers(0, cfg.action_space, size=zb.act.shape).astype(np.float32),
            rew=rng.standard_normal(zb.rew.shape).astype(np.float32) * 0.1,
            log_prob=np.full(zb.log_prob.shape, -math.log(cfg.action_space), np.float32),
            is_fir=firsts,
        )

    out = {}
    for name, (kw, path, sp) in (cases or MULTICHIP_CASES).items():
        cfg = Config.from_dict(kw)
        if sp:
            mesh = make_sp_mesh(*sp)
            family, state, step = get_algo(cfg.algo).build(
                cfg, jax.random.key(SEED), mesh=mesh
            )
            pstep = make_sp_train_step(step, mesh, cfg)
            batch = jax.device_put(
                batch_for(cfg, family), NamedSharding(mesh, P("data", "seq"))
            )
        else:
            mesh = make_mesh(n)
            family, state, step = get_algo(cfg.algo).build(cfg, jax.random.key(SEED))
            pstep = make_parallel_train_step(step, mesh, cfg)
            batch = shard_batch(batch_for(cfg, family), mesh)
        state = replicate(state, mesh)
        key = replicate(jax.random.key(1), mesh)
        on = lambda leaf: {s.device for s in leaf.addressable_shards}  # noqa: E731
        check(len(on(batch.obs)) == n, f"{name}: batch on {on(batch.obs)}")
        check(
            all(len(on(leaf)) == n for leaf in jax.tree.leaves(state)),
            f"{name}: train state not on every device",
        )
        paths = program_paths(pstep.lower(state, batch, key))
        if path is not None and info["platform"] == "tpu":
            check(
                path in paths["paths"] and paths["mosaic_calls"] > 0,
                f"{name}: took {paths}; expected {path}",
            )
        for _ in range(3):
            state, metrics = pstep(state, batch, key)
        loss = float(jax.device_get(metrics["loss"]))
        check(math.isfinite(loss), f"{name}: loss {loss}")
        if info["platform"] == "tpu":  # the CPU backend reports no stats
            mem = [d.memory_stats()["bytes_in_use"] for d in jax.devices()[:n]]
            check(all(m > 0 for m in mem), f"{name}: bytes_in_use {mem}")
        out[name] = {"loss": loss, **paths}
        print(f"[multichip] {name} {json.dumps(out[name])}", flush=True)
    return {
        **device_of(info), "wall_s": round(time.time() - t0, 1),
        **clock.stats(), "steps": out,
    }


def phase_multichip(work: str, n_devices: int) -> dict:
    """Four chips through the CLI (each run spawns its own chip owner), then
    the in-process steps in one child."""
    if n_devices < 4:
        print(f"[multichip] skipped: {n_devices} device(s)")
        return {"skipped": f"{n_devices} device(s)"}
    res = {}
    res["local-mesh4"] = phase_cli(
        "PPO", os.path.join(work, "mc-local"), 8, REF, cli=("--mesh-data", "4"),
        expect_mesh={"data": 4},
    )
    colo = {**REF, "loss_log_interval": 50, "model_save_interval": 100}
    res["colocated-mesh4"] = phase_cli(
        "PPO", os.path.join(work, "mc-colo"), 200, colo, role="colocated",
        cli=("--env-mode", "colocated", "--mesh-data", "4"),
        expect_mesh={"data": 4},
    )
    res["sebulba-2+2"] = phase_cli(
        "PPO", os.path.join(work, "mc-seb"), 200, colo, role="sebulba",
        cli=("--env-mode", "colocated", "--sebulba-split", "2"),
        expect_mesh={"data": 2},
    )
    res["steps"] = run_phase_child("multichip-steps", work, timeout=600)
    return res


# ------------------------------------------------------------------- driver
def child_main(phase: str, out_path: str) -> None:
    """Body of one chip-owning child: run the phase, write its result."""
    if phase == "probe":
        from tpu_rl.utils.platform import backend_info

        res = backend_info()
    elif phase == "kernels":
        res = phase_kernels()
    elif phase == "multichip-steps":
        res = multichip_steps()
    else:
        cfg_kw, expect = LEARNER_PHASES[phase]
        res = phase_learner(cfg_kw, expect_path=expect)
    with open(out_path, "w") as f:
        json.dump(res, f)


# Child log lines worth showing in the parent's (size-limited) output.
ECHO = ("[kernels]", "[multichip]", "[learner]", "multichip_steps:")


def run_phase_child(phase: str, work: str, timeout: float) -> dict:
    out_path = os.path.join(work, f"{phase}.json")
    log = os.path.join(work, f"{phase}.log")
    rc = run_child(
        [sys.executable, os.path.abspath(__file__), "--child", phase,
         "--out", out_path],
        timeout, log,
    )
    with open(log, errors="replace") as f:
        for line in f:
            if line.startswith(ECHO):
                print(line, end="")
    check(rc == 0, f"phase child {phase} exited {rc}\n{tail(log)}")
    return read_json(out_path)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", help="comma-separated phases of: " + ",".join(PHASES))
    ap.add_argument("--logs-dir", help="keep every phase's logs and records here")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        child_main(args.child, args.out)
        return 0

    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        print("chip_smoke: JAX_PLATFORMS=cpu — this script needs a TPU", file=sys.stderr)
        return 2
    import tpu_rl  # noqa: F401 — fails here when run outside the repo

    only = args.only.split(",") if args.only else list(PHASES)
    unknown = set(only) - set(PHASES)
    if unknown:
        ap.error(f"unknown phase(s) {sorted(unknown)}")
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        try:
            probe = run_phase_child("probe", work, timeout=180)
        except PhaseFailed as e:
            print(f"chip_smoke: device probe failed: {e}", file=sys.stderr)
            return 2
        if probe["platform"] != "tpu":
            print(
                f"chip_smoke: JAX found no accelerator (platform "
                f"{probe['platform']!r}) — this script needs a TPU",
                file=sys.stderr,
            )
            return 2
        device = device_of(probe)
        print(f"chip_smoke: device {json.dumps(device)}", flush=True)

        colo = {**REF, "loss_log_interval": 50, "model_save_interval": 100}
        ppo_dir = os.path.join(work, "ppo")
        runners = {
            "native": phase_native,
            "ppo": lambda: phase_cli("PPO", ppo_dir, 8, REF),
            "ppo-resume": lambda: phase_cli("PPO", ppo_dir, 8, REF, resume=True),
            "impala": lambda: phase_cli("IMPALA", os.path.join(work, "impala"), 8, REF),
            "colocated": lambda: phase_cli(
                "PPO", os.path.join(work, "colo"), 300, colo, role="colocated",
                cli=("--env-mode", "colocated"),
            ),
            "kernels": lambda: run_phase_child("kernels", work, timeout=600),
            **{
                name: (lambda n=name: run_phase_child(n, work, timeout=600))
                for name in LEARNER_PHASES
            },
            "multichip": lambda: phase_multichip(work, device["count"]),
        }
        results, failed = {}, []
        for name in PHASES:
            if name not in only:
                continue
            t0 = time.time()
            try:
                results[name] = runners[name]()
                verdict = "skipped" if "skipped" in results[name] else "ok"
                print(
                    f"chip_smoke: phase {name} {verdict} "
                    f"{json.dumps(results[name])}", flush=True,
                )
            except Exception as e:  # noqa: BLE001 — report it, run the rest
                failed.append(name)
                why = str(e) if isinstance(e, PhaseFailed) else traceback.format_exc()
                results[name] = {"failed": why[-3000:]}
                print(
                    f"chip_smoke: phase {name} FAILED after "
                    f"{time.time() - t0:.0f}s:\n{why}", flush=True,
                )
        if "ppo" in results and "ppo-resume" in results and not failed:
            print(
                f"chip_smoke: compile cache cold {results['ppo']['compile_s']}s "
                f"({results['ppo']['cache_misses']} misses) -> warm "
                f"{results['ppo-resume']['compile_s']}s "
                f"({results['ppo-resume']['cache_hits']} hits)", flush=True,
            )
        print("chip_smoke: summary " + json.dumps({"phases": results, "failed": failed}))
        print(json.dumps({"ok": not failed, "device": device}))
        return 1 if failed else 0
    finally:
        if args.logs_dir:
            shutil.copytree(
                work, args.logs_dir, dirs_exist_ok=True,
                ignore=shutil.ignore_patterns("models", "history", "telemetry"),
            )
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
