"""Learner benchmark suite.

Measures steady-state learner throughput in transitions/sec — the reference's
own headline metric (`learner-throughput` timer, ``/root/reference/agents/
learner.py:34-36`` + ``utils/utils.py:167-189``: transitions/update =
seq_len x batch_size = 640, window 100) — plus achieved FLOPs and MFU, for:

- all six algorithms at the reference's exact batch quantum (batch 128 x
  seq 5 x hidden 64) — the apples-to-apples rows. These are LATENCY-bound:
  640 transitions of a 64-wide LSTM is <<1% of a TPU's MXU, so transitions/sec
  measures dispatch+fusion quality, not chip capability;
- a wide-LSTM IMPALA workload and a long-context bf16 transformer PPO
  workload sized to load the MXU — the chip-utilization rows.

FLOPs are XLA's own analytical count for the compiled step
(``compiled.cost_analysis()["flops"]``); MFU is achieved FLOPs/s over the
chip's bf16 peak. The reference publishes no measured numbers (BASELINE.md);
its by-construction ceiling is 600 transitions/s (3 machines x 10 workers x
~20 env-steps/s: hard 0.05 s sleep ``agents/worker.py:131``, fleet config
``utils/machines.json:6-25``), which is the only defensible denominator for
``vs_baseline``.

stdout: ONE JSON line {"metric", "value", "unit", "vs_baseline"} (the IMPALA
reference-quantum row — same headline as rounds 1-2).
Full matrix: printed to stderr and written to ``bench_results.json`` — but
only for a full run on an accelerator. CPU-backend runs write
``bench_results.cpu.json`` and ``TPU_RL_BENCH_LIGHT`` (partial @ref-only
matrix) writes ``bench_results.light.json``, so the committed on-chip table
is never clobbered by CPU or partial numbers.

``TPU_RL_BENCH_E2E=1 python bench.py`` runs the e2e FEED comparison instead:
the production LearnerService through the real shm path, synchronous vs
prefetched data plane (``run_e2e_compare`` -> ``bench_e2e_feed[.cpu].json``).

``TPU_RL_BENCH_RELAY=1 python bench.py`` runs the fan-in A/B: raw (zero-copy
peek+forward relay, columnar push_tick ingest) vs decode baseline through the
real Manager and LearnerStorage, plus the ISSUE-8 rows — the shm
manager->storage hop with native batch validation at the sink, and the
native-vs-python frame-validation micro A/B (``run_relay_compare`` ->
``bench_relay[.cpu].json``; ``TPU_RL_BENCH_RELAY_LIGHT=1`` is the `make ci`
smoke shape, asserting direction without writing numbers).

``TPU_RL_BENCH_DIAG=1 python bench.py`` runs the learning-dynamics diag A/B:
the same chained train step with ``Config.learn_diag`` on vs off, pinning the
<=2% step-time overhead contract for the in-jit diagnostics
(``run_diag_compare`` -> ``bench_diag[.cpu].json``;
``TPU_RL_BENCH_DIAG_LIGHT=1`` is the smoke shape).
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REFERENCE_BASELINE_TPS = 600.0  # see module docstring

# Peak-FLOPs table + analytical-FLOPs extraction live in the runtime
# performance plane (tpu_rl/obs/perf.py) and are imported here, so the
# offline matrix and the live learner-mfu gauge can never disagree on the
# denominator or the cost-analysis handling. Names re-exported for
# existing importers of bench.PEAK_FLOPS / bench.device_peak_flops.
from tpu_rl.obs.perf import (  # noqa: E402
    PEAK_FLOPS,  # noqa: F401 — re-export
    compiled_flops,
    device_peak_flops,
)


def _make_batch(cfg, family):
    """Random batch at cfg shapes with the wire layout's carry widths."""
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.types import Batch

    lay = BatchLayout.from_config(cfg)
    rng = np.random.default_rng(0)
    zb = Batch.zeros(
        cfg.batch_size, cfg.seq_len, cfg.obs_shape, cfg.action_space,
        cfg.hidden_size, continuous=family.continuous,
        hx_width=lay.hx, cx_width=lay.cx,
    )
    firsts = np.zeros(zb.is_fir.shape, np.float32)
    firsts[:, 0] = 1.0
    if family.continuous:
        act = rng.normal(size=zb.act.shape).astype(np.float32) * 0.3
        log_prob = np.full(zb.log_prob.shape, -1.0, np.float32)
    else:
        act = rng.integers(0, cfg.action_space, size=zb.act.shape).astype(
            np.float32
        )
        log_prob = np.full(
            zb.log_prob.shape, -float(np.log(cfg.action_space)), np.float32
        )
    return zb.replace(
        obs=jnp.asarray(rng.normal(size=zb.obs.shape).astype(np.float32)),
        act=jnp.asarray(act),
        rew=jnp.asarray(rng.normal(size=zb.rew.shape).astype(np.float32) * 0.1),
        log_prob=jnp.asarray(log_prob),
        is_fir=jnp.asarray(firsts),
    )


def _sync(metrics) -> float:
    """Wait for the whole dispatched chain by reading its last loss back to
    the host (the chain is sequential, so one readback covers it)."""
    return float(np.asarray(jax.device_get(metrics["loss"])))


def bench_one(
    name: str, cfg_kw: dict, warmup: int, iters: int, chain: int = 1
) -> dict:
    """One workload row. ``chain > 1`` compiles K updates per dispatched
    program (``make_parallel_train_step(chain=K)``): the sub-ms
    reference-quantum update is otherwise dominated by fixed per-dispatch
    host overhead, so chaining reports the chip's sustainable update rate —
    what the reference's local-GPU timer measures
    (``/root/reference/utils/utils.py:174-189``). This is the same dispatch
    path production takes: ``LearnerService`` runs chained programs when
    ``Config.learner_chain > 1`` (equivalence to sequential updates through
    the real shm feed is asserted by
    ``tests/test_runtime.py::test_learner_chain_matches_sequential_through_shm``)."""
    from tpu_rl.algos.registry import get_algo
    from tpu_rl.config import Config
    from tpu_rl.parallel import (
        make_mesh,
        make_parallel_train_step,
        replicate,
        shard_batch,
        shard_chained_batch,
    )

    # Optional: wrap the timed region in a profiler trace (xprof/tensorboard
    # readable). Popped from a copy before Config validation — it is bench
    # plumbing, not a workload parameter, and callers reuse workload dicts.
    cfg_kw = dict(cfg_kw)
    profile_dir = cfg_kw.pop("profile_dir", None)

    cfg = Config.from_dict(cfg_kw)
    family, state, train_step = get_algo(cfg.algo).build(cfg, jax.random.key(0))
    n_vis = len(jax.devices())
    # Use every visible chip; keep the global batch at the workload quantum.
    n_dev = n_vis if cfg.batch_size % n_vis == 0 else 1
    mesh = make_mesh(n_dev)
    pstep = make_parallel_train_step(train_step, mesh, cfg, chain=chain)
    if chain > 1:
        one = _make_batch(cfg, family)
        batch = shard_chained_batch([one] * chain, mesh)
    else:
        batch = shard_batch(_make_batch(cfg, family), mesh)
    state = replicate(state, mesh)
    key = replicate(jax.random.key(1), mesh)

    lowered = pstep.lower(state, batch, key)
    compiled = lowered.compile()
    # XLA's cost analysis counts a scan/while body ONCE regardless of trip
    # count (verified: the K=4 chained program reports the same total flops
    # as the unchained step), so the chained program's count already IS
    # per-update.
    flops_per_step = compiled_flops(compiled)

    metrics = None
    for _ in range(warmup):
        state, metrics = pstep(state, batch, key)
    if metrics is not None:
        _sync(metrics)

    if profile_dir is not None:
        jax.profiler.start_trace(profile_dir)
    try:
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = pstep(state, batch, key)
        # The chain is sequential (state feeds state), so one end-of-chain
        # data readback accounts for every update in the timed region.
        _sync(metrics)
        dt = time.perf_counter() - t0
    finally:
        # finally: an exception mid-loop must still flush the trace (and
        # must not leave the profiler running to poison later rows —
        # run_all catches per-row exceptions and keeps going).
        if profile_dir is not None:
            jax.profiler.stop_trace()

    transitions = cfg.batch_size * cfg.seq_len
    updates = iters * chain
    tps = updates * transitions / dt
    achieved = flops_per_step * updates / dt
    peak = device_peak_flops()
    mfu = (achieved / (peak * n_dev)) if (peak and achieved) else None
    return {
        "name": name,
        "algo": cfg.algo,
        "model": cfg.model,
        "compute_dtype": cfg.compute_dtype,
        "batch": cfg.batch_size,
        "seq": cfg.seq_len,
        "hidden": cfg.hidden_size,
        "steps_per_call": chain,
        "step_ms": round(dt / updates * 1e3, 3),
        "tps": round(tps, 1),
        "flops_per_step": flops_per_step,
        "achieved_flops_per_s": round(achieved, 1),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "regime": (
            "latency-bound" if (mfu is None or mfu < 0.01) else "compute-bound"
        ),
        "devices": n_dev,
        "device_kind": jax.devices()[0].device_kind,
    }


# The benchmark matrix. Reference-quantum rows use the reference's exact
# shapes (``/root/reference/utils/parameters.json:13-14,27``: batch 128 x
# seq 5, hidden 64; CartPole (4,)/2 discrete, MountainCarContinuous (2,)/1
# continuous). Saturating rows are sized to load the MXU on one chip.
_REF = dict(batch_size=128, seq_len=5, hidden_size=64)
_DISC = dict(obs_shape=(4,), action_space=2)
_CONT = dict(obs_shape=(2,), action_space=1, is_continuous=True)

# (name, cfg, warmup_calls, timed_calls, updates_per_call). The @ref rows
# chain 16 updates per dispatched program (make_parallel_train_step(chain=16),
# tpu_rl/parallel/dp.py): their per-update compute is sub-ms, so fixed
# per-dispatch host overhead would otherwise dominate the measurement.
WORKLOADS: list[tuple[str, dict, int, int, int]] = [
    ("IMPALA@ref", dict(algo="IMPALA", **_REF, **_DISC), 5, 50, 16),
    ("PPO@ref", dict(algo="PPO", **_REF, **_DISC), 5, 50, 16),
    ("V-MPO@ref", dict(algo="V-MPO", **_REF, **_DISC), 5, 50, 16),
    ("SAC@ref", dict(algo="SAC", **_REF, **_DISC), 5, 25, 16),
    ("PPO-Continuous@ref", dict(algo="PPO-Continuous", **_REF, **_CONT), 5, 50, 16),
    ("SAC-Continuous@ref", dict(algo="SAC-Continuous", **_REF, **_CONT), 5, 25, 16),
    (
        "IMPALA@wide-lstm",
        dict(
            algo="IMPALA", batch_size=1024, seq_len=16, hidden_size=1024,
            obs_shape=(64,), action_space=8,
        ),
        5, 30, 1,
    ),
    # Same workload with bf16 matmul compute (params f32, f32 accumulation;
    # models/cells.py): the dtype-matched chip-capability row — its MFU is
    # against the SAME bf16 peak the denominator uses, unlike the f32 row
    # above, whose MFU vs bf16 peak understates by construction.
    (
        "IMPALA@wide-lstm-bf16",
        dict(
            algo="IMPALA", batch_size=1024, seq_len=16, hidden_size=1024,
            obs_shape=(64,), action_space=8, compute_dtype="bfloat16",
        ),
        5, 30, 1,
    ),
    (
        "PPO-transformer@longctx",
        dict(
            algo="PPO", model="transformer", compute_dtype="bfloat16",
            batch_size=8, seq_len=2048, hidden_size=512, n_heads=8,
            n_layers=4, obs_shape=(64,), action_space=8,
        ),
        3, 20, 1,
    ),
    # Same model with flash-style blockwise attention and 2x the batch: full
    # attention materializes the (B, H, S, S) f32 score tensor per layer
    # (~1 GB at these shapes) — an HBM-bound pattern that capped the row
    # above at 14.7% MFU; blockwise streams (block, block) tiles through an
    # online softmax (O(T) residuals, parallel/sequence.py) so HBM traffic
    # drops to O(T*D) and the freed memory buys batch parallelism.
    (
        "PPO-transformer@longctx-blockwise",
        dict(
            algo="PPO", model="transformer", compute_dtype="bfloat16",
            attention_impl="blockwise",
            batch_size=16, seq_len=2048, hidden_size=512, n_heads=8,
            n_layers=4, obs_shape=(64,), action_space=8,
        ),
        3, 20, 1,
    ),
    # The library's splash kernel (parallel/sequence.py flash_attention_tpu,
    # tiles from _splash_block_sizes) at the same 2x batch the blockwise row
    # buys: the kernel keeps blockwise's O(T) memory. Its time per layer is
    # examples/bench_flash_attention.py's to measure; the cells' is PERF.md's.
    (
        "PPO-transformer@longctx-flash",
        dict(
            algo="PPO", model="transformer", compute_dtype="bfloat16",
            attention_impl="flash",
            batch_size=16, seq_len=2048, hidden_size=512, n_heads=8,
            n_layers=4, obs_shape=(64,), action_space=8,
        ),
        3, 20, 1,
    ),
    # 2x batch again: the kernel's O(T) residuals leave HBM headroom full
    # attention can't touch (its (B,H,T,T) scores would be ~8 GB here), and
    # the larger per-dispatch program amortizes layer-boundary overheads —
    # the MFU-maximizing single-chip long-context configuration.
    (
        "PPO-transformer@longctx-flash-b32",
        dict(
            algo="PPO", model="transformer", compute_dtype="bfloat16",
            attention_impl="flash",
            batch_size=32, seq_len=2048, hidden_size=512, n_heads=8,
            n_layers=4, obs_shape=(64,), action_space=8,
        ),
        3, 12, 1,
    ),
]


def perf_crosscheck(warmup: int = 3, iters: int = 30) -> dict:
    """Live performance plane vs this file's offline methodology on the SAME
    compiled program at the reference quantum: ``PerfTracker``'s one-time AOT
    capture must report the same analytical FLOPs as the inline
    ``cost_analysis`` here, and its windowed achieved-FLOPs/s must agree with
    the wall-clock number within timing noise (the tier-1 test pins 15%).
    This is the structural guarantee that ``learner-mfu`` on a dashboard
    means the same thing as the committed bench table."""
    from tpu_rl.algos.registry import get_algo
    from tpu_rl.config import Config
    from tpu_rl.obs.perf import PerfTracker
    from tpu_rl.parallel import (
        make_mesh,
        make_parallel_train_step,
        replicate,
        shard_batch,
    )

    cfg = Config.from_dict(dict(algo="IMPALA", **_REF, **_DISC))
    family, state, train_step = get_algo(cfg.algo).build(cfg, jax.random.key(0))
    mesh = make_mesh(1)
    pstep = make_parallel_train_step(train_step, mesh, cfg)
    batch = shard_batch(_make_batch(cfg, family), mesh)
    state = replicate(state, mesh)
    key = replicate(jax.random.key(1), mesh)

    flops_offline = compiled_flops(pstep.lower(state, batch, key).compile())
    tracker = PerfTracker(n_devices=1)
    tracker.capture(pstep, state, batch, key)

    metrics = None
    for _ in range(warmup):
        state, metrics = pstep(state, batch, key)
    if metrics is not None:
        _sync(metrics)
    t0 = time.perf_counter()
    for _ in range(iters):
        t_it = time.perf_counter()
        state, metrics = pstep(state, batch, key)
        _sync(metrics)
        tracker.note(time.perf_counter() - t_it)
    dt = time.perf_counter() - t0

    achieved_offline = flops_offline * iters / dt if dt > 0 else 0.0
    achieved_live = tracker.achieved_flops_per_s() or 0.0
    return {
        "flops_per_step_offline": flops_offline,
        "flops_per_step_live": tracker.flops_per_call,
        "flops_agreement": (
            round(tracker.flops_per_call / flops_offline, 4)
            if flops_offline else None
        ),
        "achieved_flops_per_s_offline": round(achieved_offline, 1),
        "achieved_flops_per_s_live": round(achieved_live, 1),
        "achieved_agreement": (
            round(achieved_live / achieved_offline, 4)
            if achieved_offline else None
        ),
        "recompiles": tracker.recompiles,
        "iters": iters,
    }


def goodput_crosscheck(
    updates: int = 64,
    feeders: int = 2,
    batch_size: int = 32,
    seq_len: int = 5,
    hidden_size: int = 32,
    model_port: int = 29894,
) -> dict:
    """Goodput ledger vs the execution timer on the SAME live learner run:
    the ledger's train-step attribution (compute + recompile — the first
    dispatch carries the jit compile and lands in recompile) must equal the
    sum of the windowed ``learner-step-time`` spans within ±5%. Both observe
    identical dispatch boundaries, so disagreement means the ledger dropped
    or double-counted main-lane time — the same structural guarantee
    ``perf_crosscheck`` gives the MFU gauges, extended to the goodput plane.
    ``updates`` must stay under the timer's 100-span window (chain=1, one
    span per update) so the deque retains every step."""
    import tempfile
    import threading

    from tpu_rl.config import Config
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.data.shm_ring import OnPolicyStore, alloc_handles
    from tpu_rl.runtime.learner_service import LearnerService
    from tpu_rl.types import BATCH_FIELDS

    assert updates < 100, "timer windows hold 100 spans; keep them all"
    with tempfile.TemporaryDirectory() as result_dir:
        # result_dir turns the telemetry plane on (Config.telemetry_enabled);
        # the stat PUB merely connects, so no listener is needed.
        cfg = Config.from_dict(
            dict(
                algo="IMPALA", batch_size=batch_size, seq_len=seq_len,
                hidden_size=hidden_size, obs_shape=(4,), action_space=2,
                learner_chain=1, learner_prefetch=2,
                loss_log_interval=10**9, result_dir=result_dir,
            )
        )
        layout = BatchLayout.from_config(cfg)
        handles = alloc_handles(layout, capacity=cfg.batch_size)
        rng = np.random.default_rng(0)
        window = {}
        for f in BATCH_FIELDS:
            shape = (layout.seq_len, layout.width(f))
            if f == "act":
                window[f] = rng.integers(0, 2, size=shape).astype(np.float32)
            elif f == "is_fir":
                a = np.zeros(shape, np.float32)
                a[0] = 1.0
                window[f] = a
            elif f == "log_prob":
                window[f] = np.full(shape, -0.7, np.float32)
            else:
                window[f] = rng.standard_normal(shape).astype(np.float32) * 0.1

        stop = threading.Event()
        put_lock = threading.Lock()

        def feed() -> None:
            store = OnPolicyStore(handles, layout)
            while not stop.is_set():
                with put_lock:
                    ok = store.put(window)
                if not ok:
                    time.sleep(0)

        threads = [
            threading.Thread(target=feed, daemon=True) for _ in range(feeders)
        ]
        for t in threads:
            t.start()
        svc = LearnerService(
            cfg, handles, model_port=model_port, stop_event=stop,
            max_updates=updates, publish_interval=10**9,
            stat_port=model_port + 1,
        )
        try:
            svc.run()
        finally:
            stop.set()
        for t in threads:
            t.join(timeout=10)

    snap = svc.ledger.snapshot()
    step_sum = sum(svc.timer.elapsed.get("learner-step-time", ()))
    ledger_sum = snap["buckets"]["compute"] + snap["buckets"]["recompile"]
    return {
        "updates": updates,
        "step_timer_s": round(step_sum, 4),
        "ledger_step_s": round(ledger_sum, 4),
        "agreement": (
            round(ledger_sum / step_sum, 4) if step_sum > 0 else None
        ),
        "goodput": round(snap["goodput"], 4),
        "ratios_sum": round(sum(snap["ratios"].values()), 4),
        "overcommit_ratio": round(snap["overcommit_ratio"], 6),
    }


def run_all(out_path: str | None = None) -> dict:
    rows = []
    workloads = WORKLOADS
    on_cpu = jax.devices()[0].platform == "cpu"
    light = bool(os.environ.get("TPU_RL_BENCH_LIGHT")) or on_cpu
    if light:
        # CPU / light mode: the MXU-saturating rows take many minutes per
        # compile on a host core and measure nothing meaningful there.
        workloads = [w for w in WORKLOADS if w[0].endswith("@ref")]
    if out_path is None:
        # Never clobber the committed on-chip table with host-CPU numbers or
        # a partial (light) matrix (round 3 lost its TPU record exactly this
        # way): only a full run on an accelerator writes the canonical file.
        if on_cpu:
            out_path = "bench_results.cpu.json"
        elif light:
            out_path = "bench_results.light.json"
        else:
            out_path = "bench_results.json"
    failed = []  # rows / cross-checks that raised: the process exits nonzero
    for name, cfg_kw, warmup, iters, chain in workloads:
        try:
            row = bench_one(name, cfg_kw, warmup, iters, chain)
        except Exception as e:  # record, finish the matrix, then fail
            row = {"name": name, "error": f"{type(e).__name__}: {e}"}
            failed.append(name)
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)

    result = {
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
        "peak_bf16_flops_per_chip": device_peak_flops(),
        "reference_baseline_tps": REFERENCE_BASELINE_TPS,
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": rows,
    }
    # Live-plane agreement (PerfTracker vs this file's cost analysis) and
    # goodput-plane agreement (ledger vs timer on a live learner).
    for key, check in (
        ("perf_plane", perf_crosscheck),
        ("goodput_plane", goodput_crosscheck),
    ):
        try:
            result[key] = check()
        except Exception as e:  # noqa: BLE001 — print what we have, then fail
            result[key] = {"error": f"{type(e).__name__}: {e}"}
            failed.append(key)
    result["failed"] = failed
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)

    headline = next(
        (r for r in rows if r.get("name") == "IMPALA@ref" and "tps" in r), None
    )
    if headline is None:
        return {**ZERO_HEADLINE, "failed": failed}
    out = {
        "metric": "learner FPS (IMPALA V-trace, batch 128 x seq 5)",
        "value": headline["tps"],
        "unit": "transitions/sec",
        "vs_baseline": round(headline["tps"] / REFERENCE_BASELINE_TPS, 2),
    }
    relay = last_relay_record()
    if relay is not None:
        # Surface the committed fan-in numbers (host-side, so never stale
        # w.r.t. the accelerator) alongside the learner headline.
        out["relay"] = relay
    out["device_kind"] = jax.devices()[0].device_kind
    if on_cpu:
        # Flag CPU numbers in the summary line itself so a reader can never
        # mistake host-CPU throughput for chip throughput.
        out["note"] = "CPU backend (no accelerator); matrix in " + out_path
    out["failed"] = failed
    return out


def run(warmup: int = 5, iters: int = 50) -> dict:
    """Back-compat single-workload entry (headline row only; same chained
    methodology as the run_all headline so the two entries agree)."""
    row = bench_one(
        "IMPALA@ref", dict(algo="IMPALA", **_REF, **_DISC), warmup, iters, 16
    )
    return {
        "metric": "learner FPS (IMPALA V-trace, batch 128 x seq 5)",
        "value": row["tps"],
        "unit": "transitions/sec",
        "vs_baseline": round(row["tps"] / REFERENCE_BASELINE_TPS, 2),
    }


ZERO_HEADLINE = {
    "metric": "learner FPS (IMPALA V-trace, batch 128 x seq 5)",
    "value": 0.0,
    "unit": "transitions/sec",
    "vs_baseline": 0.0,
}


# --------------------------------------------------------------- e2e feed
def _steady_tps(timer, name: str = "learner-throughput") -> float | None:
    """Steady-state transitions/sec from the service's windowed timer with
    the FIRST dispatch dropped: it carries the jit compile (seconds against
    sub-ms steps) and at e2e-bench dispatch counts it would dominate the
    window mean. Both feed variants pay the same compile, so dropping it
    from both keeps the comparison honest."""
    q = list(timer.throughput.get(name, ()))
    if len(timer.elapsed.get(name, ())) >= 2 and len(q) >= 2:
        q = q[1:]
    return sum(q) / len(q) if q else None


def e2e_learner_row(
    updates: int = 2048,
    chain: int = 16,
    feeders: int = 4,
    publish_interval: int = 256,
    prefetch: int = 2,
    model_port: int = 29890,
    batch_size: int = 128,
    seq_len: int = 5,
    hidden_size: int = 64,
) -> dict:
    """END-TO-END learner FPS through the REAL shm feed: feeder threads put
    windows into an OnPolicyStore while the production LearnerService
    consumes, assembles, places, and train-steps them — every batch crosses
    host shm -> device exactly as in a deployment (unlike the @ref rows'
    pre-placed device batches). ``prefetch`` selects the feed
    (``Config.learner_prefetch``): > 0 pipelines the data plane, 0 is the
    synchronous serial baseline. Shared by ``run_e2e_compare`` below and
    ``examples/run_tpu_e2e_learner.py``."""
    import threading

    from tpu_rl.config import Config
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.data.shm_ring import OnPolicyStore, alloc_handles
    from tpu_rl.runtime.learner_service import LearnerService
    from tpu_rl.types import BATCH_FIELDS

    cfg = Config.from_dict(
        dict(
            algo="IMPALA", batch_size=batch_size, seq_len=seq_len,
            hidden_size=hidden_size, obs_shape=(4,), action_space=2,
            learner_chain=chain, learner_prefetch=prefetch,
            loss_log_interval=10**9,
        )
    )
    layout = BatchLayout.from_config(cfg)
    handles = alloc_handles(layout, capacity=cfg.batch_size)

    # Pre-generated window pool: the feeders only memcpy, so the feed rate
    # measures the shm path, not RNG.
    rng = np.random.default_rng(0)
    pool = []
    for _ in range(64):
        w = {}
        for f in BATCH_FIELDS:
            shape = (layout.seq_len, layout.width(f))
            if f == "act":
                w[f] = rng.integers(0, 2, size=shape).astype(np.float32)
            elif f == "is_fir":
                a = np.zeros(shape, np.float32)
                a[0] = 1.0
                w[f] = a
            elif f == "log_prob":
                w[f] = np.full(shape, -0.7, np.float32)
            else:
                w[f] = rng.standard_normal(shape).astype(np.float32) * 0.1
        pool.append(w)

    stop = threading.Event()
    puts = [0] * feeders
    put_blocked = [0] * feeders
    # OnPolicyStore.put is single-writer; serialize feeders so N threads
    # emulate N producers funneling through one writer.
    put_lock = threading.Lock()

    def feed(k: int) -> None:
        store = OnPolicyStore(handles, layout)  # per-thread views
        i = k
        while not stop.is_set():
            with put_lock:
                ok = store.put(pool[i % len(pool)])
            if ok:
                puts[k] += 1
                i += 1
            else:
                put_blocked[k] += 1
                time.sleep(0)  # store full: learner is the bottleneck

    threads = [
        threading.Thread(target=feed, args=(k,), daemon=True)
        for k in range(feeders)
    ]
    for t in threads:
        t.start()

    svc = LearnerService(
        cfg, handles, model_port=model_port, stop_event=stop,
        max_updates=updates, publish_interval=publish_interval,
    )
    t0 = time.perf_counter()
    svc.run()
    elapsed = time.perf_counter() - t0
    stop.set()
    for t in threads:
        t.join(timeout=10)

    done = updates // max(1, chain) * max(1, chain)
    transitions = done * cfg.batch_size * cfg.seq_len
    total_puts = sum(puts)
    steady = _steady_tps(svc.timer)
    tmr = svc.timer
    ms = lambda name: (  # noqa: E731 — row-local shorthand
        round(tmr.mean_elapsed(name) * 1e3, 3)
        if tmr.mean_elapsed(name) is not None else None
    )
    depth = tmr.mean_gauge("learner-queue-depth")
    return dict(
        device_kind=jax.devices()[0].device_kind,
        feed="prefetch" if prefetch > 0 else "sync",
        prefetch_depth=prefetch,
        algo=cfg.algo, batch=cfg.batch_size, seq=cfg.seq_len,
        hidden=cfg.hidden_size, chain=chain, feeders=feeders,
        updates=done, seconds=round(elapsed, 2),
        e2e_learner_tps=round(transitions / elapsed, 1),
        e2e_learner_tps_steady=(
            round(steady, 1) if steady is not None else None
        ),
        queue_wait_ms=ms("learner-queue-wait-time"),
        batching_ms=ms("learner-batching-time"),
        step_ms=ms("learner-step-time"),
        queue_depth_mean=round(depth, 2) if depth is not None else None,
        feed_windows_per_s=round(total_puts / elapsed, 1),
        feed_tps=round(total_puts * cfg.seq_len / elapsed, 1),
        feed_blocked_ratio=round(
            sum(put_blocked) / max(1, sum(put_blocked) + total_puts), 3
        ),
    )


def run_e2e_compare(
    updates: int | None = None,
    chain: int | None = None,
    feeders: int = 4,
    out_path: str | None = None,
) -> dict:
    """Sync vs prefetched feed, same workload, one process: the A/B row for
    the pipelined data plane. With prefetch the per-dispatch critical path
    is queue-wait + step (batching overlaps the device), so
    ``queue_wait_ms`` << ``batching_ms`` is the overlap made visible, and
    ``speedup`` >= 1.0 is the acceptance bar. CPU-backend runs use a
    smaller budget and write ``bench_e2e_feed.cpu.json`` (never clobbering
    the on-chip record)."""
    on_cpu = jax.devices()[0].platform == "cpu"
    if updates is None:
        updates = 384 if on_cpu else 2048
    if chain is None:
        chain = 8 if on_cpu else 16
    if out_path is None:
        out_path = "bench_e2e_feed.cpu.json" if on_cpu else "bench_e2e_feed.json"
    rows = []
    for prefetch, port in ((0, 29890), (2, 29891)):
        row = e2e_learner_row(
            updates=updates, chain=chain, feeders=feeders,
            prefetch=prefetch, model_port=port,
        )
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    sync_row, pre_row = rows
    # Compare steady windowed rates (first/compile dispatch dropped on both
    # sides); fall back to wall-clock tps if a window is missing.
    a = pre_row["e2e_learner_tps_steady"] or pre_row["e2e_learner_tps"]
    b = sync_row["e2e_learner_tps_steady"] or sync_row["e2e_learner_tps"]
    result = {
        "metric": "e2e learner FPS, prefetched vs synchronous feed",
        "device_kind": jax.devices()[0].device_kind,
        "speedup": round(a / b, 3) if b else None,
        "prefetch_tps_steady": a,
        "sync_tps_steady": b,
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": rows,
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result


# ------------------------------------------------------------ acting A/B
def _bench_local_acting(cfg, family, params, n_envs: int, acts: int) -> float:
    """Acts/sec of one worker's local path: batched jitted forward + the
    host readback every tick pays (the worker materializes numpy actions to
    step envs). Env stepping itself is excluded on BOTH sides — this A/B
    isolates the acting path, ``examples/bench_worker_throughput.py`` owns
    the full loop."""
    act = jax.jit(family.act)
    rng = np.random.default_rng(0)
    obs = rng.standard_normal((n_envs, int(cfg.obs_shape[0]))).astype(
        np.float32
    )
    hw, cw = family.carry_widths
    h = jnp.zeros((n_envs, hw))
    c = jnp.zeros((n_envs, cw))
    key = jax.random.key(0)
    key, sub = jax.random.split(key)
    a, _logits, _lp, h, c = act(params, jnp.asarray(obs), h, c, sub)  # compile
    np.asarray(a)
    t0 = time.perf_counter()
    for _ in range(acts):
        key, sub = jax.random.split(key)
        a, logits, lp, h, c = act(params, jnp.asarray(obs), h, c, sub)
        np.asarray(a), np.asarray(logits), np.asarray(lp)
    dt = time.perf_counter() - t0
    return acts * n_envs / dt


def run_act_compare(
    clients: int | None = None,
    envs_per_client: int | None = None,
    acts: int | None = None,
    port: int = 29920,
    out_path: str | None = None,
) -> dict:
    """Local vs remote (SEED-style centralized) acting throughput, one
    process: N client threads with real ``InferenceClient`` DEALER sockets
    drive the production ``InferenceService`` ROUTER + padded-batch jitted
    act, against the same model acting locally. Reports the new
    ``inference-batch-size`` / ``inference-rtt`` / ``inference-step-time``
    timers alongside acts/sec on both sides.

    On one host the remote path pays the loopback RTT + codec per tick and
    usually loses; the number that matters for the SEED thesis is the
    server-side step time vs batch size (device amortization) and the RTT
    breakdown this emits — on a TPU deployment the same wire cost buys
    accelerator-grade acting for the whole fleet.

    Also emits fleet rows: the identical client load spread over a
    two-replica elastic fleet via ``FleetClient`` (p2c routing), hedge-off
    vs hedged, quantifying the scale-out win and the hedging premium."""
    import threading

    from tpu_rl.config import Config
    from tpu_rl.models.families import build_family
    from tpu_rl.runtime.inference_service import (
        InferenceClient,
        InferenceService,
    )
    from tpu_rl.utils.timer import ExecutionTimer

    on_cpu = jax.devices()[0].platform == "cpu"
    if clients is None:
        clients = 4
    if envs_per_client is None:
        envs_per_client = 16
    if acts is None:
        acts = 150 if on_cpu else 600
    if out_path is None:
        out_path = "bench_act.cpu.json" if on_cpu else "bench_act.json"

    cfg = Config.from_dict(
        dict(
            algo="IMPALA", obs_shape=(4,), action_space=2, hidden_size=64,
            worker_num_envs=envs_per_client, act_mode="remote",
            inference_batch=clients * envs_per_client,
            inference_flush_us=500, inference_timeout_ms=30_000,
        )
    )
    family = build_family(cfg)
    params = family.init_params(jax.random.key(0), seq_len=cfg.seq_len)

    local_aps = _bench_local_acting(
        cfg, family, params, envs_per_client, acts
    )

    svc = InferenceService(cfg, family, params, port=port, seed=0).start()
    try:
        assert svc.wait_ready(300.0) and svc.error is None, svc.error
        rtt_timer = ExecutionTimer(window=10_000)  # shared; deques are safe
        barrier = threading.Barrier(clients + 1)
        failures = [0] * clients

        def drive(k: int) -> None:
            cl = InferenceClient(
                cfg, "127.0.0.1", port, wid=k, timer=rtt_timer
            )
            try:
                rng = np.random.default_rng(k)
                obs = rng.standard_normal(
                    (envs_per_client, int(cfg.obs_shape[0]))
                ).astype(np.float32)
                first = np.ones(envs_per_client, np.float32)
                cl.act(obs, first)  # join + prime outside the timed region
                barrier.wait()
                first = np.zeros(envs_per_client, np.float32)
                for _ in range(acts):
                    if cl.act(obs, first) is None:
                        failures[k] += 1
            finally:
                cl.close()

        threads = [
            threading.Thread(target=drive, args=(k,), daemon=True)
            for k in range(clients)
        ]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        remote_aps = clients * acts * envs_per_client / dt

        tmr = svc.timer
        ms = lambda t, name: (  # noqa: E731 — row-local shorthand
            round(t.mean_elapsed(name) * 1e3, 3)
            if t.mean_elapsed(name) is not None else None
        )
        batch_mean = tmr.mean_gauge("inference-batch-size")
        result = {
            "metric": "batched acting throughput, local vs remote",
            "device_kind": jax.devices()[0].device_kind,
            "clients": clients,
            "envs_per_client": envs_per_client,
            "acts_per_client": acts,
            "local_acts_per_s": round(local_aps, 1),
            "remote_acts_per_s": round(remote_aps, 1),
            "remote_vs_local": round(remote_aps / local_aps, 3),
            "inference_rtt_ms": ms(rtt_timer, "inference-rtt"),
            "inference_step_ms": ms(tmr, "inference-step-time"),
            "inference_batch_mean": (
                round(batch_mean, 1) if batch_mean is not None else None
            ),
            "inference_batch_max": cfg.inference_batch,
            "flushes_full": svc.n_flush_full,
            "flushes_deadline": svc.n_flush_deadline,
            "client_failures": sum(failures),
            "recorded_at": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
            ),
        }
    finally:
        svc.close()

    # Fleet rows: the same client threads through the elastic-fleet path —
    # two continuous-batching replicas behind the power-of-two-choices
    # ``FleetClient``, once with hedging off (pure p2c routing) and once
    # with an aggressive hedge so the duplicate-send cost is visible. The
    # delta between ``fleet2_remote_acts_per_s`` and ``remote_acts_per_s``
    # is what a second replica buys on one host; ``fleet_hedge_overhead``
    # is the tail-latency insurance premium.
    from tpu_rl.fleet import FleetClient, InferenceReplica

    def _fleet_run(hedge_ms: int, base: int) -> tuple[float, int, int, int]:
        fcfg = cfg.replace(inference_hedge_ms=hedge_ms)
        svcs = [
            InferenceReplica(fcfg, family, params, port=base + i, seed=i)
            .start()
            for i in range(2)
        ]
        try:
            for s in svcs:
                assert s.wait_ready(300.0) and s.error is None, s.error
            endpoints = [("127.0.0.1", base + i) for i in range(2)]
            barrier = threading.Barrier(clients + 1)
            fails = [0] * clients
            hedges = [0] * clients
            dedups = [0] * clients

            def drive(k: int) -> None:
                cl = FleetClient(fcfg, endpoints, wid=k)
                try:
                    rng = np.random.default_rng(k)
                    obs = rng.standard_normal(
                        (envs_per_client, int(cfg.obs_shape[0]))
                    ).astype(np.float32)
                    first = np.ones(envs_per_client, np.float32)
                    cl.act(obs, first)  # join + prime outside timed region
                    barrier.wait()
                    first = np.zeros(envs_per_client, np.float32)
                    for _ in range(acts):
                        if cl.act(obs, first) is None:
                            fails[k] += 1
                    hedges[k] = cl.n_hedges
                    dedups[k] = cl.n_dedups
                finally:
                    cl.close()

            threads = [
                threading.Thread(target=drive, args=(k,), daemon=True)
                for k in range(clients)
            ]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            aps = clients * acts * envs_per_client / dt
            return aps, sum(hedges), sum(dedups), sum(fails)
        finally:
            for s in svcs:
                s.close()

    fleet_aps, _, _, fleet_fails = _fleet_run(0, port + 2)
    hedged_aps, n_hedges, n_dedups, hedged_fails = _fleet_run(1, port + 4)
    result.update(
        fleet_replicas=2,
        fleet2_remote_acts_per_s=round(fleet_aps, 1),
        fleet2_vs_remote=round(fleet_aps / remote_aps, 3),
        fleet_hedged_acts_per_s=round(hedged_aps, 1),
        fleet_hedge_overhead=round(1.0 - hedged_aps / fleet_aps, 3),
        fleet_hedges_fired=n_hedges,
        fleet_dedup_replies=n_dedups,
        fleet_client_failures=fleet_fails + hedged_fails,
    )
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), file=sys.stderr, flush=True)
    return result


# ------------------------------------------------------- serving fast path
def run_serving_fastpath(
    clients: int | None = None,
    envs_per_client: int | None = None,
    acts: int | None = None,
    port: int = 29930,
    out_path: str | None = None,
) -> dict:
    """Serving fast-path A/B ladder (ISSUE 16): the SAME closed-loop client
    load against the production ``InferenceService``, once per knob
    combination of the three composable layers —

    - ``inference_dtype``  f32 (PR 12 baseline) vs bf16 vs int8 serving
      params (per-tensor symmetric, dequantized inside the jitted step);
    - ``inference_buckets`` 0 (single ``pad_rows`` program — every flush
      pays the largest padded shape) vs a power-of-two ladder, where a
      flush dispatches the smallest covering pre-warmed program;
    - ``act_kernel`` xla vs the fused Pallas act step (TPU-only at run
      time; rows record ``kernel_active`` so a CPU capture can never be
      misread as a kernel number).

    The load is deliberately SMALL-FLUSH (default 2 clients x 4 envs = 8-row
    flushes against ``pad_rows`` 64): the over-padding the bucket ladder
    removes is exactly the PR 12 ``pad_rows = max(inference_batch,
    worker_num_envs)`` fixed cost. Per row: acts/s, client-observed p99 RTT,
    the post-warm recompile count (must stay 0 — the serving ratchet), the
    quantized param-tree bytes and the per-bucket flush split. Headline
    deltas: ``composed_speedup`` (bf16+buckets vs baseline acts/s) and
    ``composed_p99_ratio`` (tail parity)."""
    import tempfile
    import threading

    from tpu_rl.config import Config
    from tpu_rl.models.families import build_family
    from tpu_rl.runtime.inference_service import (
        InferenceClient,
        InferenceService,
    )

    on_cpu = jax.devices()[0].platform == "cpu"
    if clients is None:
        clients = 2
    if envs_per_client is None:
        envs_per_client = 4
    if acts is None:
        acts = 300 if on_cpu else 1000
    if out_path is None:
        out_path = "bench_serving.cpu.json" if on_cpu else "bench_serving.json"

    base = dict(
        # Wide torso + large padded batch: the serving shape where the
        # PR 12 fixed pad is real money — every 8-row flush below pays a
        # 256-row LSTM step unless a smaller bucket program covers it.
        algo="IMPALA", obs_shape=(4,), action_space=2, hidden_size=256,
        worker_num_envs=envs_per_client, act_mode="remote",
        inference_batch=256, inference_flush_us=500,
        inference_timeout_ms=30_000,
        # telemetry on: installs the per-bucket PerfTracker recompile
        # watches the ratchet column reads
        result_dir=tempfile.mkdtemp(prefix="bench-serving-"),
        telemetry_interval_s=3600.0,
    )
    cases = [
        ("baseline-f32", dict()),
        ("bf16", dict(inference_dtype="bf16")),
        ("buckets", dict(inference_buckets=8)),
        ("composed-bf16-buckets",
         dict(inference_dtype="bf16", inference_buckets=8)),
        ("int8-buckets",
         dict(inference_dtype="int8", inference_buckets=8)),
        ("pallas-composed",
         dict(inference_dtype="bf16", inference_buckets=8,
              act_kernel="pallas")),
    ]

    rows = []
    for i, (name, knobs) in enumerate(cases):
        cfg = Config.from_dict({**base, **knobs})
        family = build_family(cfg)
        params = family.init_params(jax.random.key(0), seq_len=cfg.seq_len)
        svc = InferenceService(
            cfg, family, params, port=port + i, seed=0
        ).start()
        try:
            assert svc.wait_ready(300.0) and svc.error is None, svc.error
            barrier = threading.Barrier(clients + 1)
            failures = [0] * clients
            lat: list[list[float]] = [[] for _ in range(clients)]

            def drive(k: int, _port: int = port + i) -> None:
                cl = InferenceClient(cfg, "127.0.0.1", _port, wid=k)
                try:
                    rng = np.random.default_rng(k)
                    obs = rng.standard_normal(
                        (envs_per_client, int(cfg.obs_shape[0]))
                    ).astype(np.float32)
                    first = np.ones(envs_per_client, np.float32)
                    cl.act(obs, first)  # join + prime outside timed region
                    barrier.wait()
                    first = np.zeros(envs_per_client, np.float32)
                    for _ in range(acts):
                        t0 = time.perf_counter()
                        if cl.act(obs, first) is None:
                            failures[k] += 1
                        lat[k].append(time.perf_counter() - t0)
                finally:
                    cl.close()

            threads = [
                threading.Thread(target=drive, args=(k,), daemon=True)
                for k in range(clients)
            ]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            all_lat = sorted(x for ks in lat for x in ks)
            p99 = all_lat[int(0.99 * (len(all_lat) - 1))] if all_lat else None
            rows.append({
                "name": name,
                "inference_dtype": cfg.inference_dtype,
                "inference_buckets": cfg.inference_buckets,
                "act_kernel": cfg.act_kernel,
                # the fused kernel only engages on a single-device TPU
                # backend; everywhere else make_act_fn falls back to the
                # XLA act so this row is a dispatch-overhead check on CPU
                "kernel_active": (
                    cfg.act_kernel == "pallas" and not on_cpu
                    and len(jax.devices()) == 1
                ),
                "acts_per_s": round(clients * acts * envs_per_client / dt, 1),
                "p99_ms": round(p99 * 1e3, 3) if p99 is not None else None,
                "recompiles": svc.recompiles,
                "param_bytes": svc.param_bytes,
                "bucket_flushes": {
                    str(k): v for k, v in sorted(svc.n_flush_bucket.items())
                },
                "client_failures": sum(failures),
            })
        finally:
            svc.close()

    by_name = {r["name"]: r for r in rows}
    base_row = by_name["baseline-f32"]
    comp_row = by_name["composed-bf16-buckets"]
    result = {
        "metric": "serving fast path A/B (dtype x buckets x kernel)",
        "device_kind": jax.devices()[0].device_kind,
        "clients": clients,
        "envs_per_client": envs_per_client,
        "acts_per_client": acts,
        "pad_rows": 256,
        "rows": rows,
        "composed_speedup": round(
            comp_row["acts_per_s"] / base_row["acts_per_s"], 3
        ),
        "composed_p99_ratio": (
            round(comp_row["p99_ms"] / base_row["p99_ms"], 3)
            if comp_row["p99_ms"] and base_row["p99_ms"] else None
        ),
        "recompiles_total": sum(r["recompiles"] for r in rows),
        "client_failures_total": sum(r["client_failures"] for r in rows),
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), file=sys.stderr, flush=True)
    return result


# ------------------------------------------------------------- relay A/B
def _relay_tick_payload(n_envs: int = 32, hidden: int = 64) -> dict:
    """One worker tick at the reference quantum (CartPole (4,)/2 discrete,
    hidden 64): the RolloutBatch frame shape the relay A/B is specified
    against (32-env reference tick)."""
    rng = np.random.default_rng(0)
    col = lambda w: rng.standard_normal((n_envs, w)).astype(np.float32)  # noqa: E731
    return dict(
        obs=col(4), act=col(1), rew=col(1), logits=col(2), log_prob=col(1),
        is_fir=col(1), hx=col(hidden), cx=col(hidden),
        id=[f"bench-ep{i:02d}" for i in range(n_envs)],
        done=np.zeros(n_envs, np.uint8),
    )


def relay_forward_row(mode: str, base_port: int, duration: float,
                      payload: dict, transport: str = "tcp",
                      paced: bool = False) -> dict:
    """Frames/s through a REAL Manager over real ZMQ: a producer PUB floods
    pre-encoded RolloutBatch frames at the manager's worker port while a
    sink SUB (bound where storage binds) counts what comes out the other
    side. The producer and sink are identical across modes — the only
    variable is the manager's per-frame work: peek+forward (raw) vs
    decode+re-encode (decode).

    ``transport="shm"`` re-plumbs the manager->storage hop exactly as
    ``Config.transport="shm"`` does in production: the manager publishes
    onto a shared-memory ring and the sink is the storage-side ``FanInSub``
    draining in native-validated batches — ISSUE 8's fast path. The
    worker->manager hop stays TCP in every row (workers may be remote).

    ``paced=True`` bounds the producer's in-flight window instead of
    flooding — on small hosts a flooding producer burns the core on frames
    the HWM then drops, understating the relay. The committed tcp rows keep
    the flooding producer so their numbers stay comparable across rounds."""
    import threading

    from tpu_rl.config import Config
    from tpu_rl.runtime.manager import Manager
    from tpu_rl.runtime.protocol import Protocol, encode
    from tpu_rl.runtime.transport import Pub, Sub, make_data_sub

    cfg = Config.from_dict(
        dict(algo="IMPALA", obs_shape=(4,), action_space=2, hidden_size=64,
             relay_mode=mode, transport=transport)
    )
    worker_port, learner_port = base_port, base_port + 1
    stop = threading.Event()
    m = Manager(cfg, worker_port, "127.0.0.1", learner_port, stop_event=stop)
    mt = threading.Thread(target=m.run, daemon=True)
    mt.start()
    if transport == "shm":
        sink = make_data_sub(cfg, "*", learner_port, bind=True)
    else:
        sink = Sub("*", learner_port, bind=True)
    pub = Pub("127.0.0.1", worker_port, bind=False)
    frame = encode(Protocol.RolloutBatch, payload)
    send_stop = threading.Event()
    sent = [0]
    settled = [0]  # paced mode: frames delivered or written off

    def produce() -> None:
        while not send_stop.is_set():
            if paced and sent[0] - settled[0] > 512:
                time.sleep(0.0002)
                continue
            pub.send_raw(frame)
            sent[0] += 1

    pt = threading.Thread(target=produce, daemon=True)
    pt.start()
    try:
        # Warm-up: wait for the first forwarded frame (slow-joiner windows on
        # both PUB hops) before opening the timed window.
        deadline = time.time() + 30
        primed = False
        while time.time() < deadline and not primed:
            primed = sink.recv_raw(timeout_ms=100) is not None
            settled[0] = sent[0]  # slow-joiner losses settle, window reopens
        if not primed:
            raise RuntimeError(f"relay ({mode}) never forwarded a frame")
        n = nbytes = 0
        t0 = time.perf_counter()
        if transport == "shm":
            # Storage's real consumption pattern on the shm hop: batch
            # drains (one native validate call per batch), not per-frame
            # polls — the tcp rows keep the committed per-frame loop so the
            # baseline number stays comparable across rounds.
            while (dt := time.perf_counter() - t0) < duration:
                k = 0
                for _, parts in sink.drain_raw(max_msgs=1024):
                    n += 1
                    k += 1
                    nbytes += len(parts[0]) + len(parts[1])
                settled[0] += k
                if k == 0:
                    time.sleep(0.0005)
        else:
            while (dt := time.perf_counter() - t0) < duration:
                got = sink.recv_raw(timeout_ms=20)
                if got is not None:
                    n += 1
                    settled[0] += 1
                    nbytes += len(got[1][0]) + len(got[1][1])
    finally:
        send_stop.set()
        pt.join(timeout=5)
        stop.set()
        mt.join(timeout=10)
        sink.close()
        pub.close()
    n_envs = len(payload["id"])
    return dict(
        mode=mode,
        transport=transport,
        paced=paced,
        frames_per_s=round(n / dt, 1),
        env_steps_per_s=round(n * n_envs / dt, 1),
        wire_mb_per_s=round(nbytes / dt / 1e6, 2),
        frames_forwarded=n,
        frames_sent=sent[0],
        manager_dropped=m.n_dropped,
        seconds=round(dt, 2),
    )


def ingest_row(mode: str, n_ticks: int, payload: dict) -> dict:
    """Env-steps/s through the REAL LearnerStorage ingest + flush (no
    sockets — frame decode costs the same in both modes and is measured by
    the relay row): push_tick + put_many (raw) vs split_rollout_batch +
    per-step push + per-window put (decode). The ReplayStore always accepts,
    so the row measures the assembler/store path, not backpressure."""
    from tpu_rl.config import Config
    from tpu_rl.data.assembler import RolloutAssembler
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.data.shm_ring import ReplayStore, alloc_handles
    from tpu_rl.runtime.protocol import Protocol
    from tpu_rl.runtime.storage import LearnerStorage

    cfg = Config.from_dict(
        dict(algo="SAC", obs_shape=(4,), action_space=2, hidden_size=64,
             buffer_size=4096, relay_mode=mode, rollout_lag_sec=1e9)
    )
    layout = BatchLayout.from_config(cfg)
    handles = alloc_handles(layout, cfg.buffer_size)
    store = ReplayStore(handles, layout)
    st = LearnerStorage(cfg, handles, 0)
    asm = RolloutAssembler(layout, lag_sec=cfg.rollout_lag_sec)
    n_envs = len(payload["id"])
    # warm-up pass (allocators, first window emit)
    for _ in range(layout.seq_len):
        st._ingest(Protocol.RolloutBatch, payload, asm)
    st._flush(asm, store)
    t0 = time.perf_counter()
    for _ in range(n_ticks):
        st._ingest(Protocol.RolloutBatch, payload, asm)
        st._flush(asm, store)
    dt = time.perf_counter() - t0
    return dict(
        mode=mode,
        ticks_per_s=round(n_ticks / dt, 1),
        env_steps_per_s=round(n_ticks * n_envs / dt, 1),
        windows=st.n_windows,
        seconds=round(dt, 2),
    )


def hop_row(transport: str, base_port: int, duration: float,
            payload: dict) -> dict:
    """The manager->storage hop in isolation (no Manager in the loop): a
    sender thread pushes pre-encoded frames the way the manager's forward
    loop does (``send_raw`` per frame, bounded in-flight window) while the
    storage-side sink drains in native-validated batches. This is the hop
    ISSUE 8 re-plumbs — the A/B that shows whether the fan-in edge itself
    is still the bottleneck: tcp = ZMQ PUB->SUB, shm = ring + FanInSub."""
    import threading

    from tpu_rl.runtime.protocol import Protocol, encode
    from tpu_rl.runtime.transport import FanInSub, Pub, ShmPub, Sub

    frame = encode(Protocol.RolloutBatch, payload)
    if transport == "shm":
        sink = FanInSub("*", base_port, bind=True)
        pub = ShmPub(base_port)
    else:
        sink = Sub("*", base_port, bind=True)
        pub = Pub("127.0.0.1", base_port, bind=False)
    stop = threading.Event()
    sent = [0]
    settled = [0]

    def produce() -> None:
        while not stop.is_set():
            if sent[0] - settled[0] > 512:
                time.sleep(0.0002)
                continue
            pub.send_raw(frame)
            sent[0] += 1

    pt = threading.Thread(target=produce, daemon=True)
    pt.start()
    try:
        deadline = time.time() + 30
        primed = False
        while time.time() < deadline and not primed:
            primed = sink.recv_raw(timeout_ms=100) is not None
            settled[0] = sent[0]
        if not primed:
            raise RuntimeError(f"hop ({transport}) never delivered a frame")
        n = nbytes = 0
        t0 = time.perf_counter()
        while (dt := time.perf_counter() - t0) < duration:
            k = 0
            for _, parts in sink.drain_raw(max_msgs=1024):
                n += 1
                k += 1
                nbytes += len(parts[0]) + len(parts[1])
            settled[0] += k
            if k == 0:
                time.sleep(0.0005)
    finally:
        stop.set()
        pt.join(timeout=5)
        sink.close()
        pub.close()
    n_envs = len(payload["id"])
    return dict(
        transport=transport,
        frames_per_s=round(n / dt, 1),
        env_steps_per_s=round(n * n_envs / dt, 1),
        wire_mb_per_s=round(nbytes / dt / 1e6, 2),
        frames_delivered=n,
        frames_sent=sent[0],
        seconds=round(dt, 2),
    )


def validate_batch_row(use_native: bool, grade: str, n_frames: int,
                       reps: int, payload: dict) -> dict:
    """Frame VALIDATION throughput, no sockets and no decode: one batched
    native ``tpurl_validate_batch[_crc]`` call vs the per-frame Python
    checks it replaces, over identical pre-encoded traced RolloutBatch
    frames. ``grade="peek"`` is the relay-edge check (header + trailer
    structure); ``grade="crc"`` adds the body crc32 the storage edge pays.
    Decompress+unpack run in Python on both paths in production, so they
    are excluded here — this row isolates exactly what the native call
    buys."""
    import zlib as _zlib

    from tpu_rl.runtime import native
    from tpu_rl.runtime.protocol import (
        _HEADER, MAX_PROTO, Protocol, TRACE_KINDS_MASK, encode,
        make_trace_id, pack_trace, peek,
    )

    mode = "native" if use_native else "python"
    if use_native and not native.available():
        return dict(mode=mode, grade=grade, error="native codec unavailable")
    trailer = pack_trace(1, 0, make_trace_id(1, 0), 0)
    frames = [encode(Protocol.RolloutBatch, payload, trace=trailer)
              for _ in range(n_frames)]

    def py_pass() -> int:
        ok = 0
        for parts in frames:
            try:
                peek(parts)
            except ValueError:
                continue
            if grade == "crc":
                crc = _HEADER.unpack_from(parts[1])[4]
                if _zlib.crc32(parts[1][_HEADER.size:]) & 0xFFFFFFFF != crc:
                    continue
            ok += 1
        return ok

    def native_pass() -> int:
        verdicts = native.validate_batch(
            frames, TRACE_KINDS_MASK, MAX_PROTO, check_crc=(grade == "crc")
        )
        return sum(1 for v in verdicts if v == 0)

    run_pass = native_pass if use_native else py_pass
    assert run_pass() == n_frames  # warm-up + sanity
    t0 = time.perf_counter()
    for _ in range(reps):
        run_pass()
    dt = time.perf_counter() - t0
    return dict(
        mode=mode,
        grade=grade,
        frames_per_s=round(n_frames * reps / dt, 1),
        batch=n_frames,
        reps=reps,
        seconds=round(dt, 3),
    )


def run_relay_compare(
    duration: float | None = None,
    ingest_ticks: int | None = None,
    n_envs: int = 32,
    base_port: int = 29940,
    out_path: str | None = None,
) -> dict:
    """Raw vs decode fan-in, both legs of ISSUE 3's A/B: the Manager relay
    (frames/s, real ZMQ) and the storage ingest (env-steps/s, real
    assembler + shm store) at the 32-env reference tick shape. Acceptance:
    raw >= 3x decode frames/s through the manager on CPU.

    ``TPU_RL_BENCH_RELAY_LIGHT=1`` is the CI smoke shape: short windows, no
    result file (committed numbers never flap with CI load), and a hard
    assert that raw sustains at least decode's frame rate."""
    light = bool(os.environ.get("TPU_RL_BENCH_RELAY_LIGHT"))
    if duration is None:
        duration = 1.0 if light else 4.0
    if ingest_ticks is None:
        ingest_ticks = 300 if light else 3000
    payload = _relay_tick_payload(n_envs)
    rows = []
    for i, mode in enumerate(("decode", "raw")):
        row = dict(
            relay=relay_forward_row(mode, base_port + 10 * i, duration, payload),
            ingest=ingest_row(mode, ingest_ticks, payload),
        )
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    dec, raw = rows
    # ISSUE 8 rows. (a) e2e through the real Manager with the shm
    # manager->storage hop + native batch drains at the sink (paced
    # producer: on small hosts the flooding producer starves the relay).
    shm = dict(relay=relay_forward_row(
        "raw", base_port + 20, duration, payload, transport="shm", paced=True
    ))
    rows.append(shm)
    print(json.dumps(shm), file=sys.stderr, flush=True)
    # (b) The manager->storage hop in isolation, tcp vs shm — the A/B the
    # acceptance bar is stated against (is the fan-in edge the bottleneck?).
    hops = {
        tr: hop_row(tr, base_port + 30 + 2 * j, duration, payload)
        for j, tr in enumerate(("tcp", "shm"))
    }
    rows.append(dict(hop=hops))
    print(json.dumps(hops), file=sys.stderr, flush=True)
    # (c) Native-vs-python frame validation, both grades, no sockets.
    v_reps = 20 if light else 200
    validate = {
        grade: {
            mode: validate_batch_row(mode == "native", grade, 256, v_reps,
                                     payload)
            for mode in ("native", "python")
        }
        for grade in ("peek", "crc")
    }
    rows.append(dict(validate=validate))
    print(json.dumps(validate), file=sys.stderr, flush=True)
    fps_speedup = (
        raw["relay"]["frames_per_s"] / dec["relay"]["frames_per_s"]
        if dec["relay"]["frames_per_s"] else None
    )
    ingest_speedup = (
        raw["ingest"]["env_steps_per_s"] / dec["ingest"]["env_steps_per_s"]
        if dec["ingest"]["env_steps_per_s"] else None
    )
    shm_speedup = (
        shm["relay"]["frames_per_s"] / raw["relay"]["frames_per_s"]
        if raw["relay"]["frames_per_s"] else None
    )
    hop_speedup = (
        hops["shm"]["frames_per_s"] / hops["tcp"]["frames_per_s"]
        if hops["tcp"]["frames_per_s"] else None
    )
    hop_vs_relay = (
        hops["shm"]["frames_per_s"] / raw["relay"]["frames_per_s"]
        if raw["relay"]["frames_per_s"] else None
    )

    def _v_speedup(grade: str):
        na = validate[grade]["native"].get("frames_per_s")
        py = validate[grade]["python"].get("frames_per_s")
        return round(na / py, 2) if na and py else None

    result = {
        "metric": "manager relay frames/s, raw vs decode",
        "n_envs": n_envs,
        "relay_frames_speedup": round(fps_speedup, 2) if fps_speedup else None,
        "ingest_env_steps_speedup": (
            round(ingest_speedup, 2) if ingest_speedup else None
        ),
        "raw_frames_per_s": raw["relay"]["frames_per_s"],
        "decode_frames_per_s": dec["relay"]["frames_per_s"],
        "raw_ingest_env_steps_per_s": raw["ingest"]["env_steps_per_s"],
        "decode_ingest_env_steps_per_s": dec["ingest"]["env_steps_per_s"],
        "shm_frames_per_s": shm["relay"]["frames_per_s"],
        "shm_vs_raw_speedup": round(shm_speedup, 2) if shm_speedup else None,
        "hop_tcp_frames_per_s": hops["tcp"]["frames_per_s"],
        "hop_shm_frames_per_s": hops["shm"]["frames_per_s"],
        "hop_shm_speedup": round(hop_speedup, 2) if hop_speedup else None,
        "hop_shm_vs_raw_relay": (
            round(hop_vs_relay, 2) if hop_vs_relay else None
        ),
        "validate_speedup": _v_speedup("crc"),
        "validate_peek_speedup": _v_speedup("peek"),
        "light": light,
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": rows,
    }
    if light:
        # CI smoke contract: direction only, never a committed number.
        assert raw["relay"]["frames_per_s"] >= dec["relay"]["frames_per_s"], (
            f"raw relay slower than decode: {result}"
        )
        assert shm["relay"]["frames_per_s"] > 0, (
            f"shm relay forwarded nothing: {result}"
        )
        assert hops["shm"]["frames_per_s"] > 0, (
            f"shm hop delivered nothing: {result}"
        )
        return result
    if out_path is None:
        on_cpu = jax.devices()[0].platform == "cpu"
        out_path = "bench_relay.cpu.json" if on_cpu else "bench_relay.json"
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result


# ----------------------------------------------------- colocated (Anakin) A/B
def colocated_row(
    updates: int,
    n_envs: int,
    warmup: int = 5,
    seq_len: int = 5,
    hidden_size: int = 64,
    algo: str = "IMPALA",
    env: str = "CartPole-v1",
) -> dict:
    """Steady-state transitions/s of the fused act->env.step->train program
    (``runtime/colocated.py``) at the given env-batch size. Drives the jitted
    program directly (no logging/telemetry in the loop) with the compile paid
    in ``warmup``, so the number is the same steady window the distributed
    rows report. CartPole's obs/action shape matches the e2e feed row's
    reference workload (obs 4, act 2), so the train-step quantum is identical
    at ``n_envs=128`` — the same-quantum comparison is apples-to-apples."""
    from tpu_rl.config import Config
    from tpu_rl.parallel.dp import replicate
    from tpu_rl.runtime.colocated import ColocatedLoop

    cfg = Config.from_dict(
        dict(
            env=env, env_mode="colocated", algo=algo,
            batch_size=n_envs, buffer_size=n_envs, seq_len=seq_len,
            hidden_size=hidden_size, loss_log_interval=10**9,
        )
    )
    loop = ColocatedLoop(cfg, seed=0)
    state = replicate(loop.state, loop.mesh)
    carry = loop.init_carry(jax.random.PRNGKey(1))
    stats = loop.init_stats()
    metrics = None

    def dispatch(i, state, carry, stats):
        k_roll, k_train = jax.random.split(jax.random.fold_in(loop._k_base, i))
        return loop.program(state, carry, stats, k_roll, k_train)

    for i in range(warmup):
        state, carry, stats, metrics = dispatch(i, state, carry, stats)
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for i in range(warmup, warmup + updates):
        state, carry, stats, metrics = dispatch(i, state, carry, stats)
    jax.block_until_ready(metrics)
    elapsed = time.perf_counter() - t0
    transitions = updates * n_envs * seq_len
    tps = transitions / elapsed
    # Topology honesty (ISSUE 18): a colocated number is meaningless without
    # the device count behind it — pod rows must be read per-device.
    n_dev = jax.device_count()
    return dict(
        device_kind=jax.devices()[0].device_kind,
        devices=n_dev,
        num_processes=jax.process_count(),
        mode="colocated", algo=algo, env=env,
        n_envs=n_envs, seq=seq_len, hidden=hidden_size,
        updates=updates, seconds=round(elapsed, 2),
        iter_ms=round(elapsed / updates * 1e3, 3),
        colocated_tps=round(tps, 1),
        tps_per_device=round(tps / n_dev, 1),
        updates_per_s=round(updates / elapsed, 1),
    )


def run_colocated_compare(
    updates: int | None = None,
    env_batches: tuple[int, ...] | None = None,
    out_path: str | None = None,
) -> dict:
    """Colocated (fused on-device act->step->train) vs distributed
    (storage->learner through the real shm feed, prefetched — the data
    plane's best configuration) at the reference workload (IMPALA, seq 5,
    hidden 64, obs 4 / act 2). Both sides report steady transitions/s with
    the compile dropped.

    The headline ``speedup`` is the SAME-QUANTUM ratio (128-env colocated
    batch vs the 128-window distributed batch); larger env batches are
    recorded as scale rows. Acceptance (ISSUE 7): >= 2x on CPU; on an
    accelerator the scale rows are where Anakin-style numbers (10M+ tps)
    should land. Note the comparison is generous to the distributed side:
    its feeders memcpy pre-generated windows (no acting, no env physics),
    while the colocated number includes both.

    ``TPU_RL_BENCH_COLOCATED_LIGHT=1`` is the `make ci` smoke shape: short
    runs, no result file, direction-only assert (colocated >= distributed).
    """
    on_cpu = jax.devices()[0].platform == "cpu"
    light = bool(os.environ.get("TPU_RL_BENCH_COLOCATED_LIGHT"))
    if updates is None:
        updates = 40 if light else (200 if on_cpu else 2048)
    if env_batches is None:
        env_batches = (128,) if light else ((128, 1024) if on_cpu else (128, 1024, 4096))
    dist_updates = 96 if light else (384 if on_cpu else 2048)
    dist_chain = 8 if on_cpu else 16
    dist = e2e_learner_row(
        updates=dist_updates, chain=dist_chain, feeders=4,
        prefetch=2, model_port=29895,
    )
    print(json.dumps(dist), file=sys.stderr, flush=True)
    coloc_rows = []
    for n_envs in env_batches:
        row = colocated_row(updates=updates, n_envs=n_envs, warmup=3 if light else 5)
        coloc_rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    dist_tps = dist["e2e_learner_tps_steady"] or dist["e2e_learner_tps"]
    same_quantum = next(
        (r for r in coloc_rows if r["n_envs"] == 128), coloc_rows[0]
    )
    best = max(coloc_rows, key=lambda r: r["colocated_tps"])
    result = {
        "metric": "colocated fused-loop vs distributed storage->learner, "
                  "transitions/s",
        "device_kind": jax.devices()[0].device_kind,
        "speedup": round(same_quantum["colocated_tps"] / dist_tps, 2)
        if dist_tps else None,
        "colocated_tps": same_quantum["colocated_tps"],
        "colocated_tps_best": best["colocated_tps"],
        "colocated_best_n_envs": best["n_envs"],
        "distributed_tps_steady": dist_tps,
        "light": light,
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": {"distributed": dist, "colocated": coloc_rows},
    }
    if light:
        # CI smoke contract: direction only, never a committed number.
        assert same_quantum["colocated_tps"] >= dist_tps, (
            f"colocated slower than distributed feed: {result}"
        )
        return result
    if out_path is None:
        out_path = "bench_colocated.cpu.json" if on_cpu else "bench_colocated.json"
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def _mh_colocated_child() -> None:
    """Virtual-host body for :func:`run_colocated_multihost`. Runs in a
    fresh process whose ``XLA_FLAGS`` (device count) and gloo coordinator
    params arrive via ``TPU_RL_BENCH_COLOCATED_MH_CHILD`` (a JSON dict) —
    both must be set before jax initializes, hence a subprocess, never a
    fork of this process. Prints one JSON row from the chief."""
    p = json.loads(os.environ["TPU_RL_BENCH_COLOCATED_MH_CHILD"])
    from tpu_rl.config import Config
    from tpu_rl.parallel.dp import replicate
    from tpu_rl.runtime.colocated import ColocatedLoop

    nhosts, ndev = int(p["nhosts"]), int(p["ndev"])
    mh = None
    if nhosts > 1:
        mh = {
            "coordinator": f"127.0.0.1:{p['port']}",
            "num_processes": nhosts,
            "process_id": int(p["pid"]),
        }
    cfg = Config.from_dict(
        dict(
            env="CartPole-v1", env_mode="colocated", algo="IMPALA",
            batch_size=int(p["n_envs"]), buffer_size=int(p["n_envs"]),
            seq_len=5, hidden_size=64, loss_log_interval=10**9,
            mesh_data=nhosts * ndev, multihost=mh,
        )
    )
    loop = ColocatedLoop(cfg, seed=0)
    state = replicate(loop.state, loop.mesh)
    carry = loop.init_carry(jax.random.PRNGKey(1))
    stats = loop.init_stats()
    updates, warmup = int(p["updates"]), int(p["warmup"])
    metrics = None
    for i in range(warmup + updates):
        if i == warmup:
            jax.block_until_ready(metrics)
            t0 = time.perf_counter()
        k_roll, k_train = jax.random.split(jax.random.fold_in(loop._k_base, i))
        state, carry, stats, metrics = loop.program(
            state, carry, stats, k_roll, k_train
        )
    jax.block_until_ready(metrics)
    elapsed = time.perf_counter() - t0
    if jax.process_index() == 0:
        tps = updates * int(p["n_envs"]) * 5 / elapsed
        n_dev = jax.device_count()
        print(json.dumps(dict(
            device_kind=jax.devices()[0].device_kind,
            num_processes=jax.process_count(), devices=n_dev,
            n_envs=int(p["n_envs"]), updates=updates,
            seconds=round(elapsed, 2),
            colocated_tps=round(tps, 1),
            tps_per_device=round(tps / n_dev, 1),
        )), flush=True)


def _mh_colocated_row(
    nhosts: int, ndev: int, envs_per_device: int, updates: int,
    warmup: int, port: int,
) -> dict:
    """One pod-Anakin scaling row: ``nhosts`` subprocess virtual hosts with
    ``ndev`` CPU devices each, SAME per-device env batch (the weak-scaling
    shape: global envs = envs_per_device x nhosts x ndev)."""
    import subprocess

    n_envs = envs_per_device * nhosts * ndev
    procs = []
    for pid in range(nhosts):
        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
        env["TPU_RL_BENCH_COLOCATED_MH_CHILD"] = json.dumps(dict(
            pid=pid, nhosts=nhosts, ndev=ndev, port=port,
            n_envs=n_envs, updates=updates, warmup=warmup,
        ))
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env,
        ))
    outs = [p.communicate(timeout=900)[0] for p in procs]
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (
            f"virtual host {pid}/{nhosts} rc={p.returncode}\n{out[-3000:]}"
        )
    row = json.loads(outs[0].strip().splitlines()[-1])
    row["envs_per_device"] = envs_per_device
    return row


def run_colocated_multihost(out_path: str | None = None) -> dict:
    """Pod-Anakin weak-scaling A/B (ISSUE 18): the fused colocated program
    on 1 vs 2 virtual hosts (subprocess ``jax.distributed`` + gloo, 1 CPU
    device per host) at the SAME per-device env batch. Ideal scaling is 2x
    global transitions/s; the acceptance bar (>= 1.8x) only applies where
    the hosts have real parallel hardware — the record keeps ``host_cores``
    and ``oversubscribed`` so a 1-core CI box's timesharing numbers can
    never be read as a scaling regression.

    ``TPU_RL_BENCH_COLOCATED_MH_LIGHT=1`` is the smoke shape: short
    windows, no result file.
    """
    on_cpu = jax.devices()[0].platform == "cpu"
    light = bool(os.environ.get("TPU_RL_BENCH_COLOCATED_MH_LIGHT"))
    updates = 20 if light else 120
    warmup = 3 if light else 5
    envs_per_device = 64
    ndev = 1
    rows = []
    for i, nhosts in enumerate((1, 2)):
        row = _mh_colocated_row(
            nhosts, ndev, envs_per_device, updates, warmup,
            port=29960 + 2 * i,
        )
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    host_cores = os.cpu_count() or 1
    total_devices = 2 * ndev
    oversubscribed = on_cpu and total_devices > host_cores
    scaling = round(rows[1]["colocated_tps"] / rows[0]["colocated_tps"], 2)
    result = {
        "metric": "pod-Anakin colocated weak scaling, 1 vs 2 virtual hosts, "
                  "transitions/s at fixed per-device env batch",
        "device_kind": rows[0]["device_kind"],
        "scaling_2x_vs_1x": scaling,
        "tps_1host": rows[0]["colocated_tps"],
        "tps_2host": rows[1]["colocated_tps"],
        "tps_per_device_1host": rows[0]["tps_per_device"],
        "tps_per_device_2host": rows[1]["tps_per_device"],
        "envs_per_device": envs_per_device,
        "host_cores": host_cores,
        "oversubscribed": oversubscribed,
        "light": light,
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": rows,
    }
    if not oversubscribed:
        # The real acceptance bar — only meaningful with parallel hardware.
        assert scaling >= 1.8, f"pod scaling below bar: {result}"
    if light:
        return result
    if out_path is None:
        out_path = (
            "bench_colocated_multihost.cpu.json" if on_cpu
            else "bench_colocated_multihost.json"
        )
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result


# ------------------------------------------- learning-dynamics diag A/B
def run_diag_compare(out_path: str | None = None) -> dict:
    """Cost of the learning-dynamics plane: the same chained train-step
    workload with ``Config.learn_diag`` on vs off, per algo family. The
    diag pytree is computed inside the already-dispatched update program
    from intermediates the losses materialize anyway (tpu_rl/obs/learn.py),
    so its marginal cost is a handful of row-reductions per update — the
    contract is <=2% step-time overhead on the reference quantum, enforced
    on-chip (``tests/test_bench_headline.py`` checks the committed record;
    CPU captures carry the numbers but a 1-core CI box's timer noise
    exceeds the bar, so the assertion is direction-only there).

    Each side runs ``repeats`` times and keeps the fastest step_ms (min is
    the standard noise-damping estimator for a deterministic workload —
    every slowdown source is additive). ``TPU_RL_BENCH_DIAG_LIGHT=1`` is
    the `make ci` smoke shape: tiny budget, direction asserted loosely,
    nothing written."""
    on_cpu = jax.devices()[0].platform == "cpu"
    light = bool(os.environ.get("TPU_RL_BENCH_DIAG_LIGHT"))
    if light:
        algos, warmup, iters, repeats = ["IMPALA"], 2, 4, 1
    elif on_cpu:
        # PPO (clip/KL channels), IMPALA (V-trace clip rates + ESS), SAC
        # (twin-critic + alpha/target-Q channels) cover every diag shape.
        algos, warmup, iters, repeats = ["IMPALA", "PPO", "SAC"], 3, 12, 2
    else:
        algos, warmup, iters, repeats = ["IMPALA", "PPO", "SAC"], 5, 50, 3
    chain = 16  # the headline dispatch shape (see WORKLOADS @ref rows)

    rows = []
    worst = None
    for algo in algos:
        sides = {}
        for diag_on in (True, False):
            best = None
            for _ in range(repeats):
                r = bench_one(
                    f"{algo}@ref{'+diag' if diag_on else ''}",
                    dict(algo=algo, **_REF, **_DISC, learn_diag=diag_on),
                    warmup, iters, chain,
                )
                if best is None or r["step_ms"] < best["step_ms"]:
                    best = r
            sides[diag_on] = best
        on_ms, off_ms = sides[True]["step_ms"], sides[False]["step_ms"]
        overhead = (on_ms / off_ms - 1.0) * 100.0 if off_ms else None
        row = {
            "algo": algo,
            "step_ms_diag_on": on_ms,
            "step_ms_diag_off": off_ms,
            "tps_diag_on": sides[True]["tps"],
            "tps_diag_off": sides[False]["tps"],
            "overhead_pct": round(overhead, 2) if overhead is not None else None,
        }
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
        if overhead is not None and (worst is None or overhead > worst):
            worst = overhead

    result = {
        "metric": "learn_diag step-time overhead, diag on vs off",
        "device_kind": jax.devices()[0].device_kind,
        "chain": chain,
        "repeats": repeats,
        "max_overhead_pct": round(worst, 2) if worst is not None else None,
        "contract_pct": 2.0,
        # The binding <=2% check runs on accelerator captures only; CPU
        # numbers are recorded with the flag so readers (and the schema
        # test) know which regime they are in.
        "contract_binding": not on_cpu,
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": rows,
    }
    if light:
        # ci smoke: diag must not be catastrophically expensive even under
        # timer noise (a real regression — e.g. a host sync sneaking into
        # the step — shows up as 2x, not 2%).
        assert worst is not None and worst < 50.0, result
        return result
    if not on_cpu:
        assert worst is not None and worst <= 2.0, (
            f"learn_diag overhead above the 2% contract: {result}"
        )
    if out_path is None:
        out_path = "bench_diag.cpu.json" if on_cpu else "bench_diag.json"
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result


# --------------------------------------------------- run-history overhead
def run_history_compare(out_path: str | None = None) -> dict:
    """Cost of the run-history plane on the exporter cadence: a synthetic
    fleet's export tick (ingest every worker snapshot + JsonExporter
    write) with ``TimeSeriesStore.record`` appended vs without. The
    contract is the plane consumes <=2% of the exporter cadence budget
    (``telemetry_interval_s`` wall seconds per tick) — the record call is
    one flatten + one jsonl line, so the margin is wide even on a 1-core
    CI box, and the assertion binds on every non-light capture.

    The plane's OFF cost is pinned separately with tracemalloc: the hot
    path with no store is ONE ``is None`` check, and the bench asserts
    that loop allocates zero bytes (``off_path_alloc_bytes``).

    ``TPU_RL_BENCH_HISTORY_LIGHT=1`` is the `make ci` smoke shape: tiny
    budget, loose direction assert, nothing written."""
    import shutil
    import tempfile
    import tracemalloc

    from tpu_rl.obs import JsonExporter, TelemetryAggregator, TimeSeriesStore
    from tpu_rl.obs.registry import MetricsRegistry

    light = bool(os.environ.get("TPU_RL_BENCH_HISTORY_LIGHT"))
    workers, ticks, repeats = (2, 20, 1) if light else (8, 200, 3)
    interval_s = 2.0  # the repo-default exporter cadence the contract is
    # measured against (Config.telemetry_interval_s)

    def _fleet():
        regs = []
        for wid in range(workers):
            reg = MetricsRegistry(
                role="worker", labels={"wid": str(wid)}, pid=10_000 + wid
            )
            regs.append(reg)
        return regs

    def _tick(regs, agg, exporter, store, seq, t_wall):
        for wid, reg in enumerate(regs):
            reg.gauge("frame-rate").set(50.0 + seq % 7 + wid)
            reg.counter("frames").set_total(float(100 * seq + wid))
            reg.histogram("rtt-ms").observe(1.0 + (seq % 5) * 0.5)
            agg.ingest(reg.snapshot())
        exporter.maybe_export(now=float(seq))  # interval 0: always exports
        if store is not None:
            store.record(agg, now=t_wall)

    rows = []
    record_ms_best = None
    for _ in range(repeats):
        sides = {}
        for history_on in (True, False):
            tmp = tempfile.mkdtemp(prefix="bench_history_")
            try:
                regs = _fleet()
                agg = TelemetryAggregator()
                exporter = JsonExporter(
                    agg, os.path.join(tmp, "telemetry.json"), interval_s=0.0
                )
                store = (
                    TimeSeriesStore(
                        os.path.join(tmp, "history"),
                        chunk_s=60.0, retention_s=240.0,
                    )
                    if history_on else None
                )
                _tick(regs, agg, exporter, store, 0, 0.0)  # warm caches
                t0 = time.perf_counter()
                for seq in range(1, ticks + 1):
                    # wall clock advances one cadence per tick, so chunk
                    # rotation AND retention GC run inside the timed loop.
                    _tick(regs, agg, exporter, store, seq, seq * interval_s)
                elapsed_ms = (time.perf_counter() - t0) * 1e3
                sides[history_on] = elapsed_ms / ticks
                if store is not None:
                    store.close()
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
        record_ms = max(0.0, sides[True] - sides[False])
        row = {
            "tick_ms_on": round(sides[True], 4),
            "tick_ms_off": round(sides[False], 4),
            "record_ms": round(record_ms, 4),
        }
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
        if record_ms_best is None or record_ms < record_ms_best:
            record_ms_best = record_ms

    # The plane-off pin: the per-tick hook reduces to `store is not None`,
    # and that loop must allocate nothing.
    gate = None
    spins = (None,) * 10_000  # pre-built so the loop variable never allocates
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for _ in spins:
        if gate is not None:
            gate.record(None)
    off_path_alloc = tracemalloc.get_traced_memory()[0] - base
    tracemalloc.stop()

    overhead_pct = record_ms_best / (interval_s * 1e3) * 100.0
    result = {
        "metric": "run-history record overhead per exporter tick, "
                  "history on vs off",
        "device_kind": jax.devices()[0].device_kind,
        "workers": workers,
        "ticks": ticks,
        "repeats": repeats,
        "interval_s": interval_s,
        "record_ms": round(record_ms_best, 4),
        "overhead_pct_of_cadence": round(overhead_pct, 4),
        "contract_pct": 2.0,
        # Unlike the chip benches, this is a host-side budget measured
        # against a 2000ms cadence — the bar binds on every capture.
        "contract_binding": True,
        "off_path_alloc_bytes": int(off_path_alloc),
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": rows,
    }
    assert off_path_alloc == 0, (
        f"history-off hot path allocated {off_path_alloc} bytes: {result}"
    )
    if light:
        # ci smoke: a catastrophic regression (a sync/fsync per append)
        # shows up as 10x the budget, not a timer-noise wiggle.
        assert overhead_pct < 20.0, result
        return result
    assert overhead_pct <= 2.0, (
        f"history record above the 2% cadence contract: {result}"
    )
    if out_path is None:
        on_cpu = jax.devices()[0].platform == "cpu"
        out_path = "bench_history.cpu.json" if on_cpu else "bench_history.json"
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def last_relay_record(path: str | None = None) -> dict | None:
    """Summary of the newest committed non-light relay A/B
    (``bench_relay[.cpu].json``), so the run_all summary line surfaces the
    fan-in numbers (raw vs decode, shm hop, native validation) without
    re-running the relay harness every time."""
    here = os.path.dirname(os.path.abspath(__file__))
    paths = [path] if path else [
        os.path.join(here, "bench_relay.json"),
        os.path.join(here, "bench_relay.cpu.json"),
    ]
    for p in paths:
        try:
            with open(p) as f:
                rec = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if rec.get("light"):
            continue  # CI smoke shapes are direction-only, not numbers
        return {
            k: rec.get(k)
            for k in (
                "raw_frames_per_s", "decode_frames_per_s",
                "relay_frames_speedup", "shm_frames_per_s",
                "shm_vs_raw_speedup", "hop_shm_frames_per_s",
                "hop_shm_speedup", "hop_shm_vs_raw_relay",
                "validate_speedup", "validate_peek_speedup", "recorded_at",
            )
        }
    return None


if __name__ == "__main__":
    from tpu_rl.utils.platform import enable_compile_cache

    enable_compile_cache()
    if os.environ.get("TPU_RL_BENCH_COLOCATED_MH_CHILD"):
        # Virtual-host body spawned by run_colocated_multihost — must be
        # dispatched before anything queries devices (its XLA_FLAGS device
        # count and distributed-runtime params came in via the environment).
        _mh_colocated_child()
        sys.exit(0)
    if os.environ.get("TPU_RL_BENCH_COLOCATED_MH"):
        # Pod-Anakin scaling A/B: the fused colocated program on 1 vs 2
        # virtual hosts at the same per-device env batch (ISSUE 18).
        # TPU_RL_BENCH_COLOCATED_MH_LIGHT=1 is the smoke shape.
        print(json.dumps(run_colocated_multihost()))
        sys.exit(0)
    if os.environ.get("TPU_RL_BENCH_COLOCATED"):
        # Colocated (Anakin) A/B mode: fused on-device act->step->train vs
        # the distributed storage->learner feed, on whatever backend jax
        # resolved. TPU_RL_BENCH_COLOCATED_LIGHT=1 is the `make ci` smoke
        # shape. See also examples/bench_colocated.py for the CLI.
        print(json.dumps(run_colocated_compare()))
        sys.exit(0)
    if os.environ.get("TPU_RL_BENCH_RELAY"):
        # Relay/ingest A/B mode: zero-copy raw fan-in vs the decode baseline
        # through the real Manager + LearnerStorage (host-side; no
        # accelerator involved). TPU_RL_BENCH_RELAY_LIGHT=1 is the `make ci`
        # smoke shape. See also examples/bench_relay.py for the CLI.
        print(json.dumps(run_relay_compare()))
        sys.exit(0)
    if os.environ.get("TPU_RL_BENCH_ACT"):
        # Acting A/B mode: local jitted acting vs the centralized inference
        # service (SEED-style remote acting) with real DEALER/ROUTER
        # round-trips, on whatever backend jax resolved. See also
        # examples/bench_remote_acting.py for the parameterized CLI.
        print(json.dumps(run_act_compare()))
        sys.exit(0)
    if os.environ.get("TPU_RL_BENCH_SERVING"):
        # Serving fast-path A/B mode (ISSUE 16): the quantized-dtype x
        # bucket-ladder x act-kernel matrix against the production
        # InferenceService, small-flush load vs the padded baseline.
        print(json.dumps(run_serving_fastpath()))
        sys.exit(0)
    if os.environ.get("TPU_RL_BENCH_DIAG"):
        # Learning-dynamics diag A/B (ISSUE 19): the chained train step with
        # Config.learn_diag on vs off — pins the <=2% step-time overhead
        # contract for the in-jit diagnostics. TPU_RL_BENCH_DIAG_LIGHT=1 is
        # the `make ci` smoke shape.
        print(json.dumps(run_diag_compare()))
        sys.exit(0)
    if os.environ.get("TPU_RL_BENCH_HISTORY"):
        # Run-history overhead A/B (ISSUE 20): the exporter tick with the
        # TimeSeriesStore recording vs without — pins the <=2%-of-cadence
        # record budget and the zero-alloc plane-off hot path.
        # TPU_RL_BENCH_HISTORY_LIGHT=1 is the `make ci` smoke shape.
        print(json.dumps(run_history_compare()))
        sys.exit(0)
    if os.environ.get("TPU_RL_BENCH_E2E"):
        # e2e feed A/B mode: sync vs prefetched LearnerService through the
        # real shm path, on whatever backend jax resolved (set
        # JAX_PLATFORMS=cpu for a host run). Separate from the step-level
        # matrix below: this measures the data plane, that measures the chip.
        print(json.dumps(run_e2e_compare()))
        sys.exit(0)
    out = run_all()
    print(json.dumps(out))
    sys.exit(1 if out["failed"] else 0)
