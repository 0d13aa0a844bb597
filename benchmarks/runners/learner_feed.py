"""The production ``LearnerService`` in this process, fed through the real
shared-memory store by a feeder thread with windows made from the seed.

No children: this process owns the chip(s). The feeder cycles a pool of
distinct windows into ``OnPolicyStore`` as fast as the store takes them, so the
learner's own feed (consume -> assemble -> H2D, ``data/prefetch.py``) runs as in
a deployment and never starves. A watcher thread follows ``learn.jsonl``, lets
the window pass and sets the stop event. A traced run uses the learner's own
profiler window (``Config.profile_dir / profile_start / profile_steps``).
"""

from __future__ import annotations

import contextlib
import math
import os
import re
import shutil
import tempfile
import threading
import time

from benchmarks import harness, parity, traffic


class CompileCount:
    """Backend compilations (cache retrievals included) of this process."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def peak_bytes(device) -> int:
    """Peak HBM use of one chip. This runtime keeps two books: live buffers
    (``peak_bytes_in_use``: parameters, optimizer state, batches, snapshots)
    and the scratch space reserved for running programs
    (``peak_bytes_reserved``: activations and temporaries, 7.4 GB of this
    cell's 7.9). Both peak while an update runs, so the peak is their sum."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def warm_snapshots(cfg, seed: int) -> None:
    """The checkpointer and the publisher snapshot the train state leaf by
    leaf (``jnp.copy``) and read the PRNG key's data, each a small program of
    its own on first use — and the first checkpoint falls inside the window.
    Run them once here, so that they are set-up like every other shape."""
    import jax
    import jax.numpy as jnp

    from tpu_rl.algos.registry import get_algo

    _, state, _ = get_algo(cfg.algo).build(cfg, jax.random.key(seed))
    if cfg.mesh_data > 1:
        from tpu_rl.parallel.dp import replicate
        from tpu_rl.parallel.mesh import make_mesh

        placed = replicate(state, make_mesh(cfg.mesh_data))
    else:
        placed = jax.device_put(state, jax.devices()[0])
    # A step's outputs are committed to their devices, a fresh state is not:
    # the two are different programs to jit.
    for tree in (state, placed):
        jax.block_until_ready(jax.tree.map(jnp.copy, tree))
    jax.block_until_ready(jax.random.key_data(jax.random.key(seed)))


def run(spec: harness.Spec) -> harness.Run:
    import jax

    from tpu_rl.config import Config
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.data.shm_ring import OnPolicyStore, alloc_handles
    from tpu_rl.runtime.learner_service import LearnerService
    from tpu_rl.types import BATCH_FIELDS
    from tpu_rl.utils.platform import enable_compile_cache

    phases = {"imports": time.monotonic() - spec.t_start}  # where set-up goes
    enable_compile_cache()
    devices = jax.devices()
    harness.check_device(devices[0].platform, len(devices), spec.chips)
    phases["reach_chip"] = time.monotonic() - spec.t_start

    work = tempfile.mkdtemp(prefix="bench-learner-")
    t = spec.traffic
    params = dict(spec.params, result_dir=work, model_dir=os.path.join(work, "models"))
    if spec.trace:
        params.update(
            profile_dir=os.path.join(work, "prof"),
            profile_start=int(t["trace"]["start_update"]),
            profile_steps=int(t["trace"]["updates"]),
        )
    cfg = Config.from_dict(params)
    layout = BatchLayout.from_config(cfg)
    widths = {f: layout.width(f) for f in BATCH_FIELDS}
    pool = traffic.make_windows(
        widths, cfg.seq_len, cfg.action_space, t["windows"], spec.seed
    )
    verdict = parity.check(spec.params, spec.config["parity"], spec.seed)
    phases["parity"] = time.monotonic() - spec.t_start
    warm_snapshots(cfg, spec.seed)
    phases["warm_snapshots"] = time.monotonic() - spec.t_start

    handles = alloc_handles(layout, capacity=cfg.batch_size)
    stop = threading.Event()
    compiles = CompileCount()
    result: dict = {}

    def feed() -> None:
        store = OnPolicyStore(handles, layout)
        i = 0
        while not stop.is_set():
            n = store.put_many([pool[(i + k) % len(pool)] for k in range(8)])
            i += n
            if n < 8:
                time.sleep(0.001)  # store full: the learner has yet to consume

    def watch() -> None:
        tail = harness.LearnTail(os.path.join(work, "learn.jsonl"))
        try:
            result["window"] = harness.measure(
                tail, int(t["warmup_pairs"]), spec.seconds,
                alive=lambda: not stop.is_set(),
                warmup_timeout_s=float(t["warmup_timeout_s"]),
                on_start=lambda: result.update(compiles_before=compiles.n),
            )
            result["recompiles"] = compiles.n - result["compiles_before"]
            result["first_line"] = tail.rows[0].mono
        except Exception as e:  # noqa: BLE001 — re-raised by the main thread
            result["error"] = e
        finally:
            tail.close()
            stop.set()

    svc = LearnerService(
        cfg, handles, model_port=harness.free_port_block(), stop_event=stop,
        publish_interval=int(spec.config["publish_interval"]), seed=spec.seed,
    )
    threads = [
        threading.Thread(target=feed, name="bench-feed", daemon=True),
        threading.Thread(target=watch, name="bench-watch", daemon=True),
    ]
    log_path = os.path.join(work, "learner.log")
    try:
        for th in threads:
            th.start()
        with open(log_path, "w") as log, contextlib.redirect_stdout(log):
            svc.run()
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=30)
    try:
        if "error" in result:
            raise result["error"]
        if "window" not in result:
            raise harness.RunFailed("the learner returned before its window")
        with open(log_path) as f:
            losses = [float(x) for x in re.findall(r"  loss (\S+)", f.read())]
        used = devices[: cfg.mesh_data]
        reduced = None
        if spec.trace:
            from benchmarks import trace

            reduced = trace.load(trace.first_xplane(os.path.join(work, "prof")))
        return harness.Run(
            spec=spec,
            window=result["window"],
            transitions_per_update=cfg.batch_size * cfg.seq_len,
            bytes_per_update=layout.traj_floats * 4 * cfg.batch_size,
            device={
                "platform": devices[0].platform,
                "kind": devices[0].device_kind,
                "count": len(devices),
                "memory_peak_bytes": max(peak_bytes(d) for d in used),
            },
            parity=verdict,
            losses_finite=bool(losses) and all(math.isfinite(x) for x in losses),
            failed_updates=int(svc.n_nonfinite_updates + svc.n_rollbacks),
            recompiles=result["recompiles"],
            paths=harness.load_json(os.path.join(work, "backend-learner.json")),
            timers={
                name: svc.timer.mean_elapsed(name)
                for name in ("learner-queue-wait-time", "learner-batching-time",
                             "learner-step-time")
            },
            trace=reduced,
            notes={"window": {
                "setup_phases_s": phases,
                "first_line_s": result["first_line"] - spec.t_start,
            }},
        )
    finally:
        if spec.artifacts:
            shutil.copytree(
                work, spec.artifacts, dirs_exist_ok=True,
                ignore=shutil.ignore_patterns("models"),
            )
        shutil.rmtree(work, ignore_errors=True)
