"""Sebulba-mode driver: split actor/learner device groups + bounded queue.

Podracer/Sebulba (PAPERS.md, arxiv 2104.06272) splits one host's devices
into two groups instead of fusing everything into one dispatch the way
Anakin does: a dedicated ACTOR group runs the jitted act->env.step rollout
program while the remaining LEARNER group runs ``train_step``, and the two
overlap in wall time. The seam between them is a bounded queue of
device-resident :class:`~tpu_rl.types.Batch` slots:

    actor thread                          learner thread (main)
    ------------                          ---------------------
    rollout on act_mesh                   train_step on mesh
    device_put -> learner group   ──►     BoundedPipe.get (queue-wait)
    BoundedPipe.put (queue-wait)          fresh act params -> actor group

Queue protocol (``BoundedPipe``): ``Config.sebulba_queue`` slots (2 =
double buffering, 3 = triple). A full queue blocks the actor — that wait is
BACKPRESSURE and lands in the actor ledger's existing ``queue-wait``
bucket; an empty queue blocks the learner — actor-bound, same bucket on
the learner ledger. The queue holds batches already transferred to the
learner group (the ``jax.device_put`` reshard is actor-lane time, ``h2d``
bucket), so depth bounds BOTH learner-group staging memory and policy
staleness: a batch can be at most ``depth + 1`` updates stale.

Parameter feedback is latest-wins: after every update the learner reshards
``act_params(state)`` onto the actor group and swaps it into a slot the
actor reads at rollout start — no handshake, the actor never waits for
params.

Durability is inherited from :class:`ColocatedLoop` unchanged: two-phase
commits + newest-committed resume with a run-epoch bump, stateless
``fold_in`` key streams on both lanes (actor keys are derived from the
produced-batch index, so a resumed run replays the unbroken run's stream).

Telemetry: one goodput ledger per lane thread (``sebulba-actor`` /
``sebulba-learner`` roles — the ledger rule is one ledger per loop THREAD),
plus queue-depth gauges. Both lanes' compute ratios being simultaneously
nonzero in one snapshot window is the "acting overlaps training" acceptance
signal (``tests/test_sebulba.py``, ``examples/sebulba_smoke.py``).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp

from tpu_rl.config import Config
from tpu_rl.parallel.mesh import (
    batch_sharding,
    check_divisible,
    make_mesh,
    replicated,
)
from tpu_rl.runtime.colocated import ColocatedLoop, act_params
from tpu_rl.utils.timer import ExecutionTimer


def split_local_devices(n_act: int) -> tuple[list, list]:
    """Partition THIS process's devices into (actor, learner) groups:
    actors take the first ``n_act`` local devices, the learner the rest.
    Raises with the config knob's name when the split does not partition
    the local device count into two non-empty groups (the check needs
    ``jax.local_device_count()``, so it lives here, not in
    ``Config.validate`` — config never imports jax)."""
    local = jax.local_devices()
    if not 0 < n_act < len(local):
        raise ValueError(
            f"sebulba_split={n_act} must partition jax.local_device_count()"
            f"={len(local)} into two non-empty groups (actor devices "
            f"[0, split), learner devices [split, n))"
        )
    return local[:n_act], local[n_act:]


class BoundedPipe:
    """Bounded handoff of device-resident items between the two lanes.

    A thin ``queue.Queue`` wrapper that (a) attributes the caller's
    blocking time to its goodput ledger's ``queue-wait`` bucket — the
    backpressure signal — and (b) tracks the depth high-watermark so tests
    and telemetry can pin "bounded, never past ``depth``". Waits poll in
    ``poll_s`` slices so a stop event always unsticks both lanes (no
    deadlock on shutdown regardless of which side quit first)."""

    __slots__ = ("_q", "depth", "peak_depth", "_peak_lock")

    def __init__(self, depth: int):
        self._q = queue.Queue(maxsize=int(depth))
        self.depth = int(depth)
        self.peak_depth = 0
        self._peak_lock = threading.Lock()

    def qsize(self) -> int:
        return self._q.qsize()

    def put(self, item, ledger=None, stop=None, poll_s: float = 0.05) -> bool:
        """Enqueue; block while full (backpressure). False = stop was set
        before a slot opened, and the item was NOT enqueued."""
        t0 = time.perf_counter()
        ok = False
        while True:
            try:
                self._q.put(item, timeout=poll_s)
                ok = True
                break
            except queue.Full:
                if stop is not None and stop.is_set():
                    break
        if ledger is not None:
            from tpu_rl.obs.goodput import QUEUE_WAIT

            ledger.add(QUEUE_WAIT, time.perf_counter() - t0)
        if ok:
            with self._peak_lock:
                depth = self._q.qsize()
                if depth > self.peak_depth:
                    self.peak_depth = depth
        return ok

    def get(self, ledger=None, stop=None, poll_s: float = 0.05):
        """Dequeue; block while empty. None = stop was set while empty."""
        t0 = time.perf_counter()
        item = None
        while True:
            try:
                item = self._q.get(timeout=poll_s)
                break
            except queue.Empty:
                if stop is not None and stop.is_set():
                    break
        if ledger is not None:
            from tpu_rl.obs.goodput import QUEUE_WAIT

            ledger.add(QUEUE_WAIT, time.perf_counter() - t0)
        return item


class SebulbaLoop(ColocatedLoop):
    """Sebulba split of the colocated plane: same envs, same algo build,
    same checkpoint/resume semantics as :class:`ColocatedLoop`, different
    topology — ``cfg.sebulba_split`` local devices act, the rest train,
    and :meth:`run` drives the two lanes concurrently through a
    :class:`BoundedPipe` instead of one fused dispatch."""

    role = "sebulba"

    # ---------------------------------------------------------- topology hooks
    def _build_meshes(self) -> None:
        if jax.process_count() > 1:
            raise ValueError(
                "sebulba_split is a per-host (single-process) split; "
                "multihost pod scaling uses the fused Anakin path "
                "(Config.multihost without sebulba_split)"
            )
        acts, learns = split_local_devices(self.cfg.sebulba_split)
        self.act_mesh = make_mesh(devices=acts)
        self.mesh = make_mesh(devices=learns)
        check_divisible(self.cfg.batch_size, self.act_mesh)
        check_divisible(self.cfg.batch_size, self.mesh)

    def _compile(self) -> None:
        rs_l, bs_l = replicated(self.mesh), batch_sharding(self.mesh)
        rs_a, bs_a = replicated(self.act_mesh), batch_sharding(self.act_mesh)
        self._rs, self._bs = rs_l, bs_l
        self._act_rs, self._act_bs = rs_a, bs_a
        # Actor-lane program: rollout + on-device episode stats, everything
        # resident on the actor group. Carry is donated (it never leaves
        # the lane); stats are NOT — the live handle rides the queue to the
        # learner for log-interval reads, so its buffer must survive the
        # next dispatch.
        self.rollout = jax.jit(
            self._sebulba_rollout,
            in_shardings=(rs_a, bs_a, rs_a, rs_a),
            out_shardings=(bs_a, rs_a, bs_a),
            donate_argnums=(1,),
        )
        # Learner-lane program: the same pure train_step the fused program
        # embeds, compiled alone over the learner group.
        self.train = jax.jit(
            self._train_body,
            in_shardings=(rs_l, bs_l, rs_l),
            out_shardings=(rs_l, rs_l),
            donate_argnums=(0,),
        )
        # No fused `program` in this mode: ColocatedLoop.program users
        # (assembler-parity tests) run the Anakin class.
        self.program = None

    # -------------------------------------------------------------- jit bodies
    def _sebulba_rollout(self, params, carry, stats, key):
        from tpu_rl.models import cells

        prev = cells._DATA_MESH
        cells.set_data_mesh(self.act_mesh)
        try:
            carry, batch, done, ep_ret = self._rollout_body(params, carry, key)
        finally:
            cells.set_data_mesh(prev)
        import jax.numpy as jnp

        stats = {
            "episodes": stats["episodes"] + done.sum(dtype=jnp.int32),
            "ret_sum": stats["ret_sum"] + ep_ret.sum(),
        }
        return carry, stats, batch

    def _train_body(self, state, batch, key):
        from tpu_rl.models import cells

        prev = cells._DATA_MESH
        cells.set_data_mesh(self.mesh)
        try:
            return self._train_step(state, batch, key)
        finally:
            cells.set_data_mesh(prev)

    # ---------------------------------------------------------------- telemetry
    def _setup_telemetry(self) -> None:
        self._pipe = None
        super()._setup_telemetry()
        self.ledger_actor = None
        if self.ledger is not None:
            from tpu_rl.obs import GoodputLedger

            # One ledger per lane THREAD (the ledger rule): re-role the
            # inherited main-lane ledger as the learner's, add the actor's.
            self.ledger = GoodputLedger("sebulba-learner")
            self.ledger_actor = GoodputLedger("sebulba-actor")

    def _ledgers(self) -> list:
        return [
            led for led in (self.ledger, self.ledger_actor) if led is not None
        ]

    def _goodput_payload(self) -> dict:
        return {
            "colocated": (
                self.ledger.snapshot() if self.ledger is not None else None
            ),
            "roles": {
                led.role: led.snapshot() for led in self._ledgers()
            },
            "stragglers": [],
        }

    def _telemetry_tick(self, *args) -> None:
        # Queue gauges BEFORE the base tick: the base tick may export (and
        # record a history row), and that row should carry this tick's
        # depth, not the previous one's.
        if self.aggregator is not None and self._pipe is not None:
            reg = self.aggregator.registry
            reg.gauge("sebulba-queue-depth").set(float(self._pipe.qsize()))
            reg.gauge("sebulba-queue-peak-depth").set(
                float(self._pipe.peak_depth)
            )
        super()._telemetry_tick(*args)

    # ---------------------------------------------------------------- run loop
    def _actor_loop(self, carry, stats, needed: int | None) -> None:
        """Actor-lane thread entry (tools/analysis threads INVENTORY). All
        cross-thread publication goes through the BoundedPipe or the
        params/stats slots under ``self._lane_lock``."""
        from tpu_rl.obs.goodput import COMPUTE, H2D

        ledger = self.ledger_actor
        pipe = self._pipe
        produced = self._start_it
        while not self._lane_stop.is_set() and (
            needed is None or produced < needed
        ):
            with self._lane_lock:
                params = self._params_slot
                pver = self._params_ver
            k = jax.random.fold_in(self._k_act_base, produced)
            t0 = time.perf_counter()
            carry, stats, batch = self.rollout(params, carry, stats, k)
            batch = jax.block_until_ready(batch)
            t1 = time.perf_counter()
            if ledger is not None:
                ledger.add(COMPUTE, t1 - t0)
            # Reshard onto the learner group while the NEXT rollout could
            # already be dispatched — device-to-device transfer time is the
            # actor lane's h2d bucket (the split's analogue of a host feed).
            lbatch = jax.device_put(batch, self._bs)
            if ledger is not None:
                ledger.add(H2D, time.perf_counter() - t1)
            with self._lane_lock:
                self._stats_slot = stats
            if not pipe.put(
                (lbatch, stats, pver), ledger=ledger, stop=self._lane_stop
            ):
                break
            produced += 1

    def run(self, log: bool = True) -> dict:
        """Drive both lanes to ``max_updates`` (or the stop event). The
        learner lane is this thread; the actor lane is a daemon thread
        joined on every exit path."""
        cfg = self.cfg
        n, s = cfg.batch_size, cfg.seq_len
        timer = ExecutionTimer(num_transition=n * s)
        from tpu_rl.utils.metrics import make_writer

        writer = make_writer(cfg.result_dir)
        from tpu_rl.parallel.dp import replicate

        state = self.state
        if self.ckpt is not None:
            restored = self.ckpt.restore_run(
                jax.device_get(state),
                fingerprint=self._fingerprint,
                force=cfg.resume_force,
            )
            if restored is not None:
                state, self._start_it, meta = restored
                self.run_epoch = int(meta.get("epoch", 0)) + 1
                self._record_resume(self._start_it)
                if log:
                    print(
                        f"[sebulba] resumed from committed checkpoint "
                        f"idx {self._start_it} (run epoch {self.run_epoch})",
                        flush=True,
                    )
        state = replicate(state, self.mesh)
        k_carry = jax.random.fold_in(self._k_base, 0xC0C0)
        self._k_act_base = jax.random.fold_in(self._k_base, 0xAC7)
        carry = self.init_carry(k_carry)
        stats = self.init_stats()
        self._pipe = BoundedPipe(cfg.sebulba_queue)
        self._lane_stop = threading.Event()
        self._lane_lock = threading.Lock()
        self._params_slot = jax.device_put(act_params(state), self._act_rs)
        # Learner version of the published acting params: every batch in the
        # pipe is stamped with it, so the learner can attribute diagnostics
        # to real policy staleness (bounded by queue depth, but measured,
        # not assumed).
        self._params_ver = self._start_it
        self._stats_slot = stats
        ledger = self.ledger
        if ledger is not None:
            from tpu_rl.obs.goodput import CKPT, COMPUTE, H2D
        metrics: Any = {}
        # Learning-dynamics plane: same fold/drain as the fused loop, but
        # each batch carries the REAL policy lag (learner updates applied
        # since its acting params were published), so the by-staleness
        # gauge families are live in the split too.
        diag_acc = None
        if cfg.learn_diag:
            from tpu_rl.obs.learn import (
                DiagAccumulator,
                learn_record as _learn_record,
                publish as _publish_diag,
            )

            diag_acc = DiagAccumulator()
        log_every = max(1, cfg.loss_log_interval)
        it = self._start_it
        last_it, last_ep, last_ret = 0, 0, 0.0
        mean_ret, best_ret = 0.0, float("-inf")
        actor = threading.Thread(
            target=self._actor_loop,
            args=(carry, stats, self.max_updates),
            name="sebulba-actor",
            daemon=True,
        )
        t_mark = time.perf_counter()
        t0 = t_mark
        actor.start()
        try:
            while not self._stopping() and (
                self.max_updates is None or it < self.max_updates
            ):
                item = self._pipe.get(ledger=ledger, stop=self._stop)
                if item is None:
                    break
                batch, stats_ref, bver = item
                k_train = jax.random.fold_in(self._k_base, it)
                if self._perf is not None:
                    self._perf.capture(self.train, state, batch, k_train)
                self._backend.add_program(self.train, state, batch, k_train)
                t_disp = time.perf_counter()
                state, metrics = self.train(state, batch, k_train)
                if diag_acc is not None and isinstance(metrics, dict):
                    diag = metrics.pop("diag", None)
                    if diag is not None:
                        n_rows = (
                            next(iter(diag["rows"].values())).shape[0]
                            if diag["rows"] else 0
                        )
                        stale = float(max(0, it - bver))
                        diag_acc.add(
                            diag, jnp.full((n_rows,), stale, jnp.float32)
                        )
                metrics = jax.block_until_ready(metrics)
                t_done = time.perf_counter()
                if ledger is not None:
                    ledger.add(COMPUTE, t_done - t_disp)
                # Latest-wins param feedback onto the actor group: staleness
                # is bounded by the queue depth, not by a handshake.
                aparams = jax.device_put(act_params(state), self._act_rs)
                if ledger is not None:
                    ledger.add(H2D, time.perf_counter() - t_done)
                with self._lane_lock:
                    self._params_slot = aparams
                    self._params_ver = it + 1
                it += 1
                if self._heartbeat is not None:
                    self._heartbeat.value = time.time()
                if (
                    self.ckpt is not None
                    and it % cfg.model_save_interval == 0
                ):
                    t_ck = time.perf_counter()
                    self.ckpt.save(
                        state,
                        it,
                        meta={
                            "epoch": self.run_epoch,
                            "fingerprint": self._fingerprint,
                        },
                    )
                    if ledger is not None:
                        ledger.add(CKPT, time.perf_counter() - t_ck)
                    self._last_saved = it
                if it % log_every and it != self.max_updates:
                    continue
                host_stats = jax.device_get(stats_ref)
                host_metrics = {
                    k: float(v) for k, v in jax.device_get(metrics).items()
                }
                now = time.perf_counter()
                iters = it - last_it
                chunk_s = (now - t_mark) / max(1, iters)
                timer.record(
                    "sebulba-iteration", chunk_s, check_throughput=True
                )
                ups = iters / max(now - t_mark, 1e-9)
                tps = ups * n * s
                episodes = int(host_stats["episodes"])
                ret_sum = float(host_stats["ret_sum"])
                if episodes > last_ep:
                    mean_ret = (ret_sum - last_ret) / (episodes - last_ep)
                    best_ret = max(best_ret, mean_ret)
                self._telemetry_tick(
                    it, it * n * s, episodes, ups, tps, chunk_s, mean_ret
                )
                if diag_acc is not None:
                    diag_doc = diag_acc.drain(it)
                    if diag_doc is not None:
                        if self.aggregator is not None:
                            _publish_diag(self.aggregator.registry, diag_doc)
                        if cfg.result_dir is not None:
                            from tpu_rl.obs.audit import append_jsonl

                            append_jsonl(
                                cfg.result_dir, "learn.jsonl",
                                _learn_record(it, diag_doc),
                            )
                for name, val in host_metrics.items():
                    writer.add_scalar(f"loss/{name}", val, it)
                writer.add_scalar("colocated/env_steps_per_s", tps, it)
                writer.add_scalar(
                    "colocated/mean_episode_return", mean_ret, it
                )
                if log:
                    print(
                        f"[sebulba] update {it}  tps {tps:,.0f}  "
                        f"queue {self._pipe.qsize()}/{self._pipe.depth}  "
                        f"episodes {episodes}  mean_return {mean_ret:.1f}  "
                        + "  ".join(
                            f"{k} {v:.4f}" for k, v in host_metrics.items()
                        ),
                        flush=True,
                    )
                last_it, last_ep, last_ret = it, episodes, ret_sum
                t_mark = time.perf_counter()
        finally:
            self._lane_stop.set()
            actor.join(timeout=30.0)
        with self._lane_lock:
            stats_ref = self._stats_slot
        host_stats = jax.device_get(stats_ref)
        elapsed = time.perf_counter() - t0
        if (
            self.ckpt is not None
            and it > self._start_it
            and it != self._last_saved
        ):
            if ledger is not None:
                t_ck = time.perf_counter()
            self.ckpt.save(
                state,
                it,
                meta={
                    "epoch": self.run_epoch,
                    "fingerprint": self._fingerprint,
                },
            )
            if ledger is not None:
                ledger.add(CKPT, time.perf_counter() - t_ck)
        writer.flush()
        writer.close()
        self.close()
        self.state = state
        episodes = int(host_stats["episodes"])
        ret_sum = float(host_stats["ret_sum"])
        new_it = it - self._start_it
        return {
            "updates": it,
            "env_steps": it * n * s,
            "episodes": episodes,
            "mean_return_overall": ret_sum / max(1, episodes),
            "mean_return_recent": mean_ret,
            "mean_return_best_window": best_ret,
            "elapsed_s": elapsed,
            "transitions_per_s": new_it * n * s / max(elapsed, 1e-9),
            "queue_peak_depth": self._pipe.peak_depth,
            "scalars": timer.scalars(),
        }


def sebulba_main(
    cfg: Config, stop_event, heartbeat, max_updates: int | None = None,
    seed: int = 0,
) -> None:
    """Supervised child entry for the sebulba split (the colocated role
    routes here when ``cfg.sebulba_split > 0``)."""
    loop = SebulbaLoop(
        cfg,
        seed=seed,
        max_updates=max_updates,
        stop_event=stop_event,
        heartbeat=heartbeat,
    )
    out = loop.run()
    print(
        f"[sebulba] done: {out['updates']} updates, "
        f"{out['env_steps']:,} env steps, {out['episodes']} episodes, "
        f"mean return {out['mean_return_overall']:.1f}, "
        f"{out['transitions_per_s']:,.0f} transitions/s, "
        f"queue peak {out['queue_peak_depth']}",
        flush=True,
    )
    if cfg.slo_fail_run and loop.slo_failed:
        print("[sebulba] SLO verdict failing; exiting nonzero", flush=True)
        raise SystemExit(3)
