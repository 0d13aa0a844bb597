"""Colocated (Anakin-mode) driver: envs on-device, one fused program.

Podracer/Anakin (PAPERS.md, arxiv 2104.06272) colocates environments with
the learner on the same accelerator: ``act -> env.step -> train`` compiles
into ONE jitted program, so a training iteration is a single XLA dispatch
with zero host<->device traffic and none of the distributed plane's
worker/relay/storage machinery. This module is that mode for jittable envs
(``tpu_rl/envs``); the distributed path stays the default for real
(host-side) simulators.

The fused program per iteration:

1. ``lax.scan`` over ``cfg.seq_len`` acting ticks. Each tick reproduces the
   distributed worker's tick semantics EXACTLY (runtime/worker.py): store the
   pre-step obs / pre-step carry / pre-tick ``is_fir``, act, step the env,
   scale the reward, zero the carry on done (``where``, never multiply — NaN
   safety), raise ``is_fir`` for the post-reset step. Auto-reset and the
   ``time_horizon`` truncation live in ``envs.core.make_vec_env``.
2. Transpose the scan's ``(S, B, w)`` stack to the learner's ``(B, S, w)``
   :class:`~tpu_rl.types.Batch`. Because every env contributes exactly one
   full window per scan with no cross-env interleaving, this IS what
   ``data.assembler.RolloutAssembler`` would emit for the same transition
   stream (``tests/test_colocated.py`` pins it bit-for-bit).
3. Run the pure ``train_step(state, batch, key)`` from the algo registry —
   the same function the distributed learner compiles — on the batch while
   it is still on device.

The env batch is the train batch (``batch_size`` envs, overridable via
``Config.colocated_envs``), sharded over the data mesh like any learner
batch; parameters are replicated and GSPMD inserts the gradient all-reduce.
Episode bookkeeping (completed-episode count / return sum) is accumulated
*on device* in a replicated ``stats`` tree so the steady-state loop does
zero per-iteration host transfers; the host only fetches at log intervals.
"""

from __future__ import annotations

import os
import time
from typing import Any

import jax
import jax.numpy as jnp

from tpu_rl.config import Config
from tpu_rl.data.layout import BatchLayout
from tpu_rl.envs import get_spec, make_vec_env
from tpu_rl.parallel.mesh import (
    batch_sharding,
    check_divisible,
    make_mesh,
    replicated,
)
from tpu_rl.types import BATCH_FIELDS, Batch
from tpu_rl.utils.timer import ExecutionTimer


def act_params(state) -> dict:
    """Acting parameter tree for either train-state flavor (colocated mode is
    on-policy-only, but keep the SAC shape for completeness)."""
    if hasattr(state, "actor_params"):
        return {"actor": state.actor_params}
    return {"actor": state.params["actor"]}


def resolve_colocated_config(cfg: Config) -> Config:
    """Apply the colocated-mode config overrides: ``colocated_envs`` replaces
    ``batch_size`` (the env batch IS the train batch), and the obs/action
    spaces are derived from the jittable env spec (no gymnasium)."""
    if cfg.colocated_envs:
        cfg = cfg.replace(
            batch_size=cfg.colocated_envs,
            buffer_size=max(cfg.buffer_size, cfg.colocated_envs),
        )
    spec = get_spec(cfg.env)
    return cfg.replace(
        obs_shape=spec.obs_shape,
        action_space=spec.action_space,
        is_continuous=spec.is_continuous,
    )


class ColocatedLoop:
    """Owns the fused act->step->train program and its device-resident state.

    Two compiled entry points:

    - :attr:`rollout` — ``(params, carry, key) -> (carry, batch, done, ret)``:
      the acting scan alone. Used by tests (assembler equivalence).
    - :attr:`program` — ``(state, carry, stats, k_roll, k_train) ->
      (state, carry, stats, metrics)``: rollout + train fused. ``state``,
      ``carry`` and ``stats`` are donated; the steady-state loop re-dispatches
      on the device-resident outputs without any host hop.
    """

    role = "colocated"  # bring-up record / log prefix

    def __init__(
        self,
        cfg: Config,
        seed: int = 0,
        max_updates: int | None = None,
        stop_event=None,
        heartbeat=None,
    ):
        cfg = resolve_colocated_config(cfg)
        assert cfg.env_mode == "colocated", cfg.env_mode
        self.cfg = cfg
        self.seed = int(seed)
        self.max_updates = max_updates
        self._stop = stop_event
        self._heartbeat = heartbeat

        # Pod-Anakin: join the jax.distributed runtime BEFORE any device
        # query, exactly like the learner role (learner_service.run). After
        # init the meshes below span every host's chips and GSPMD inserts
        # the cross-host gradient all-reduce into the unchanged fused
        # program. Single-host configs (multihost=None or num_processes=1)
        # skip this entirely.
        if cfg.multihost:
            from tpu_rl.parallel.multihost import init_multihost

            init_multihost(**cfg.multihost)
        self._chief = jax.process_index() == 0
        self._build_meshes()
        from tpu_rl.utils.platform import BackendRecord

        self._backend = BackendRecord(self.role, cfg, self.mesh)
        self.spec = get_spec(cfg.env)
        self._v_reset, self._v_step = make_vec_env(
            self.spec, cfg.batch_size, cfg.time_horizon
        )
        key = jax.random.PRNGKey(self.seed)
        k_build, self._k_base = jax.random.split(key)
        from tpu_rl.algos.registry import get_algo

        self.family, self.state, self._train_step = get_algo(cfg.algo).build(
            cfg, k_build, self.mesh
        )
        self.layout = BatchLayout.from_config(cfg)

        # Durability (PR 9 semantics, extended to the fused loop for the
        # population plane): two-phase commits every model_save_interval,
        # newest-committed resume with fingerprint refusal, run-epoch chain
        # in the marker meta. The per-iteration PRNG is fold_in(base, it) —
        # stateless in it — so resuming needs only the update index: the
        # continued run replays the exact key stream the unbroken run would
        # have used.
        self.ckpt = None
        self.run_epoch = 0
        self._start_it = 0
        self._last_saved = -1
        self._fingerprint = None
        if cfg.model_dir:
            from tpu_rl.checkpoint import Checkpointer, resume_fingerprint

            self.ckpt = Checkpointer(
                cfg.model_dir,
                cfg.algo,
                keep=cfg.ckpt_keep,
                async_save=cfg.ckpt_async,
            )
            self._fingerprint = resume_fingerprint(cfg)

        self._compile()

        # Telemetry plane (same knobs/ports as every other role; satellite of
        # the obs registry — nothing is constructed when the plane is off).
        self.aggregator = None
        self._http = None
        self._json_exp = None
        self._perf = None
        self._prof = None
        self._slo = None
        # Run-history store (tpu_rl.obs.history): the colocated deployment
        # is its own storage side, so it self-serves the plane — fed on the
        # exporter cadence, served live at /query. None = plane off.
        self._history = None
        # Goodput ledger for the fused loop (tpu_rl.obs.goodput). The whole
        # deployment is one process, so one ledger covers it: dispatch +
        # blocking device_get land in compute, checkpoint saves in ckpt,
        # everything else (telemetry, logging) spills into overhead.
        self.ledger = None
        self._setup_telemetry()

    # ---------------------------------------------------------- topology hooks
    def _build_meshes(self) -> None:
        """Device-topology hook: the Anakin loop is ONE mesh for acting and
        training alike (the sebulba subclass splits them). Under multihost
        the default ``mesh_data=1`` widens to the full global device set —
        a pod run saying nothing about mesh width means "use the pod"."""
        cfg = self.cfg
        if cfg.multihost and jax.process_count() > 1 and cfg.mesh_data == 1:
            self.mesh = make_mesh(jax.device_count())
        else:
            self.mesh = make_mesh(cfg.mesh_data)
        self.act_mesh = self.mesh
        check_divisible(cfg.batch_size, self.mesh)

    def _compile(self) -> None:
        """Compile hook: build the jitted entry points for this topology."""
        rs, bs = replicated(self.mesh), batch_sharding(self.mesh)
        self._rs, self._bs = rs, bs
        # Acting-side shardings: identical to the train mesh here; the
        # sebulba split points them at the actor device group instead.
        self._act_rs, self._act_bs = rs, bs
        # Every rollout output is batch-leading, so one sharding prefix
        # covers carry, batch, done and ret alike.
        self.rollout = jax.jit(
            self._rollout_body,
            in_shardings=(rs, bs, rs),
            out_shardings=bs,
            donate_argnums=(1,),
        )
        self.program = jax.jit(
            self._program_body,
            in_shardings=(rs, bs, rs, rs, rs),
            out_shardings=(rs, bs, rs, rs),
            donate_argnums=(0, 1, 2),
        )

    def _place(self, tree, sharding):
        """Put host-built (or locally-committed) arrays under a global
        sharding. Single-process meshes take the direct ``device_put``;
        multi-process meshes route through an SPMD identity jit (same trick
        as ``parallel.dp.replicate`` — ``device_put`` refuses shardings that
        span non-addressable devices). Valid because every host builds the
        identical value (same seed/key stream)."""
        local = jax.process_index()
        if all(d.process_index == local for d in sharding.mesh.devices.flat):
            return jax.device_put(tree, sharding)
        return jax.jit(lambda t: t, out_shardings=sharding)(tree)

    # ------------------------------------------------------------ device init
    def init_carry(self, key: jax.Array) -> dict:
        """Fresh device carry: reset envs, zero recurrent state, ``is_fir=1``
        (every env starts an episode), zero running returns."""
        env, obs = self._v_reset(key)
        n = self.cfg.batch_size
        hw, cw = self.family.carry_widths
        carry = {
            "env": env,
            # copy: for state==obs envs (CartPole) reset returns ONE array for
            # both leaves, and the donated program rejects aliased buffers.
            "obs": jnp.array(obs, copy=True),
            "h": jnp.zeros((n, hw), jnp.float32),
            "c": jnp.zeros((n, cw), jnp.float32),
            "is_fir": jnp.ones((n,), jnp.float32),
            "ret": jnp.zeros((n,), jnp.float32),
        }
        return self._place(carry, self._act_bs)

    def init_stats(self) -> dict:
        return self._place(
            {
                "episodes": jnp.zeros((), jnp.int32),
                "ret_sum": jnp.zeros((), jnp.float32),
            },
            self._act_rs,
        )

    # -------------------------------------------------------------- jit bodies
    def _tick(self, params, cr: dict, k: jax.Array):
        """One acting tick — the worker loop's body as pure jax."""
        cfg, family = self.cfg, self.family
        k_act, k_env = jax.random.split(k)
        a, logits, log_prob, h2, c2 = family.act(
            params, cr["obs"], cr["h"], cr["c"], k_act
        )
        env, obs2, rew, done = self._v_step(cr["env"], a, k_env)
        ret2 = cr["ret"] + rew
        if family.store_carry:
            hx, cx = cr["h"], cr["c"]
        else:
            n = cfg.batch_size
            hx = jnp.zeros((n, self.layout.width("hx")), jnp.float32)
            cx = jnp.zeros((n, self.layout.width("cx")), jnp.float32)
        ys = dict(
            obs=cr["obs"],
            act=a,
            rew=(rew * cfg.reward_scale)[:, None].astype(jnp.float32),
            logits=logits,
            log_prob=log_prob,
            is_fir=cr["is_fir"][:, None],
            hx=hx,
            cx=cx,
            done=done,
            # Completed-episode RAW return, emitted on the terminal tick.
            ep_ret=jnp.where(done, ret2, 0.0),
        )
        keep = (~done)[:, None]
        cr2 = {
            "env": env,
            "obs": obs2,
            # where(), not multiply: a NaN carry from a diverged net must not
            # survive the reset (same guard as the worker).
            "h": jnp.where(keep, h2, 0.0),
            "c": jnp.where(keep, c2, 0.0),
            "is_fir": done.astype(jnp.float32),
            "ret": jnp.where(done, 0.0, ret2),
        }
        return cr2, ys

    def _rollout_body(self, params, carry: dict, key: jax.Array):
        keys = jax.random.split(key, self.cfg.seq_len)
        carry, ys = jax.lax.scan(
            lambda cr, k: self._tick(params, cr, k), carry, keys
        )
        swap = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731 — (S,B,w)->(B,S,w)
        batch = Batch(**{f: swap(ys[f]) for f in BATCH_FIELDS})
        return carry, batch, swap(ys["done"]), swap(ys["ep_ret"])

    def _program_body(self, state, carry, stats, k_roll, k_train):
        # Register the mesh only while this body traces, so LSTM unrolls emit
        # the fused Pallas kernel as a shard_map island over the data axis
        # (same dance as parallel.dp.make_parallel_train_step).
        from tpu_rl.models import cells

        prev = cells._DATA_MESH
        cells.set_data_mesh(self.mesh)
        try:
            carry, batch, done, ep_ret = self._rollout_body(
                act_params(state), carry, k_roll
            )
            state, metrics = self._train_step(state, batch, k_train)
        finally:
            cells.set_data_mesh(prev)
        stats = {
            "episodes": stats["episodes"] + done.sum(dtype=jnp.int32),
            "ret_sum": stats["ret_sum"] + ep_ret.sum(),
        }
        return state, carry, stats, metrics

    # ---------------------------------------------------------------- telemetry
    def _setup_telemetry(self) -> None:
        cfg = self.cfg
        if not cfg.telemetry_enabled:
            return
        from tpu_rl.obs import (
            GoodputLedger,
            JsonExporter,
            MetricsRegistry,
            PerfTracker,
            ProfilerCapture,
            TelemetryAggregator,
            TelemetryHTTPServer,
            maybe_history,
            maybe_slo_engine,
        )

        self.aggregator = TelemetryAggregator(
            registry=MetricsRegistry(role="colocated"),
            stale_after_s=cfg.telemetry_stale_s,
        )
        self.ledger = GoodputLedger("colocated")
        self._perf = PerfTracker()
        self._slo = maybe_slo_engine(cfg)
        self._history = maybe_history(cfg)
        if cfg.result_dir is not None:
            self._prof = ProfilerCapture(os.path.join(cfg.result_dir, "prof"))
        if cfg.telemetry_port > 0:
            self._http = TelemetryHTTPServer(
                self.aggregator,
                cfg.telemetry_port,
                slo=self._slo.report if self._slo is not None else None,
                prof=(
                    self._prof.capture_async if self._prof is not None else None
                ),
                goodput=self._goodput_payload,
                query=(
                    self._history.http_query
                    if self._history is not None else None
                ),
            )
        if cfg.result_dir is not None:
            self._json_exp = JsonExporter(
                self.aggregator,
                os.path.join(cfg.result_dir, "telemetry.json"),
                interval_s=cfg.telemetry_interval_s,
            )

    def _telemetry_tick(
        self,
        updates: int,
        env_steps: int,
        episodes: int,
        ups: float,
        tps: float,
        chunk_s: float,
        mean_ret: float,
    ) -> None:
        if self.aggregator is None:
            return
        reg = self.aggregator.registry
        reg.counter("colocated-updates").set_total(updates)
        reg.counter("colocated-env-steps").set_total(env_steps)
        reg.counter("colocated-episodes").set_total(episodes)
        reg.gauge("colocated-updates-per-s").set(ups)
        reg.gauge("colocated-env-steps-per-s").set(tps)
        reg.gauge("colocated-mean-episode-return").set(mean_ret)
        reg.histogram("colocated-scan-chunk-s").observe(chunk_s)
        if self._perf is not None:
            # chunk_s is the per-iteration mean measured against a blocking
            # device_get — exactly the dispatch interval the tracker wants.
            self._perf.note(chunk_s)
            reg.gauge("colocated-flops-per-step").set(
                self._perf.flops_per_call
            )
            achieved = self._perf.achieved_flops_per_s()
            if achieved is not None:
                reg.gauge("colocated-achieved-flops").set(achieved)
            mfu = self._perf.mfu()
            if mfu is not None:
                reg.gauge("colocated-mfu").set(mfu)
            reg.counter("colocated-xla-recompiles").set_total(
                self._perf.recompiles
            )
            from tpu_rl.obs.perf import device_memory_bytes, process_self_stats

            in_use, peak = device_memory_bytes()
            reg.gauge("colocated-device-mem-bytes").set(in_use)
            reg.gauge("colocated-device-mem-peak-bytes").set(peak)
            rss, n_fds = process_self_stats()
            reg.gauge("colocated-rss-bytes").set(rss)
            reg.gauge("colocated-open-fds").set(float(n_fds))
        for led in self._ledgers():
            led.publish(reg)
        if self._slo is not None:
            self._slo.evaluate(self.aggregator)
        if self._json_exp is not None and self._json_exp.maybe_export():
            if self._history is not None:
                # Same cadence decision the JSON exporter just made: one
                # flattened history row per export.
                self._history.record(self.aggregator)
            if self.ledger is not None:
                # Ledger audit trail on the exporter's cadence — the offline
                # twin of GET /goodput, same file name as storage writes.
                from tpu_rl.obs.audit import append_jsonl

                append_jsonl(
                    self.cfg.result_dir, "goodput.jsonl",
                    self._goodput_payload(),
                )

    def _ledgers(self) -> list:
        """Every goodput ledger this loop owns (one per lane thread; the
        fused Anakin loop is one lane, the sebulba split is two)."""
        return [self.ledger] if self.ledger is not None else []

    def _goodput_payload(self) -> dict:
        """The GET /goodput document for the single-process deployment: just
        this loop's ledger snapshot (no fleet, so no stragglers)."""
        return {
            "colocated": (
                self.ledger.snapshot() if self.ledger is not None else None
            ),
            "roles": {},
            "stragglers": [],
        }

    def _record_resume(self, idx: int) -> None:
        """Append one resume record to result_dir/learner_resume.jsonl —
        the same audit file (and shape) the distributed learner writes
        (pinned by test), so resume-smoke-style assertions work against
        either mode."""
        from tpu_rl.obs.audit import append_resume

        append_resume(self.cfg.result_dir, idx, self.run_epoch)

    def close(self) -> None:
        self._backend.close()
        if self.ckpt is not None:
            self.ckpt.close()
            self.ckpt = None
        if self._http is not None:
            self._http.close()
        if self._prof is not None:
            self._prof.close()
        if self._slo is not None and self.cfg.result_dir is not None:
            import json

            with open(
                os.path.join(self.cfg.result_dir, "slo.json"), "w"
            ) as f:
                json.dump(self._slo.report(), f, indent=2)
        if self._json_exp is not None:
            # Force a final write regardless of the exporter's cadence.
            self._json_exp.maybe_export(now=float("inf"))
        if self._history is not None:
            # Final history row + release the active chunk handle.
            self._history.record(self.aggregator)
            self._history.close()
            self._history = None

    @property
    def slo_failed(self) -> bool:
        """The ``Config.slo_fail_run`` exit gate for the colocated role."""
        return self._slo is not None and self._slo.failed

    # ---------------------------------------------------------------- run loop
    def _stopping(self) -> bool:
        return self._stop is not None and self._stop.is_set()

    def run(self, log: bool = True) -> dict:
        """Drive the fused program to ``max_updates`` (or until the stop
        event). Returns a summary dict with run totals and timer scalars."""
        cfg = self.cfg
        # Non-chief pod processes run the identical SPMD program but leave
        # stdout and checkpoint writes to process 0 (the restore below runs
        # everywhere — model_dir is shared storage on a pod).
        log = log and self._chief
        n, s = cfg.batch_size, cfg.seq_len
        timer = ExecutionTimer(num_transition=n * s)
        from tpu_rl.utils.metrics import make_writer

        writer = make_writer(cfg.result_dir)
        k_carry = jax.random.fold_in(self._k_base, 0xC0C0)
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
                        f"[colocated] resumed from committed checkpoint "
                        f"idx {self._start_it} (run epoch {self.run_epoch})",
                        flush=True,
                    )
        state = replicate(state, self.mesh)
        carry = self.init_carry(k_carry)
        stats = self.init_stats()
        ledger = self.ledger
        if ledger is not None:
            from tpu_rl.obs.goodput import CKPT, COMPUTE
        metrics: Any = {}
        # Learning-dynamics plane: fold each iteration's in-jit ``diag`` into
        # the on-device accumulator (one tiny extra dispatch, zero syncs) and
        # drain on the log cadence below. Colocated rollouts are consumed the
        # same iteration they are produced, so every row is staleness-0.
        diag_acc = None
        if cfg.learn_diag:
            from tpu_rl.obs.learn import (
                DiagAccumulator,
                learn_record as _learn_record,
                publish as _publish_diag,
            )

            diag_acc = DiagAccumulator()
        stale0 = None
        log_every = max(1, cfg.loss_log_interval)
        it = self._start_it
        last_it, last_ep, last_ret = 0, 0, 0.0
        mean_ret, best_ret = 0.0, float("-inf")
        t_mark = time.perf_counter()
        t0 = t_mark
        while not self._stopping() and (
            self.max_updates is None or it < self.max_updates
        ):
            k_roll, k_train = jax.random.split(
                jax.random.fold_in(self._k_base, it)
            )
            if self._perf is not None:
                # One-time AOT cost analysis (identity no-op afterwards) —
                # must run before dispatch, while donated buffers are alive.
                self._perf.capture(
                    self.program, state, carry, stats, k_roll, k_train
                )
            self._backend.add_program(
                self.program, state, carry, stats, k_roll, k_train
            )
            t_disp = time.perf_counter()
            state, carry, stats, metrics = self.program(
                state, carry, stats, k_roll, k_train
            )
            if diag_acc is not None and isinstance(metrics, dict):
                diag = metrics.pop("diag", None)
                if diag is not None:
                    if stale0 is None:
                        n_rows = (
                            next(iter(diag["rows"].values())).shape[0]
                            if diag["rows"] else 0
                        )
                        stale0 = jnp.zeros((n_rows,), jnp.float32)
                    diag_acc.add(diag, stale0)
            if ledger is not None:
                ledger.add(COMPUTE, time.perf_counter() - t_disp)
            it += 1
            if self._heartbeat is not None:
                self._heartbeat.value = time.time()
            if (
                self.ckpt is not None
                and self._chief
                and it % cfg.model_save_interval == 0
            ):
                # `state` is the program's fresh output buffers (donation
                # consumes the inputs), so the save path may snapshot it.
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
            # device_get blocks on iteration `it`, so the wall-clock delta
            # below covers real device work (dispatch is async in between) —
            # the block lands in the ledger's compute bucket for the same
            # reason.
            t_get = time.perf_counter()
            host_stats = jax.device_get(stats)
            host_metrics = {
                k: float(v) for k, v in jax.device_get(metrics).items()
            }
            if ledger is not None:
                ledger.add(COMPUTE, time.perf_counter() - t_get)
            now = time.perf_counter()
            iters = it - last_it
            chunk_s = (now - t_mark) / max(1, iters)
            timer.record("colocated-iteration", chunk_s, check_throughput=True)
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
            writer.add_scalar("colocated/mean_episode_return", mean_ret, it)
            if log:
                print(
                    f"[colocated] update {it}  tps {tps:,.0f}  "
                    f"episodes {episodes}  mean_return {mean_ret:.1f}  "
                    + "  ".join(
                        f"{k} {v:.4f}" for k, v in host_metrics.items()
                    ),
                    flush=True,
                )
            last_it, last_ep, last_ret = it, episodes, ret_sum
            t_mark = time.perf_counter()
        host_stats = jax.device_get(stats)
        elapsed = time.perf_counter() - t0
        if (
            self.ckpt is not None
            and self._chief
            and it > self._start_it
            and it != self._last_saved
        ):
            # Final commit so a member finishing its budget (or stopped by
            # the controller for an exploit) leaves its newest state
            # durable — PBT winners are copied from disk, not from RAM.
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
        # Expose the final device state: the donated input handles are dead,
        # and tests/parity probes read params from here after run().
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
            # Max over per-log-window completed-episode means: the stable
            # "did it learn" signal (on-policy curves oscillate after peak).
            "mean_return_best_window": best_ret,
            "elapsed_s": elapsed,
            "transitions_per_s": new_it * n * s / max(elapsed, 1e-9),
            "scalars": timer.scalars(),
        }


def colocated_main(
    cfg: Config, stop_event, heartbeat, max_updates: int | None = None,
    seed: int = 0,
) -> None:
    """Supervised child entry: the whole colocated deployment is this one
    process (supervisor spawns it via ``runner.colocated_role``)."""
    loop = ColocatedLoop(
        cfg,
        seed=seed,
        max_updates=max_updates,
        stop_event=stop_event,
        heartbeat=heartbeat,
    )
    out = loop.run()
    print(
        f"[colocated] done: {out['updates']} updates, "
        f"{out['env_steps']:,} env steps, {out['episodes']} episodes, "
        f"mean return {out['mean_return_overall']:.1f}, "
        f"{out['transitions_per_s']:,.0f} transitions/s",
        flush=True,
    )
    if cfg.slo_fail_run and loop.slo_failed:
        print("[colocated] SLO verdict failing; exiting nonzero", flush=True)
        raise SystemExit(3)
