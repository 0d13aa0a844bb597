"""Inference fleet replica: continuous batching + GSPMD sharding + version-
keyed weight rollout.

:class:`InferenceReplica` specializes the PR 2
:class:`~tpu_rl.runtime.inference_service.InferenceService` on the three
axes the single-service design fixed:

- **continuous batching**: the base service waits for
  ``inference_batch`` rows OR the ``inference_flush_us`` deadline before a
  flush. Under fleet-scale open-loop load that deadline is pure queueing
  delay: the replica instead admits whatever has arrived and dispatches
  immediately — requests landing DURING a dispatch form the next in-flight
  batch, so the device never idles while work is queued and latency tracks
  the actual dispatch time, not a tuning knob;
- **GSPMD-sharded acting** (``Config.inference_mesh_data > 1``): the padded
  act program is jitted with ``NamedSharding`` constraints over the
  existing :mod:`tpu_rl.parallel.mesh` named mesh — obs/carry/first batches
  split along the leading axis (``P("data")``), params replicated — and
  ``pad_rows`` is rounded up to a mesh-divisible shape (checked with
  ``check_divisible``), so one replica spans several devices;
- **version-consistent rollout**: ``set_params`` is keyed on ``ver`` and
  NEVER rolls back — a re-delivered or out-of-order broadcast is a no-op.
  Combined with the client-side version floor (``FleetClient``) this gives
  the fleet guarantee: no client ever observes weights older than ones it
  already saw, no matter which replica answers.

``replica_main`` is the standalone-process entry for replicas 1..N−1
(replica 0 stays in-process in the learner): it subscribes the same model
PUB broadcast workers use, applies frames through the ver-keyed swap, and
emits telemetry snapshots stamped with its ``rid`` + served ``ver`` onto the
stat channel — which is exactly what storage's :class:`ReplicaTable` leases
on (and what triggers the learner's join-push of current weights).
"""

from __future__ import annotations

import time

from tpu_rl.config import Config
from tpu_rl.runtime.inference_service import InferenceService
from tpu_rl.runtime.protocol import Protocol
from tpu_rl.runtime.transport import MODEL_HWM, Sub, make_data_pub


class InferenceReplica(InferenceService):
    """One elastic fleet member. Same constructor and thread contract as
    the base service; ``start()``/``wait_ready()``/``close()`` unchanged."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.n_stale_sets = 0  # ver-keyed swaps refused (<= current ver)
        self.n_flush_continuous = 0  # dispatches admitted without a deadline

    # ------------------------------------------------------ version rollout
    def set_params(self, params, version: int = -1) -> None:
        """Atomic swap keyed on ``ver``: apply only strictly NEWER weights.
        Re-delivered broadcasts (idle rebroadcast, join push) and reordered
        frames become no-ops instead of rollbacks, so every reply's ``ver``
        is monotonic per replica — the server half of the fleet's
        version-floor guarantee. Quantization to the serving dtype runs
        OUTSIDE the lock (it launches device work); the ver gate is checked
        before (skip the cast for frames already known stale) and again
        under the lock (a newer frame may have landed meanwhile)."""
        with self._lock:
            if version <= self._version:
                self.n_stale_sets += 1
                return
        quant = self._quantize(params)
        with self._lock:
            if version <= self._version:
                self.n_stale_sets += 1
                return
            self._params = quant
            self._version = version

    # ---------------------------------------------------------------- GSPMD
    def _build_step(self, jax, jnp):
        """Jit the act program under the named data mesh when
        ``inference_mesh_data > 1``; single-device replicas keep the base
        bucketed jits. Every bucket shape is rounded UP to a mesh-divisible
        count (then deduped) so each padded program shards evenly — the
        quantized param tree stays replicated leaf-wise exactly like f32."""
        cfg = self.cfg
        n = int(getattr(cfg, "inference_mesh_data", 1))
        if n <= 1:
            return super()._build_step(jax, jnp)
        from tpu_rl.parallel.mesh import (
            batch_sharding,
            check_divisible,
            make_mesh,
            replicated,
        )

        mesh = make_mesh(n)
        # ceil each bucket to a shardable batch; dedupe collisions
        buckets = sorted({-(-b // n) * n for b in self._bucket_ladder()})
        check_divisible(buckets[-1], mesh)
        rep, bsh = replicated(mesh), batch_sharding(mesh)
        steps = {
            rows: jax.jit(
                self._step_fn(jnp, n_devices=n),
                # Params replicated, batch-shaped operands split on "data",
                # PRNG key replicated; outputs inherit GSPMD's propagation.
                in_shardings=(rep, bsh, bsh, bsh, bsh, rep),
            )
            for rows in buckets
        }
        return steps, buckets

    # --------------------------------------------------- continuous batching
    def _loop(self, jax, router, steps, buckets, key) -> None:
        """Admit-and-dispatch: no max-batch gate, no deadline. Whatever is
        pending when the device is free forms the batch (bounded by the
        largest bucket program) and dispatches through the smallest covering
        bucket. The base counters stay honest: a dispatch at the padded
        capacity counts as ``n_flush_full``, everything else as a
        continuous admission."""
        from bisect import bisect_left

        jnp = self._jnp
        pad_rows = buckets[-1]
        store_carry = self.family.store_carry
        pending = []
        pending_rows = 0
        ledger = self.ledger
        if ledger is not None:
            from tpu_rl.obs.goodput import COMPUTE, IDLE, QUEUE_WAIT, WIRE

        while not self._stop.is_set():
            # Block only when idle; with work queued, just sweep the socket.
            t_recv = time.perf_counter()
            got = router.recv(timeout_ms=0 if pending else 20)
            if ledger is not None:
                span = time.perf_counter() - t_recv
                if pending:
                    ledger.add(QUEUE_WAIT, span)
                elif got is not None:
                    ledger.add(WIRE, span)
                else:
                    ledger.add(IDLE, span)
            if got is not None:
                req = self._ingest(*got)
                if req is not None:
                    pending.append(req)
                    pending_rows += req.obs.shape[0]
                for parts in router.drain():
                    req = self._ingest(*parts)
                    if req is not None:
                        pending.append(req)
                        pending_rows += req.obs.shape[0]
            if not pending:
                continue
            chunk, rows = [], 0
            while pending and rows + pending[0].obs.shape[0] <= pad_rows:
                req = pending.pop(0)
                chunk.append(req)
                rows += req.obs.shape[0]
            if not chunk:
                # A request wider than the padded program can never be
                # served at this fixed shape; drop it (counted) rather than
                # wedging the queue head forever.
                req = pending.pop(0)
                pending_rows -= req.obs.shape[0]
                self.n_rejected_payload += 1
                continue
            pending_rows -= rows
            if rows >= pad_rows:
                self.n_flush_full += 1
            else:
                self.n_flush_continuous += 1
            bucket = buckets[bisect_left(buckets, rows)]
            key, sub = jax.random.split(key)
            t_fl = time.perf_counter()
            self._flush(
                router, steps[bucket], chunk, rows, bucket, sub,
                store_carry, jnp,
            )
            if ledger is not None:
                ledger.add(COMPUTE, time.perf_counter() - t_fl)


def replica_main(
    cfg: Config,
    replica_id: int,
    port: int,
    learner_ip: str,
    model_port: int,
    stat_port: int,
    stop_event,
    heartbeat,
    seed: int = 0,
) -> None:
    """mp.Process target for standalone replicas (supervisor children named
    ``inference-<i>`` — the name the chaos plane's ``kill:inference-<i>``
    faults match). Boots on random-init params; the telemetry snapshot's
    ``rid`` reaches storage's ReplicaTable, whose JOIN raises the mailbox
    flag, and the learner's join-push delivers current weights + ver over
    the model broadcast this process already subscribes."""
    import jax

    from tpu_rl.models.families import build_family

    # Finish the tpu_rl.obs package import on THIS thread before the serving
    # thread starts: InferenceReplica's loop lazily imports tpu_rl.obs.perf,
    # and two threads entering the package import concurrently trip Python's
    # import-deadlock breaker — one of them sees a partially initialized
    # module and the replica dies (a crash loop on scale-out respawns).
    import tpu_rl.obs.perf  # noqa: F401
    from tpu_rl.utils.platform import BackendRecord

    backend = BackendRecord(f"inference-{replica_id}", cfg)
    family = build_family(cfg)
    params = family.init_params(
        jax.random.key(seed * 6151 + replica_id), seq_len=cfg.seq_len
    )
    svc = InferenceReplica(
        cfg, family, params, port, timer=None, seed=seed + replica_id,
        version=-1,
    ).start()
    sub = Sub(learner_ip, model_port, bind=False, hwm=MODEL_HWM)
    registry = emitter = pub = None
    if cfg.telemetry_enabled:
        from tpu_rl.obs import MetricsRegistry, PeriodicSnapshot

        registry = MetricsRegistry(
            role="inference", labels={"rid": str(replica_id)}
        )
        pub = make_data_pub(cfg, learner_ip, stat_port, bind=False)

        def _send_snap(snap, _rid=replica_id):
            # Top-level rid + ver: the ReplicaTable's lease key and the
            # version its floor ratchets from.
            snap["rid"] = _rid
            snap["ver"] = svc.version
            pub.send(Protocol.Telemetry, snap)

        emitter = PeriodicSnapshot(
            registry, _send_snap, interval_s=cfg.telemetry_interval_s
        )
    try:
        if not svc.wait_ready(300.0):
            raise RuntimeError(f"replica {replica_id} never became ready")
        while not (stop_event is not None and stop_event.is_set()):
            if svc.error is not None:
                raise svc.error
            for proto, payload in sub.drain(max_msgs=MODEL_HWM):
                if proto == Protocol.Model:
                    # Ver-keyed swap: stale/re-delivered broadcasts no-op.
                    svc.set_params(
                        {"actor": payload["actor"]},
                        version=int(payload.get("ver", -1)),
                    )
            if registry is not None:
                registry.counter("inference-requests").set_total(
                    svc.n_requests
                )
                registry.counter("inference-replies").set_total(svc.n_replies)
                registry.counter("inference-batches").set_total(svc.n_batches)
                registry.gauge("fleet-replica-version").set(svc.version)
                if svc.perf is not None:
                    registry.gauge("inference-flops-per-step").set(
                        svc.perf.flops_per_call
                    )
                    achieved = svc.perf.achieved_flops_per_s()
                    if achieved is not None:
                        registry.gauge("inference-achieved-flops").set(
                            achieved
                        )
                # Fast-path observables: summed per-bucket recompile watch,
                # param footprint, bucket dispatch histogram + counters.
                svc.publish_serving_metrics(registry)
                if svc.ledger is not None:
                    svc.ledger.publish(registry)
                emitter.maybe_emit()
            if heartbeat is not None:
                heartbeat.value = time.time()
            time.sleep(0.05)
    finally:
        svc.close()
        sub.close()
        if pub is not None:
            pub.close()
        backend.close()
