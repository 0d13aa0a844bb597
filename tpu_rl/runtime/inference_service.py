"""SEED-style centralized inference service: batched remote acting on the
learner's device.

New subsystem, no reference equivalent. The reference (and ``act_mode=
"local"``) runs one jitted policy forward per worker process on host CPU —
acting throughput scales only with host cores, and every worker acts on
stale broadcast weights. SEED RL (Espeholt et al. 1910.06591) and the
Podracer/Sebulba split (Hessel et al. 2104.06272) move inference onto the
accelerator behind a batching server; workers become thin env-steppers.

Design:

- a ZMQ ROUTER (``transport.Router``) bound next to the learner collects
  ``ObsRequest`` frames (one per worker tick: the tick's observations and
  episode-first flags — the recurrent carry does NOT ride the request);
- requests accumulate until ``Config.inference_batch`` observation rows are
  pending or the oldest request is ``Config.inference_flush_us`` old, then
  ONE jitted act step runs over padded batch slots on the learner's device.
  Padding comes from a power-of-two **bucket ladder**
  (``Config.inference_buckets``): each flush dispatches the smallest
  pre-warmed bucket program covering its rows, so small flushes stop paying
  the full padded step; every bucket compiles before the socket binds, so
  the recompile ratchet (``inference-xla-recompiles``) stays at zero.
  ``inference_buckets = 0`` keeps the single fixed
  ``pad_rows = max(inference_batch, worker_num_envs)`` shape bit-for-bit
  (the A/B baseline);
- the **serving fast path** (tpu_rl.models.quant) composes here: params are
  cast to ``Config.inference_dtype`` once at ``set_params`` time and
  dequantized inside the jitted step (fewer HBM bytes per flush), and
  ``Config.act_kernel = "pallas"`` swaps the act computation for the fused
  torso->LSTM->head kernel (tpu_rl.ops.pallas_act) where supported;
- the recurrent carry (h/c) lives server-side per worker-env slot, zeroed
  where the request flags an episode first — workers never maintain or ship
  acting state. For ``store_carry`` families (LSTM) the *reply* carries the
  pre-step carry rows, because the learner trains from them and they must
  reach the RolloutBatch the worker publishes;
- params are swapped in-process by the learner (``set_params`` after every
  update): remote acting is ZERO-staleness — no model broadcast lag, no
  codec, no wire copy. (The model PUB channel stays up regardless: it feeds
  the worker's local-fallback path and any late local-mode joiners.)

The service runs as a daemon thread inside the learner process so the param
handoff is a pointer swap. It is transport-complete on its own (tests run it
against synthetic Dealer clients without a learner).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from tpu_rl.config import Config
from tpu_rl.runtime.protocol import Protocol
from tpu_rl.runtime.transport import Router
from tpu_rl.utils.timer import ExecutionTimer


class _ClientState:
    """Per-DEALER-identity acting state: the env-slot carries (HOST numpy
    rows — see ``_flush``) and the row count the client established on
    first contact."""

    __slots__ = ("n", "h", "c")

    def __init__(self, n: int, h, c):
        self.n = n
        self.h = h
        self.c = c


class _Pending:
    __slots__ = ("identity", "seq", "obs", "first", "arrived")

    def __init__(self, identity: bytes, seq: int, obs, first, arrived: float):
        self.identity = identity
        self.seq = seq
        self.obs = obs
        self.first = first
        self.arrived = arrived


class InferenceService:
    """Batched acting server. ``start()`` spawns the serve thread;
    ``set_params`` swaps the policy in-process (zero staleness);
    ``close()`` shuts the thread down and releases the socket.

    ``timer`` (optional, shared with the learner's ``ExecutionTimer``)
    receives ``inference-batch-size`` / ``inference-wait-rows`` gauges and
    the ``inference-step-time`` span, so the service shows up on the same
    tensorboard dashboards as the learner hot loop.
    """

    def __init__(
        self,
        cfg: Config,
        family,
        params,
        port: int,
        ip: str = "*",
        timer: ExecutionTimer | None = None,
        seed: int = 0,
        version: int = -1,
    ):
        self.cfg = cfg
        self.family = family
        self._params = params
        # Policy version of the params currently served (the learner update
        # index). Echoed in every Act reply ("ver") so remote-acting workers
        # can tag their rollouts for the staleness histograms (tpu_rl.obs).
        self._version = version
        self.addr = (ip, port)
        self.timer = timer or ExecutionTimer()
        self.seed = seed
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._ready = threading.Event()  # set once compiled and serving
        self._lock = threading.Lock()  # guards the params slot
        self.clients: dict[bytes, _ClientState] = {}
        # observability counters
        self.n_requests = 0
        self.n_replies = 0
        self.n_batches = 0
        self.n_flush_full = 0
        self.n_flush_deadline = 0
        self.n_rejected_payload = 0
        # Per-bucket flush counts {bucket_rows: n} — the serving fast path's
        # dispatch histogram source (emitters replay deltas into the
        # inference-bucket-rows registry histogram).
        self.n_flush_bucket: dict[int, int] = {}
        self.error: BaseException | None = None
        # Live perf accounting for the act step (tpu_rl.obs.perf): FLOPs
        # per flushed batch + recompile watch. Built by the serve thread iff
        # telemetry is on; the learner's _emit_telemetry reads it.
        # One tracker per bucket program (each bucket is its own jit, so
        # each _JitWatch sees exactly its one expected compile); ``perf``
        # stays the largest bucket's tracker — the shape whose FLOPs defines
        # the headline MFU, and the only tracker in the single-bucket
        # baseline.
        self.perf = None
        self.perf_buckets: dict[int, object] = {}
        # Bucket ladder actually compiled (set by the serve thread) and the
        # served param-tree footprint (inference-param-bytes gauge).
        self.buckets: list[int] = []
        self.param_bytes = 0
        # Per-bucket flush counts already replayed into the registry
        # histogram (publish_serving_metrics delta bookkeeping).
        self._hist_emitted: dict[int, int] = {}
        # Goodput ledger for the SERVE thread (tpu_rl.obs.goodput), built in
        # _warm iff telemetry is on. Its own thread-lane: inference wait /
        # flush time must not double into the owning learner's ledger.
        # Published by whoever owns the registry (learner _emit_telemetry or
        # fleet.replica_main).
        self.ledger = None
        self._jnp = None  # bound by the serve thread (deferred jax import)
        # Service-level fault injection (tpu_rl.chaos): stall:inference
        # sleeps before a batch flush, refuse:inference swallows replies so
        # clients time out — exercising the worker fallback + re-probe
        # path. None unless cfg.chaos_spec names this service.
        self.chaos = None
        if getattr(cfg, "chaos_spec", None):
            from tpu_rl.chaos import maybe_service_chaos

            self.chaos = maybe_service_chaos(cfg)

    # --------------------------------------------------------------- control
    def start(self) -> "InferenceService":
        self._thread = threading.Thread(
            target=self._serve, name="inference-service", daemon=True
        )
        self._thread.start()
        return self

    def wait_ready(self, timeout: float = 60.0) -> bool:
        """Block until the act program is compiled and the socket is bound
        (first-request latency then excludes the XLA compile)."""
        return self._ready.wait(timeout)

    def _quantize(self, params):
        """Cast to the serving precision (``Config.inference_dtype``) —
        idempotent, so re-applied frames never double-scale. EVERY mode then
        commits the tree to the default device: the bucket jits have no
        in_shardings, so their cache keys on the param placement, and swap
        sources disagree about it — wire-decoded HOST trees (fleet replicas
        off the model broadcast) vs the learner's in-process trees carrying
        the train step's NamedSharding. Either one, unpinned, lands in a
        fresh jit cache entry vs the warmup trace — a real executable build
        on the serve path and a false positive on the recompile ratchet.
        (The GSPMD replica path is placement-insensitive — its jits pin
        explicit in_shardings — so the committed copy is just as correct
        there.) Boot params pass through this same gate at serve start, so
        warmup and swaps agree by construction."""
        import jax

        mode = getattr(self.cfg, "inference_dtype", "f32")
        if mode != "f32":
            from tpu_rl.models.quant import quantize_tree

            params = quantize_tree(params, mode)
        return jax.device_put(params, jax.devices()[0])

    def set_params(self, params, version: int = -1) -> None:
        """In-process param swap from the learner — quantize to the serving
        dtype OUTSIDE the lock, then one reference assignment of the device
        pytree (the swap itself stays atomic and copy-free). The NEXT
        flushed batch acts with the new weights, and replies echo the new
        ``version``."""
        params = self._quantize(params)
        with self._lock:
            self._params = params
            self._version = version

    def close(self, timeout: float = 10.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    @property
    def version(self) -> int:
        """Policy version currently served (the update index echoed in every
        Act reply)."""
        with self._lock:
            return self._version

    # ----------------------------------------------------------------- serve
    def _serve(self) -> None:
        import jax
        import jax.numpy as jnp

        self._jnp = jnp
        # Boot params enter through the same quantization gate as swaps
        # (idempotent, so a set_params that already ran is a no-op cast).
        with self._lock:
            self._params = self._quantize(self._params)
        steps, buckets = self._build_step(jax, jnp)
        self.buckets = list(buckets)
        router = None
        try:
            self._warm(jax, jnp, steps, buckets)
            router = Router(*self.addr, bind=True)
            key = jax.random.key(self.seed * 7919 + 17)
            self._ready.set()
            self._loop(jax, router, steps, buckets, key)
        except BaseException as e:  # noqa: BLE001 — surfaced via .error
            self.error = e
            self._ready.set()  # never leave wait_ready() hanging
            raise
        finally:
            if router is not None:
                router.close()

    def _step_fn(self, jnp, n_devices: int = 1):
        """The pure padded act program (shared by every jit variant;
        ``n_devices`` is the width of the mesh it will be jitted over).
        Serving-dtype params are dequantized INSIDE the program (the
        compiled step reads the narrow bytes from HBM and widens on chip);
        the act computation itself is the ``Config.act_kernel`` dispatch."""
        from tpu_rl.models.quant import dequantize_tree, make_act_fn

        act = make_act_fn(self.cfg, self.family, n_devices)

        def _step(params, obs, h, c, first, key):
            params = dequantize_tree(params)
            # Zero the carry rows whose env just reset (server-side episode
            # seam — the request's `first` flag is the only state the worker
            # contributes). The zeroed PRE-step carry is what local workers
            # store into the RolloutBatch, so it is returned alongside the
            # post-step carry.
            keep = (first < 0.5)[:, None]
            h = jnp.where(keep, h, 0.0)
            c = jnp.where(keep, c, 0.0)
            a, logits, log_prob, h2, c2 = act(params, obs, h, c, key)
            return a, logits, log_prob, h, c, h2, c2

        return _step

    def _bucket_ladder(self) -> list[int]:
        """Padded-batch shapes to pre-compile, ascending. ``inference_buckets
        = 0`` (default) reproduces the legacy single fixed shape
        ``max(inference_batch, worker_num_envs)`` bit-for-bit; > 0 is the
        power-of-two ladder from that floor up to pad_rows, so a flush of r
        rows dispatches the smallest covering program instead of always
        paying the largest."""
        cfg = self.cfg
        pad_rows = max(cfg.inference_batch, cfg.worker_num_envs)
        floor = int(getattr(cfg, "inference_buckets", 0))
        if floor <= 0 or floor >= pad_rows:
            return [pad_rows]
        b = 1
        while b < floor:
            b *= 2
        ladder = []
        while b < pad_rows:
            ladder.append(b)
            b *= 2
        ladder.append(pad_rows)
        return ladder

    def _build_step(self, jax, jnp):
        """Jit the padded act program, once per bucket shape; ->
        (steps: {bucket_rows: jitted step}, buckets ascending). Each bucket
        is a SEPARATE ``jax.jit`` (fresh closure) so every program carries
        its own dispatch cache — the per-bucket PerfTracker's recompile
        watch then expects exactly one compile each. Overridden by the fleet
        replica (tpu_rl.fleet) to apply GSPMD batch sharding and
        mesh-divisible bucket rounding."""
        buckets = self._bucket_ladder()
        steps = {rows: jax.jit(self._step_fn(jnp)) for rows in buckets}
        return steps, buckets

    def _warm(self, jax, jnp, steps, buckets) -> None:
        """Compile EVERY bucket shape BEFORE binding the socket: the first
        real request must never eat an XLA compile inside the workers'
        inference_timeout_ms window, at any flush size."""
        hw, cw = self.family.carry_widths
        obs_dim = int(self.cfg.obs_shape[0])
        with self._lock:
            params = self._params
        telemetry = getattr(self.cfg, "telemetry_enabled", False)
        if telemetry:
            from tpu_rl.obs.goodput import GoodputLedger
            from tpu_rl.models.quant import tree_bytes

            self.ledger = GoodputLedger("inference")
            self.param_bytes = tree_bytes(params)
        for rows in buckets:
            step = steps[rows]
            # HOST zeros, matching the arg kinds `_flush` passes at runtime
            # (numpy staging buffers): host and device operands land in
            # DIFFERENT jit cache entries even at identical avals, so
            # warming with device arrays would make the first real flush
            # count as a recompile.
            zeros = (
                np.zeros((rows, obs_dim), np.float32),
                np.zeros((rows, hw), np.float32),
                np.zeros((rows, cw), np.float32),
                np.zeros((rows,), np.float32),
            )
            if telemetry:
                from tpu_rl.obs.perf import PerfTracker

                tracker = PerfTracker()
                # One-time cost analysis at this bucket's padded shape —
                # the only shape its program ever dispatches, so a later
                # cache miss is a real drift signal
                # (inference-xla-recompiles sums the per-bucket watches).
                tracker.capture(
                    step, params, *zeros, jax.random.key(self.seed)
                )
                self.perf_buckets[rows] = tracker
            jax.block_until_ready(
                step(params, *zeros, jax.random.key(self.seed))
            )
        if telemetry:
            self.perf = self.perf_buckets[buckets[-1]]

    @property
    def recompiles(self) -> int:
        """Act-program recompiles after warmup, summed over every bucket
        program — the PR 11 ratchet (and the loadgen smoke's
        ``counter:inference-xla-recompiles==0`` SLO source). 0 when
        telemetry is off (no watches installed)."""
        return sum(t.recompiles for t in self.perf_buckets.values())

    def publish_serving_metrics(self, registry) -> None:
        """Replay the serving fast-path observables into a MetricsRegistry —
        called by whoever owns the registry (the learner's telemetry emit or
        ``fleet.replica_main``). Cumulative counters use set_total; the
        bucket histogram replays per-bucket flush-count DELTAS so repeated
        calls never double-observe."""
        registry.counter("inference-xla-recompiles").set_total(
            self.recompiles
        )
        registry.gauge("inference-param-bytes").set(self.param_bytes)
        hist = registry.histogram("inference-bucket-rows")
        for rows, n in list(self.n_flush_bucket.items()):
            registry.counter(
                "inference-bucket-flushes", labels={"rows": str(rows)}
            ).set_total(n)
            prev = self._hist_emitted.get(rows, 0)
            if n > prev:
                hist.observe_n(rows, n - prev)
                self._hist_emitted[rows] = n

    def _loop(self, jax, router, steps, buckets, key) -> None:
        """Max-batch-or-deadline dynamic batching (the PR 2 semantics): a
        flush dispatches when ``inference_batch`` rows are pending or the
        oldest request is ``inference_flush_us`` old — into the smallest
        covering bucket program. The fleet replica overrides this with
        continuous batching."""
        from bisect import bisect_left

        cfg = self.cfg
        jnp = self._jnp
        pad_rows = buckets[-1]  # chunk capacity = the largest program
        store_carry = self.family.store_carry
        pending: list[_Pending] = []
        pending_rows = 0
        flush_s = cfg.inference_flush_us / 1e6
        ledger = self.ledger
        if ledger is not None:
            from tpu_rl.obs.goodput import COMPUTE, IDLE, QUEUE_WAIT, WIRE

        while not self._stop.is_set():
            # Bounded poll: until the flush deadline when requests are
            # pending, a housekeeping tick otherwise.
            if pending:
                budget = flush_s - (time.perf_counter() - pending[0].arrived)
                timeout_ms = max(0, int(budget * 1e3))
            else:
                timeout_ms = 20
            t_recv = time.perf_counter()
            got = router.recv(timeout_ms=timeout_ms)
            if ledger is not None:
                # Holding a partial batch for the deadline is queue-wait; a
                # bare poll that delivered a request is wire; a bare timeout
                # is idle.
                if pending:
                    recv_bucket = QUEUE_WAIT
                elif got is not None:
                    recv_bucket = WIRE
                else:
                    recv_bucket = IDLE
                ledger.add(recv_bucket, time.perf_counter() - t_recv)
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
            full = pending_rows >= cfg.inference_batch
            expired = (
                time.perf_counter() - pending[0].arrived >= flush_s
            )
            if not (full or expired):
                continue
            self.n_flush_full += 1 if full else 0
            self.n_flush_deadline += 0 if full else 1
            # Flush whole-client chunks of at most pad_rows rows; a
            # burst larger than one padded program drains over several
            # back-to-back dispatches.
            while pending:
                chunk, rows = [], 0
                while pending and rows + pending[0].obs.shape[0] <= pad_rows:
                    req = pending.pop(0)
                    chunk.append(req)
                    rows += req.obs.shape[0]
                pending_rows -= rows
                bucket = buckets[bisect_left(buckets, rows)]
                key, sub = jax.random.split(key)
                t_fl = time.perf_counter()
                self._flush(
                    router, steps[bucket], chunk, rows, bucket, sub,
                    store_carry, jnp,
                )
                if ledger is not None:
                    ledger.add(COMPUTE, time.perf_counter() - t_fl)
                if rows < cfg.inference_batch:
                    break  # partial tail came from the deadline, done

    # ---------------------------------------------------------------- ingest
    def _ingest(self, identity: bytes, proto: Protocol, payload
                ) -> _Pending | None:
        """Validate one request; establish the client's carry slots on first
        contact. Malformed-but-decodable payloads are dropped (counted on the
        router's reject counter semantics: a bad client must not kill the
        fleet's acting path)."""
        if proto != Protocol.ObsRequest or not isinstance(payload, dict):
            self.n_rejected_payload += 1
            return None
        try:
            obs = np.asarray(payload["obs"], np.float32)
            first = np.asarray(payload["first"], np.float32).reshape(-1)
            seq = int(payload["seq"])
        except (KeyError, TypeError, ValueError):
            self.n_rejected_payload += 1
            return None
        if obs.ndim != 2 or obs.shape[0] != first.shape[0]:
            self.n_rejected_payload += 1
            return None
        self.n_requests += 1
        client = self.clients.get(identity)
        if client is None or client.n != obs.shape[0]:
            hw, cw = self.family.carry_widths
            n = obs.shape[0]
            client = _ClientState(
                n,
                np.zeros((n, hw), np.float32),
                np.zeros((n, cw), np.float32),
            )
            self.clients[identity] = client
        return _Pending(identity, seq, obs, first, time.perf_counter())

    # ----------------------------------------------------------------- flush
    def _flush(self, router, step, chunk, rows, pad_rows, key,
               store_carry, jnp) -> None:
        if self.chaos is not None:
            self.chaos.maybe_stall()
        t0 = time.perf_counter()
        # Shape-stable staging: obs/first/h/c are built as HOST buffers at
        # exactly the bucket's padded shape, so the ONLY device programs a
        # flush ever runs are the pre-warmed bucket jits. Gathering carries
        # with jnp.concatenate over per-client device slices would compile
        # a fresh concat executable for every novel chunk composition
        # (20ms+ each, unbounded combos under open-loop load) — a hidden
        # recompile the bucket ratchet exists to forbid.
        obs = np.zeros((pad_rows, chunk[0].obs.shape[1]), np.float32)
        first = np.ones((pad_rows,), np.float32)  # pad slots: reset carry
        hw, cw = self.family.carry_widths
        h = np.zeros((pad_rows, hw), np.float32)
        c = np.zeros((pad_rows, cw), np.float32)
        off = 0
        offsets = []
        for req in chunk:
            n = req.obs.shape[0]
            obs[off:off + n] = req.obs
            first[off:off + n] = req.first
            client = self.clients[req.identity]
            h[off:off + n] = client.h
            c[off:off + n] = client.c
            offsets.append(off)
            off += n
        with self._lock:
            params = self._params
            version = self._version
        a, logits, log_prob, h_pre, c_pre, h2, c2 = step(
            params, obs, h, c, first, key
        )
        # One host transfer for the whole batch; per-client row slices view it.
        a_np = np.asarray(a)
        logits_np = np.asarray(logits)
        lp_np = np.asarray(log_prob)
        h2_np = np.asarray(h2)
        c2_np = np.asarray(c2)
        h_pre_np = np.asarray(h_pre) if store_carry else None
        c_pre_np = np.asarray(c_pre) if store_carry else None
        for req, off in zip(chunk, offsets, strict=True):
            n = req.obs.shape[0]
            client = self.clients[req.identity]
            client.h = h2_np[off:off + n]
            client.c = c2_np[off:off + n]
            reply = {
                "seq": req.seq,
                "act": a_np[off:off + n],
                "logits": logits_np[off:off + n],
                "log_prob": lp_np[off:off + n],
                # Policy version these actions were sampled with — the
                # worker echoes it into the published RolloutBatch.
                "ver": version,
            }
            if store_carry:
                reply["hx"] = h_pre_np[off:off + n]
                reply["cx"] = c_pre_np[off:off + n]
            if self.chaos is not None and self.chaos.refuse():
                # Swallowed reply: the client burns a timeout and retries /
                # falls back. n_replies stays honest — it counts replies
                # actually sent. The carry above already advanced, the same
                # smudge a genuinely lost reply leaves (see InferenceClient).
                continue
            router.send(req.identity, Protocol.Act, reply)
            self.n_replies += 1
        self.n_batches += 1
        self.timer.record_gauge("inference-batch-size", rows)
        # ``pad_rows`` here is the dispatched bucket's padded shape: the
        # per-bucket flush count feeds the inference-bucket-rows histogram
        # (emitters replay the deltas) and the per-bucket FLOPs tracker
        # keeps MFU honest at every shape.
        self.n_flush_bucket[pad_rows] = self.n_flush_bucket.get(pad_rows, 0) + 1
        flush_secs = time.perf_counter() - t0
        self.timer.record("inference-step-time", flush_secs)
        tracker = self.perf_buckets.get(pad_rows)
        if tracker is not None:
            tracker.note(flush_secs)


class InferenceClient:
    """Worker-side remote-acting client: one in-flight request per tick
    (send then timed receive), correlated by a monotonically increasing
    ``seq`` echo — stale replies (a retry's ghost) are skipped by seq.

    ``act`` returns the reply payload dict, or None once
    ``Config.inference_retries`` retries have all timed out
    (``Config.inference_timeout_ms`` each) — the caller's cue to fall back
    to local acting. Retries resend the same seq: if the server actually
    served the lost reply, its carry advanced once more than the episode —
    a policy-lag-sized smudge on a fault path the IS corrections absorb.
    """

    def __init__(
        self,
        cfg: Config,
        ip: str,
        port: int,
        wid: int = 0,
        identity: bytes | None = None,
        timer: ExecutionTimer | None = None,
    ):
        import uuid

        self.cfg = cfg
        self.wid = wid
        self.timer = timer
        self.seq = 0
        self.n_timeouts = 0
        # Identity must be unique per socket across worker restarts: a
        # restarted worker reusing a dead identity would inherit the old
        # carry rows AND zmq may still route the dead peer's queue.
        from tpu_rl.runtime.transport import Dealer

        self.dealer = Dealer(
            ip, port,
            identity=identity or f"w{wid}-{uuid.uuid4().hex[:8]}".encode(),
        )

    @property
    def n_rejected(self) -> int:
        """Corrupt/foreign replies dropped by the DEALER — surfaced so the
        worker's stat dict covers this receive channel too, not just the
        model SUB."""
        return self.dealer.n_rejected

    def act(
        self,
        obs: np.ndarray,
        first: np.ndarray,
        retries: int | None = None,
    ) -> dict | None:
        """``retries`` overrides ``Config.inference_retries`` for this call
        (the worker's re-probe uses 0: one cheap attempt, not a full retry
        burst against a possibly-still-dead server)."""
        cfg = self.cfg
        attempts = (
            cfg.inference_retries if retries is None else int(retries)
        ) + 1
        req = {"wid": self.wid, "seq": self.seq, "obs": obs, "first": first}
        t0 = time.perf_counter()
        try:
            for _attempt in range(attempts):
                self.dealer.send(Protocol.ObsRequest, req)
                deadline = time.perf_counter() + cfg.inference_timeout_ms / 1e3
                while True:
                    left_ms = int((deadline - time.perf_counter()) * 1e3)
                    if left_ms <= 0:
                        break
                    got = self.dealer.recv(timeout_ms=left_ms)
                    if got is None:
                        continue  # rejected frame burned some budget; keep waiting
                    proto, payload = got
                    if proto != Protocol.Act or not isinstance(payload, dict):
                        continue
                    if payload.get("seq") != self.seq:
                        continue  # stale ghost from an earlier retry
                    if self.timer is not None:
                        self.timer.record(
                            "inference-rtt", time.perf_counter() - t0
                        )
                    return payload
                self.n_timeouts += 1
            return None
        finally:
            self.seq += 1

    def close(self) -> None:
        self.dealer.close()
