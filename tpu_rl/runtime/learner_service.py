"""Learner process: the only process that owns the TPU.

Capability parity with the reference learner
(``/root/reference/agents/learner.py:39-305`` + the per-algo loops in
``agents/learner_module/*/learning.py``): sample trajectory batches out of
shared memory, run the algorithm's update, broadcast fresh policy weights to
every worker, log losses/timers/fleet-reward to tensorboard, checkpoint every
``model_save_interval`` updates, heartbeat.

TPU-first redesign:
- the six per-algo asyncio coroutines collapse into ONE loop around the
  algorithm's pure jitted ``train_step`` (the registry supplies it);
- when ``cfg.mesh_data > 1`` the step is compiled with GSPMD shardings over
  the data mesh (``tpu_rl.parallel.dp``) — XLA inserts the ICI gradient
  all-reduce the reference has no equivalent of;
- the host data plane is PIPELINED (``cfg.learner_prefetch``): a feeder
  thread samples shm, assembles the batch, and eagerly places it on device
  with the step's sharding, so the next dispatch's shm copy + H2D transfer
  overlaps the current ``train_step`` (``tpu_rl/data/prefetch.py``; the
  Podracer overlap, Hessel et al. 2104.06272). ``learner_prefetch=0``
  restores the serial feed, an A/B baseline no cell measures (ROADMAP D2);
- weight broadcast is an ASYNC snapshot of the actor tree only — ONE device
  program copies the whole tree (``snapshot_tree``), and the D2H transfer,
  its wait and the ZMQ send run on a publisher thread — throttled by
  ``publish_interval``, so the loop dispatches ahead of the chip and host
  transfer never stalls the device pipeline (SURVEY.md §7 hard-parts);
- off-policy learners honor ``cfg.max_update_data_ratio`` (update:data
  ratio gate — the replay learner waits for fresh transitions instead of
  free-running against the ring, CLUSTER_R5_SAC.md);
- checkpoints carry params + optimizer state + update counter (orbax).
"""

from __future__ import annotations

import functools
import os
import threading
import time
from collections import deque

import numpy as np

from tpu_rl.config import Config, is_off_policy
from tpu_rl.data.layout import BatchLayout
from tpu_rl.data.prefetch import (
    PrefetchPipeline,
    SynchronousFeed,
    UpdateRatioGate,
)
from tpu_rl.data.shm_ring import ShmHandles, make_store
from tpu_rl.runtime.mailbox import (
    SLOT_ACTIVATE,
    SLOT_FORWARD_BYTES,
    SLOT_GAME_COUNT,
    SLOT_JOIN_REQ,
    SLOT_MEAN_REW,
    SLOT_MODEL_LOADS,
    SLOT_REJECTED,
    SLOT_RELAY_DROPPED,
    SLOT_RUN_EPOCH,
)
from tpu_rl.runtime.manager import STAT_WINDOW
from tpu_rl.runtime.protocol import Protocol, fits_frame, frame_args
from tpu_rl.runtime.transport import MODEL_HWM, Pub, make_data_pub
from tpu_rl.utils.metrics import LearnerLogger, make_writer
from tpu_rl.utils.timer import ExecutionTimer


# Updates dispatched and not yet finished that the loop allows itself, the one
# the chip is running included: it asks the feed for the next batch only once
# fewer are in flight. One running and two behind it keep the chip fed over
# any stall of this thread short of two updates; every update further ahead
# would only hold one more placed batch in device memory for as long — a
# batch the feed can keep, at no cost, in the store it leases from.
RUN_AHEAD = 3


def _crossed(prev: int, cur: int, interval: int) -> bool:
    """Did the counter cross a multiple of ``interval`` moving prev -> cur?
    Equivalent to ``cur % interval == 0`` when steps are 1; with chained
    dispatch the counter advances K per iteration and plain modulo would
    skip firings whose multiple falls inside the jump."""
    return cur // interval > prev // interval


@functools.cache
def _snapshot_program(recycle: bool):
    import jax
    import jax.numpy as jnp

    def snapshot(tree, into=None):
        return jax.tree.map(jnp.copy, tree)

    if recycle:  # ``into`` is donated: each output is written over its leaf
        return jax.jit(snapshot, donate_argnums=1, keep_unused=True)
    return jax.jit(snapshot)


def snapshot_tree(tree, into=None):
    """Donation-proof device copy of a whole pytree in ONE program launch.

    The outputs share no buffer with the inputs (``copy`` is not forwarded
    through jit), so the next ``train_step``'s donation of the state cannot
    invalidate the snapshot. ``into`` is an earlier snapshot of the same tree
    that nobody needs any more: it is donated, and the new snapshot is
    written into its buffers instead of newly allocated ones. jit keeps one
    executable per tree structure and placement: every algorithm's actor
    tree, the replicated mesh tree and a multihost global tree each compile
    their own on first use. (One ``jnp.copy`` per leaf is one launch per
    leaf: for the transformer's 56-leaf actor more host time than the chip
    needs for the update, PERF.md §6.)"""
    if into is None:
        return _snapshot_program(False)(tree)
    return _snapshot_program(True)(tree, into)


class AsyncPublisher:
    """Weight broadcast off the learner's critical path.

    ``publish(actor)`` runs one async dispatch on the caller and nothing
    else: ``snapshot_tree``, the device-side copy of the actor tree. The D2H
    transfer is started on this thread, for the snapshot it actually takes
    from the slot (a superseded one is never transferred). The blocking
    ``jax.device_get`` — which must wait for the update that produced the
    weights AND the transfer — plus framing + ZMQ send happen here too,
    overlapped with the learner's next dispatches; the device snapshot is
    let go as soon as it is on the host. The host tree is this thread's and
    is never written: the frame's array parts are its leaves' own buffers
    (``protocol.PARTS_KINDS``), and zmq holds them until they are on the wire.

    The loop dispatches ahead of the chip, and a buffer is allocated at
    dispatch: a snapshot still in the slot when the next one is made is
    superseded, and the new one is written into its buffers, so at most two
    device snapshots are alive (the slot's and the publisher's) however far
    ahead the loop runs. That makes two programs, with and without a
    snapshot to recycle; the first call for each placement of the tree (the
    fresh state's, then the step outputs') runs both, so neither compiles
    later in the run.

    ``book`` is the owner's memory book (``utils.platform.MemoryBook``): a
    snapshot newly allocated counts one ``publish-snapshot`` up, the one this
    thread has brought to the host counts one down, and the books are
    stamped there (``publish-d2h``).

    Latest-wins slot (not a queue): under backpressure workers want the
    NEWEST weights, and per-snapshot order is irrelevant once superseded.
    The ZMQ ``Pub`` is used from this thread only after construction
    (sockets are single-threaded); ``close()`` flushes a pending snapshot
    so the final weights of a run still reach the fleet, then joins.
    A send failure re-raises out of the next ``publish()``.
    """

    def __init__(self, pub: Pub, tracer, book=None):
        self._pub = pub
        self._span = tracer.span  # lane "publisher"
        self._book = book
        self._cond = threading.Condition()
        self._pending = None
        self._error: BaseException | None = None
        self._closed = False
        self.n_snapshots = 0  # made on the caller's lane
        self.n_sent = 0  # taken from the slot and sent; the rest were superseded
        self.n_bytes = 0  # handed to the socket, all parts of every frame sent
        self._to_warm = 2  # placements whose recycling program has yet to run
        self._thread = threading.Thread(
            target=self._run, name="learner-publish", daemon=True
        )
        self._thread.start()

    def publish(self, actor, ver: int = -1, epoch: int = 0) -> None:
        if self._error is not None:
            raise self._error
        with self._cond:  # a snapshot still here will never be sent
            superseded, self._pending = self._pending, None
        if superseded is not None:
            snap = snapshot_tree(actor, into=superseded[0])
        else:
            snap = snapshot_tree(actor)
            if self._book is not None:
                self._book.hold("publish-snapshot")
            if self._to_warm:
                self._to_warm -= 1
                snap = snapshot_tree(actor, into=snap)
        self.n_snapshots += 1
        with self._cond:
            self._pending = (snap, ver, epoch)  # latest wins
            self._cond.notify()

    def _run(self) -> None:
        import jax

        while True:
            with self._cond:
                while self._pending is None and not self._closed:
                    self._cond.wait(timeout=0.1)
                if self._pending is None:  # closed and flushed
                    return
                (snap, ver, epoch), self._pending = self._pending, None
            try:
                # The transfer's start, the wait for the update that produced
                # the weights, and for the transfer.
                with self._span("publish-d2h", tid="publisher"):
                    actor = jax.device_get(snap)
                snap = None  # only the host tree is needed through the send
                if self._book is not None:
                    self._book.drop("publish-snapshot")
                    self._book.stamp("publish-d2h", ver, tid="publisher")
                # "ver" is the learner update index that produced these
                # weights: workers echo it through their rollouts so storage
                # can measure per-worker policy staleness (tpu_rl.obs).
                # "epoch" is the run epoch (bumped on every checkpoint
                # resume): workers adopt and echo it so storage can fence
                # out frames acted under a pre-crash learner incarnation.
                # The span's args (bytes / parts / codec of the frame) are
                # known once it is framed: filled in before the span closes.
                args: dict = {}
                with self._span("publish-send", tid="publisher", args=args):
                    sent = self._pub.send(
                        Protocol.Model,
                        {
                            "actor": actor,
                            "ver": ver,
                            "epoch": epoch,
                            # Clock-sync echo origin (t0): workers pair this
                            # with their receive time and ship both back on
                            # their Telemetry snapshots, closing the NTP
                            # round trip at the storage edge
                            # (tpu_rl.obs.clocksync).
                            "t_tx": time.time_ns(),
                        },
                    )
                    if sent is not None:  # chaos dropped it otherwise
                        args.update(frame_args(sent))
                with self._cond:
                    self.n_sent += 1
                    self.n_bytes += args.get("bytes", 0)
            except BaseException as e:  # noqa: BLE001 — surfaces in publish()
                self._error = e
                return

    def close(self, timeout: float = 10.0) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify()
        self._thread.join(timeout=timeout)


class LearnerService:
    def __init__(
        self,
        cfg: Config,
        handles: ShmHandles,
        model_port: int,
        stat_array=None,
        stop_event=None,
        heartbeat=None,
        max_updates: int | None = None,
        publish_interval: int = 1,
        seed: int = 0,
        inference_port: int | None = None,
        stat_port: int | None = None,
    ):
        self.cfg = cfg
        self.handles = handles
        self.model_port = model_port
        self.stat_array = stat_array
        self.stop_event = stop_event
        self.heartbeat = heartbeat
        self.max_updates = max_updates
        self.publish_interval = publish_interval
        self.seed = seed
        self.inference_port = inference_port
        # Stat-channel port (the one storage SUB-binds): the learner's own
        # Telemetry snapshots ship there over a tiny local PUB — storage is
        # colocated (same runner host), so 127.0.0.1 always reaches it.
        self.stat_port = stat_port
        self._publisher: AsyncPublisher | None = None
        self._inference = None  # InferenceService when act_mode="remote"
        self._tracer = None  # TraceRecorder of this process, set by run()
        self._book = None  # its MemoryBook (the bring-up record's), likewise
        self._perf = None  # PerfTracker when telemetry is on
        self._prof_capture = None  # ProfilerCapture when any capture path is
        # Idle-rebroadcast odometer: model publishes fired from the starving
        # branch (no fresh update) so late-joining or restarted workers stop
        # acting on a stale/random policy (chaos-plane hardening).
        self.n_rebroadcasts = 0
        # Run epoch: 0 for a fresh run, (checkpointed epoch + 1) on every
        # resume. Stamped on Model broadcasts/telemetry and echoed by
        # workers; storage fences stale-epoch frames on it.
        self.run_epoch = 0
        # Publishes triggered by storage's join flag (a NEW worker appeared
        # in the membership table): the joiner gets weights+ver now instead
        # of waiting out rebroadcast_idle_s.
        self.n_join_pushes = 0
        # Broadcasts not made because the actor tree cannot be framed
        # (protocol.fits_frame): such a policy is served, not shipped
        # (act_mode="remote"). None until the first publish has looked.
        self.n_publish_oversize = 0
        self._broadcast_fits: bool | None = None
        self._ckpt = None  # Checkpointer while cfg.model_dir is set
        # Self-healing plane (tpu_rl.heal): cumulative guard-skipped updates
        # (host mirror of the on-device accumulator, refreshed at the
        # loss-log cadence) and watchdog-triggered rollbacks performed.
        self.n_nonfinite_updates = 0.0
        self.n_rollbacks = 0
        # Learning-dynamics plane (tpu_rl.obs.learn): the on-device diag
        # accumulator and the per-dispatch staleness sidecar FIFO (filled on
        # the feeder thread, drained by the hot loop — same ordering as the
        # prefetch queue). Both None unless Config.learn_diag.
        self._diag = None
        self._diag_vers = None
        self.last_losses: dict = {}  # newest loss-log readback
        # Logged updates by where their books were closed: behind the next
        # dispatch (the chip at work meanwhile), or in line at the crossing,
        # by what the loop saw there.
        self.n_log_behind_dispatch = 0
        self.n_log_inline = {"save": 0, "stop": 0, "empty feed": 0}
        # Raw batches by how the store handed them to the feed: leased (views
        # of shared memory, placed from where they lie) or copied out. The
        # feed leases where it holds one raw batch at a time: on-policy,
        # unchained.
        self.n_feed = {"leased": 0, "copied": 0}
        self._leased = False

    # ------------------------------------------------------------------ run
    def run(self) -> None:
        # The process's clock from here: every second between this stamp and
        # the loop's first iteration lies under one span of the lane
        # "startup" (or, for the first broadcast, of "main").
        run_entry = time.time()
        cfg = self.cfg
        # Span tracing (tpu_rl.obs.trace): every statement of this method
        # runs inside one named span — of the lane "startup" up to the loop,
        # of the lane "main" inside it; the feeder, publisher and
        # checkpoint-writer threads have lanes of their own, compilations
        # the lane "xla" (utils.platform.CompileClock). A span is a
        # jax.profiler.TraceAnnotation (on the device trace's clock whenever
        # any capture is open), a ring entry, and — where the site names
        # them — the ExecutionTimer window and the goodput bucket. The ring
        # and its trace.json export (a thread of the recorder's own) exist
        # only with a result_dir; the annotations always do. Built first: it
        # needs no backend, only the pid.
        from tpu_rl.obs import TraceRecorder, flightrec

        tracer = self._tracer = TraceRecorder(
            capacity=cfg.trace_capacity if cfg.result_dir is not None else 0,
            pid=os.getpid(),
            role="learner",
            annotate=True,
        )
        span = tracer.span
        with span("init-multihost", tid="startup"):
            if cfg.multihost:
                # Must precede any backend use in this process; afterwards
                # jax.devices() spans every host in the slice.
                from tpu_rl.parallel.multihost import init_multihost

                init_multihost(**cfg.multihost)

        with span("imports", tid="startup"):
            import jax

            from tpu_rl.algos.registry import get_algo
            from tpu_rl.checkpoint import Checkpointer, resume_fingerprint
            from tpu_rl.utils.platform import BackendRecord

            if cfg.result_dir is not None:
                flightrec.install(
                    "learner", cfg.result_dir, tracer=tracer, cfg=cfg
                )
                tracer.start_export(os.path.join(cfg.result_dir, "trace.json"))

        with span("mesh", tid="startup"):
            layout = BatchLayout.from_config(cfg)
            store = make_store(cfg, layout, handles=self.handles)
            off_policy = is_off_policy(cfg.algo)
            rng = np.random.default_rng(self.seed)

            chain = max(1, cfg.learner_chain)
            if self.max_updates is not None and chain > self.max_updates:
                # A budget smaller than the chain would otherwise complete
                # "successfully" with ZERO updates (the pre-dispatch budget
                # check fires before the first dispatch). Clamp so a small
                # budget performs real updates; callers wanting a hard error
                # should validate their own run plans.
                print(
                    f"[learner] learner_chain {chain} exceeds max_updates "
                    f"{self.max_updates}; clamping chain to "
                    f"{max(1, self.max_updates)}", flush=True,
                )
                chain = max(1, self.max_updates)

            # Compile target meshes first: the family needs the mesh when the
            # transformer's ring/Ulysses attention is sequence-sharded, and
            # the bring-up record names the devices this learner runs on.
            mesh = None
            if cfg.mesh_seq > 1:
                from tpu_rl.parallel import make_sp_mesh

                mesh = make_sp_mesh(cfg.mesh_data, cfg.mesh_seq)
            elif cfg.mesh_data > 1 or chain > 1:
                # chain > 1 rides the same GSPMD wrapper even on one device
                # (make_mesh(1)): the chained lax.scan program is what
                # amortizes per-dispatch overhead, mesh width is orthogonal.
                from tpu_rl.parallel.mesh import make_mesh

                mesh = make_mesh(cfg.mesh_data)

        with span("backend-open", tid="startup"):
            backend = BackendRecord("learner", cfg, mesh, tracer=tracer)
            # The memory book (utils.platform.MemoryBook): the runtime's
            # books stamped at the exit of every site below where a
            # tree-sized buffer is made or let go, and the pieces this
            # program itself holds counted as it makes them. "run": before
            # anything of the learner's is allocated — what the process held
            # and had ever held when the learner got it.
            book = self._book = backend.memory
            book.stamp("run", tid="startup")
            init_key = jax.random.key(self.seed)  # the first program to run
        # Spans "family", "train-state", "step-build" of the lane "startup".
        spec = get_algo(cfg.algo)
        family, state, train_step = spec.build(
            cfg, init_key, mesh=mesh if cfg.mesh_seq > 1 else None, span=span
        )
        del init_key  # a device buffer (512 B of the chip's peak) nobody reads again

        with span("restore", tid="startup"):
            book.stamp("train-state", tid="startup")  # the build's, read on entry
            # ---- checkpoint resume (newest COMMITTED index wins) ----
            # Full-run resume: train state + update index + learner PRNG key +
            # run epoch, refused on config-fingerprint mismatch unless
            # cfg.resume_force. A torn (uncommitted) save is invisible here by
            # construction (tpu_rl/checkpoint.py's marker protocol).
            ckpt = None
            start_idx = 0
            resumed_key_data = None
            fingerprint = resume_fingerprint(cfg)
            if cfg.model_dir:
                ckpt = self._ckpt = Checkpointer(
                    cfg.model_dir,
                    cfg.algo,
                    keep=cfg.ckpt_keep,
                    async_save=cfg.ckpt_async,
                    tracer=tracer,
                    book=book,
                )
                restored = ckpt.restore_run(
                    state, fingerprint=fingerprint, force=cfg.resume_force
                )
                if restored is not None:
                    state, start_idx, meta = restored
                    self.run_epoch = int(meta.get("epoch", 0)) + 1
                    resumed_key_data = meta.get("key")
                    print(
                        f"[learner] resumed from checkpoint idx {start_idx} "
                        f"(run epoch {self.run_epoch})"
                    )
                    self._record_resume(start_idx)
            # Publish the epoch into the cross-respawn mailbox BEFORE the first
            # broadcast: storage (its mp.Array outlives child respawns) learns
            # the new fence before any worker can act on the new weights, which
            # makes stale-epoch rejection deterministic instead of a race.
            sa = self.stat_array
            if sa is not None and len(sa) > SLOT_RUN_EPOCH:
                sa[SLOT_RUN_EPOCH] = float(self.run_epoch + 1)  # 0 = unknown
            book.stamp("restore", tid="startup")

        with span("place", tid="startup"):
            # ---- compile: single-chip jit, data-parallel, or data x seq mesh ----
            # _wrap is reused by the entropy-anneal switch below, which rebuilds
            # the raw train step with the post-switch cfg and must re-apply the
            # same mesh/jit wrapping.
            self._place_global = None
            self._chain_mesh = None
            self._batch_sharding = None  # eager-placement target (prefetch feed)
            self._device = jax.devices()[0]
            if cfg.mesh_seq > 1:
                from jax.sharding import NamedSharding, PartitionSpec as P

                from tpu_rl.parallel.dp import make_sp_train_step, replicate
                from tpu_rl.parallel.sequence import DATA_AXIS, SEQ_AXIS

                def _wrap(step, wcfg):
                    return make_sp_train_step(step, mesh, wcfg)

                state = replicate(state, mesh)
                self._batch_sharding = NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS))
                self._setup_multihost_feed(self._batch_sharding)
            elif mesh is not None:
                from tpu_rl.parallel.dp import make_parallel_train_step, replicate
                from tpu_rl.parallel.mesh import batch_sharding

                if chain > 1:
                    self._chain_mesh = mesh

                def _wrap(step, wcfg):
                    return make_parallel_train_step(step, mesh, wcfg, chain=chain)

                state = replicate(state, mesh)
                if chain == 1:
                    # chain > 1 places via shard_chained_batch in _assemble;
                    # chain == 1 places eagerly against the DP batch sharding.
                    self._batch_sharding = batch_sharding(mesh)
                self._setup_multihost_feed(batch_sharding(mesh))
            else:

                def _wrap(step, wcfg):
                    return jax.jit(step, donate_argnums=(0,))

            train_step = _wrap(train_step, cfg)
            book.declare("train-state", state)
            book.hold("train-state")
            book.stamp("place", tid="startup")

        with span("wire", tid="startup"):
            # Two-phase entropy/lr anneal switch point (Config.entropy_anneal;
            # same semantics as the inline harness, examples/train_inline.py).
            # "at" is an ABSOLUTE update index — checked with >= against the
            # global counter, so a run resumed past the switch re-enters the
            # cold phase on its first update instead of undoing the anneal.
            # "frac" is relative to THIS run's max_updates budget.
            anneal = cfg.entropy_anneal
            anneal_at = None
            anneal_absolute = False
            if anneal is not None:
                if "at" in anneal:
                    anneal_at = max(1, int(anneal["at"]))
                    anneal_absolute = True
                elif self.max_updates is not None:
                    anneal_at = max(1, int(float(anneal["frac"]) * self.max_updates))
                else:
                    print(
                        "[learner] entropy_anneal uses 'frac' but the run has no "
                        "max_updates budget; anneal disabled", flush=True,
                    )

            # Fault injection (tpu_rl.chaos): delay:learner shims the model
            # broadcast sends. None unless a chaos_spec names this site.
            chaos = None
            if cfg.chaos_spec:
                from tpu_rl.chaos import maybe_transport_chaos

                chaos = maybe_transport_chaos(cfg, "learner")
            pub = Pub("*", self.model_port, bind=True, hwm=MODEL_HWM, chaos=chaos)
            # Async broadcast rides the same switch as the feed pipeline so
            # learner_prefetch=0 is a FULLY serial A/B baseline.
            self._publisher = (
                AsyncPublisher(pub, tracer, book)
                if cfg.learner_prefetch > 0
                else None
            )
            writer = make_writer(cfg.result_dir)
            logger = LearnerLogger(writer, cfg.algo)
            # Telemetry plane (tpu_rl.obs): the learner ships its own registry
            # snapshots to the storage-side aggregator over the stat channel —
            # the same port every other role's telemetry already converges on.
            # None when disabled: the hot loop then pays one `is None` check per
            # update and opens no extra socket (pinned by tests/test_obs.py).
            from tpu_rl.obs.goodput import (
                CKPT,
                COMPUTE,
                H2D,
                IDLE,
                QUEUE_WAIT,
                RECOMPILE,
                ROLLBACK,
                WIRE,
                GoodputLedger,
            )

            telem_reg = telem_pub = None
            telem_last = float("-inf")
            self._perf = None
            self.ledger = None
            # With prefetch the pop wait is residual feed latency (queue-wait);
            # the synchronous feed does the shm copy + H2D inside get(), so the
            # same span is h2d there.
            wait_bucket = QUEUE_WAIT if cfg.learner_prefetch > 0 else H2D
            if cfg.telemetry_enabled and self.stat_port is not None:
                from tpu_rl.obs import MetricsRegistry
                from tpu_rl.obs.perf import PerfTracker

                telem_reg = MetricsRegistry(role="learner")
                # Goodput ledger (tpu_rl.obs.goodput): exhaustive wall-clock
                # attribution for THIS thread only — feeder / async-ckpt-writer /
                # async-publisher lanes overlap the device step and would
                # double-count, so only "main"-lane span sites name a bucket.
                self.ledger = tracer.ledger = GoodputLedger("learner")
                # Live performance plane (tpu_rl.obs.perf): FLOPs/MFU from a
                # one-time AOT cost analysis of train_step, recompile and
                # device-memory watermarks on the emit cadence. None when
                # telemetry is off — the hot loop pays one `is None` check.
                self._perf = PerfTracker()
                # Storage telemetry hop: loopback by construction (learner and
                # storage share the host), so transport="shm"/"auto" routes it
                # through the shm channel instead of a TCP loopback socket.
                telem_pub = make_data_pub(
                    cfg, "127.0.0.1", self.stat_port, bind=False
                )
            # Profiler capture gate (tpu_rl.obs.perf.ProfilerCapture): ONE
            # serialized gate for the config window below, `kill -USR2 <pid>`
            # (mirroring the flight recorder's SIGUSR1), and the telemetry
            # server's /prof?ms=N. Its flight-recorder crash hook guarantees
            # stop_trace() on fatal exceptions, so the capture meant to explain
            # a crash is flushed instead of dying with the process.
            prof_capture = self._prof_capture = None
            if cfg.profile_dir is not None or cfg.result_dir is not None:
                from tpu_rl.obs.perf import ProfilerCapture

                prof_capture = self._prof_capture = ProfilerCapture(
                    cfg.profile_dir or os.path.join(cfg.result_dir, "prof"),
                    tracer=tracer,
                )
                prof_capture.install_sigusr2()
            # One timed window per DISPATCH; a chained dispatch carries
            # chain x (seq x batch) transitions. Kept on self: the lane gauges
            # below and chip_smoke.py read the steady-state windowed rates after
            # run() — the window excludes idle polls and dilutes the first
            # dispatch's compile across the deque.
            timer = self.timer = tracer.timer = ExecutionTimer(
                num_transition=cfg.seq_len * cfg.batch_size * chain
            )
            key = jax.random.key(self.seed + 1)
            if resumed_key_data is not None:
                # Continue the checkpointed RNG stream instead of replaying the
                # seed's: a resumed run keeps sampling fresh subkeys.
                import jax.numpy as jnp

                try:
                    key = jax.random.wrap_key_data(
                        jnp.asarray(resumed_key_data, dtype=jnp.uint32)
                    )
                except (TypeError, ValueError):
                    print(
                        "[learner] checkpointed PRNG key unreadable; keeping "
                        "the seed-derived stream", flush=True,
                    )

            def _ckpt_meta() -> dict:
                # Captures the loop's live `key` binding: the meta snapshot is
                # taken at save-call time, consistent with the state snapshot.
                return {
                    "epoch": self.run_epoch,
                    "key": np.asarray(jax.random.key_data(key)).tolist(),
                    "fingerprint": fingerprint,
                }

        with span("inference-start", tid="startup"):
            # SEED-style centralized inference (act_mode="remote"): serve
            # batched acting from THIS process on the learner's device. Params
            # reach the service as a device-side snapshot after every update —
            # zero broadcast staleness, no host copy, no wire. The service
            # shares `timer`, so inference-batch-size / inference-step-time land
            # on the learner's tensorboard alongside the hot-loop timings.
            if cfg.act_mode == "remote" and self.inference_port is not None:
                if cfg.inference_replicas > 1:
                    # Fleet mode: the in-learner service is replica 0 —
                    # continuous batching + the ver-keyed swap, so its replies
                    # respect the same version monotonicity the standalone
                    # replicas give (learner versions only ever rise, so every
                    # in-process swap applies).
                    from tpu_rl.fleet import InferenceReplica as InferenceService
                else:
                    from tpu_rl.runtime.inference_service import InferenceService

                serving = self._actor_snapshot(state)
                book.declare("inference-params", serving)
                book.hold("inference-params")
                self._inference = InferenceService(
                    cfg,
                    family,
                    serving,
                    self.inference_port,
                    timer=timer,
                    seed=self.seed,
                    version=start_idx,
                ).start()
                del serving
                self._inference.wait_ready()
            book.stamp("inference-start", tid="startup")

        # First broadcast so workers act with the resumed/initial policy
        # rather than their own random init. It answers any join request
        # already pending (a respawned learner typically finds the flag
        # raised: storage re-registered every worker while it was booting).
        with span("publish", bucket=WIRE):
            self._publish(pub, state, ver=start_idx)
            self._consume_join_flag()
        last_pub_m = time.monotonic()

        with span("feed-start", tid="startup"):
            if (
                self.max_updates is not None
                and chain > 1
                and self.max_updates % chain
            ):
                print(
                    f"[learner] max_updates {self.max_updates} is not a multiple "
                    f"of learner_chain {chain}; budget rounds DOWN to "
                    f"{self.max_updates // chain * chain} updates", flush=True,
                )
            # Self-healing plane (tpu_rl.heal): the guards already run inside
            # train_step (cfg.update_guard, folded in at make_train_step time);
            # here lives the host side — a lazy on-device accumulator over the
            # per-dispatch "nonfinite-updates" metric (one jnp add per update,
            # read back only at the loss-log cadence) plus the divergence
            # watchdog + rollback budget when enabled. The watchdog needs a
            # checkpointer to roll back to, so it stays off without model_dir.
            track_nf = cfg.update_guard
            nf_acc = 0.0  # device scalar after the first guarded dispatch
            nf_base = 0.0  # cumulative count at the last rollback (host float)
            watchdog = budget = None
            if cfg.watchdog_enabled and ckpt is not None:
                from tpu_rl.heal import DivergenceWatchdog, RollbackBudget

                watchdog = DivergenceWatchdog(
                    window=cfg.watchdog_window,
                    z_max=cfg.watchdog_z,
                    sustain=cfg.watchdog_sustain,
                    nonfinite_max=cfg.watchdog_nonfinite,
                )
                budget = RollbackBudget(
                    max_rollbacks=cfg.max_rollbacks,
                    window_s=cfg.rollback_window_s,
                )
            # Learning-dynamics plane (tpu_rl.obs.learn): fold every dispatch's
            # in-jit diag pytree into an on-device accumulator bucketed by the
            # batch's policy staleness (the per-slot version sidecar the store
            # reads back); host readback only at the loss-log cadence below.
            # Must exist BEFORE the feed: the feeder thread's _assemble_device
            # detaches the sidecar into _diag_vers.
            diag_acc = diag_vers = None
            _stale_rows = _learn_record = _publish_diag = None
            if cfg.learn_diag:
                from collections import deque as _deque

                from tpu_rl.obs.learn import (
                    DiagAccumulator,
                    host_stale_rows as _stale_rows,
                    learn_record as _learn_record,
                    publish as _publish_diag,
                )

                diag_acc = self._diag = DiagAccumulator()
                diag_vers = self._diag_vers = _deque()
            # The feed: a background prefetch pipeline (default) or the inline
            # synchronous path (learner_prefetch=0). Either way the loop below
            # pops ONE device-ready dispatch batch per iteration.
            feed = self._make_feed(store, rng, chain)
            idx = start_idx
            # The profiler window opens once and closes once: None until its
            # capture opens, True while it runs, False for the rest of the run.
            profiling = None if cfg.profile_dir is not None else False
            # A logged update's books: (its index, its scalars' handles, the
            # non-finite count's array as of it, the diag sums handed over,
            # its log-sync's span). The log-sync empties the pipeline,
            # so nothing the next dispatch does not need runs in front of
            # it: the books are set aside at the crossing and closed right
            # after that dispatch has been issued, while the chip works.
            books = None
            # One small output of each dispatched update that may not have
            # finished yet, oldest first (RUN_AHEAD).
            ahead: deque = deque()
            book.stamp("feed-start", tid="startup")

            def _stamp(site: str, update: int) -> None:
                """A stamp of the main lane: first the pieces only this
                thread can count — a batch is alive from the start of its
                placement (the feed's count) until the update it was
                dispatched into has finished; the diag sums while the
                accumulator or a logged update's books hold them."""
                while ahead and ahead[0].is_ready():
                    ahead.popleft()
                book.count("batch", feed.held() + len(ahead))
                if diag_acc is not None:
                    book.count(
                        "diag",
                        diag_acc.live + (books is not None and books[3] is not None),
                    )
                book.stamp(site, update)

            def _close_books(cause: str | None = None) -> str | None:
                """Everything a logged update's read-back owes besides the
                wait itself, in one order wherever it runs: the scalars'
                transfer, ``log-write``, ``diag-drain``, ``watchdog`` and the
                ``rollback`` it asks for. ``cause`` says why the books are
                closed in line at the crossing; None means behind the next
                dispatch. Returns "stop" (the rollback budget is spent),
                "rolled" (state, index and key are the restored ones: the
                caller starts its iteration over) or None."""
                nonlocal books, state, idx, key, nf_acc, nf_base, last_pub_m
                b_idx, b_metrics, b_nf, b_diag, sp_sync = books
                books = None
                if cause is None:
                    self.n_log_behind_dispatch += 1
                else:
                    self.n_log_inline[cause] += 1
                with span("log-write"):
                    # One transfer of handles the log-sync saw finished (it
                    # does not wait for the program now running: 1.6 ms for
                    # twelve scalars either way, PERF.md section 6, PR 44).
                    # Kept on self (harnesses read it after run()) and
                    # printed, like the colocated loop's update line.
                    scalars, nf = jax.device_get((b_metrics, b_nf))
                    del b_metrics  # freed under a span, as every array is
                    self.last_losses = {k: float(v) for k, v in scalars.items()}
                    if track_nf:
                        self.n_nonfinite_updates = float(nf)
                    # The first time, an update has just finished on the
                    # device and the start-up is over: the record keeps
                    # what the ring holds of it (a long run's ring
                    # forgets), and from here on a compilation is
                    # reported by name. A no-op ever after.
                    backend.record_startup(
                        run_entry, loop_entry, sp_sync.t0 + sp_sync.secs
                    )
                    print(
                        f"[learner] update {b_idx}  "
                        + "  ".join(
                            f"{k} {v:.4f}" for k, v in self.last_losses.items()
                        ),
                        flush=True,
                    )
                    logger.log_losses(b_idx, self.last_losses)
                    logger.log_timers(b_idx, timer)
                    self._log_fleet_stat(logger)
                    logger.flush()
                diag_doc = None
                if b_diag is not None:
                    with span("diag-drain"):
                        # The plane's ONLY readback: derive the handed-over
                        # sums into gauges + the learn.jsonl audit line.
                        diag_doc = diag_acc.read(b_diag)
                        del b_diag
                        if diag_doc is not None:
                            if telem_reg is not None:
                                _publish_diag(telem_reg, diag_doc)
                            if cfg.result_dir is not None:
                                from tpu_rl.obs.audit import append_jsonl

                                append_jsonl(
                                    cfg.result_dir,
                                    "learn.jsonl",
                                    _learn_record(b_idx, diag_doc),
                                )
                if watchdog is None:
                    return None
                with span("watchdog"):
                    tripped = self._watchdog_tripped(
                        watchdog, self.last_losses, diag_doc, nf_base
                    )
                if not tripped:
                    return None
                if budget.exhausted():
                    print(
                        f"[learner] rollback budget exhausted "
                        f"({budget.used}/{cfg.max_rollbacks} in "
                        f"{cfg.rollback_window_s:.0f}s): "
                        f"{watchdog.last_reason}; stopping cleanly", flush=True,
                    )
                    return "stop"
                with span("rollback", bucket=ROLLBACK):
                    # An update dispatched since the crossing goes with the
                    # state it came from: wait it out (the restore's arrays
                    # must not land beside a running program's scratch),
                    # then drop what it folded.
                    jax.block_until_ready(state)
                    book.hold("train-state")  # the restored beside the live one
                    rolled = self._rollback(
                        ckpt, state, mesh, pub, fingerprint, key,
                        watchdog.last_reason,
                    )
                    book.drop("train-state")
                    if rolled is None:
                        return None
                    state, idx, key = rolled
                    ahead.clear()
                    _stamp("rollback", idx)
                    nf_acc = b_nf
                    if diag_acc is not None:
                        diag_acc.take()
                    last_pub_m = time.monotonic()
                    watchdog.reset()
                    nf_base = self.n_nonfinite_updates
                    budget.record()
                return "rolled"

        loop_entry = time.time()
        try:
            # Between one dispatch and the next, every statement below runs
            # inside exactly one span of the "main" lane (none nests in
            # another), so an idle gap of the device has a name. Sites that
            # feed a timer window or a goodput bucket say so; what names no
            # bucket is the ledger's "overhead". Between a log-sync and the
            # next dispatch lie feed-wait, rng-split and program-record only.
            while not self._stopped():
                # A dispatch always advances the counter by `chain`, so stop
                # before one that would exceed the budget (never overshoot;
                # non-divisible budgets round down, warned above).
                if (
                    self.max_updates is not None
                    and idx - start_idx + chain > self.max_updates
                ):
                    break
                # As far ahead of the chip as the loop goes (RUN_AHEAD): wait
                # for the oldest update in flight before asking for a batch
                # the chip cannot reach yet. The chip is at work meanwhile.
                while ahead and ahead[0].is_ready():
                    ahead.popleft()
                chip_secs = 0.0
                if len(ahead) >= RUN_AHEAD:
                    with span("chip-wait", bucket=COMPUTE) as sp_chip:
                        while len(ahead) >= RUN_AHEAD:
                            jax.block_until_ready(ahead.popleft())
                    chip_secs = sp_chip.secs
                # Idle polls (store starving, or the update-ratio gate
                # holding) stay OUTSIDE the throughput timer: they process
                # zero transitions and must not deflate the learner-FPS
                # window. A successful pop's bounded wait IS counted — with
                # prefetch it is the pipeline's residual feed latency, the
                # honest critical-path cost of a dispatch.
                with span(
                    "feed-wait", timer="learner-queue-wait-time",
                    bucket=wait_bucket,
                ) as sp_wait:
                    item = feed.get(timeout=0.05)
                    if item is None:
                        sp_wait.timed = False
                        sp_wait.bucket = IDLE
                if item is None:
                    if books is not None:
                        # No dispatch to hide behind after all: close them
                        # now, before the poll.
                        verdict = _close_books("empty feed")
                        if verdict == "stop":
                            break
                        if verdict == "rolled":
                            continue
                    with span("idle-poll", bucket=IDLE):
                        if self.heartbeat is not None:
                            self.heartbeat.value = time.time()
                        # Idle rebroadcast (chaos-plane hardening): a PUB
                        # frame is lost to any SUB that connected after the
                        # send (slow-joiner), so a worker restarted by the
                        # supervisor — or a learner restarted mid-run —
                        # would act on a stale/random policy until the next
                        # update-driven publish. While the store starves,
                        # re-ship the current weights + ver on a slow clock
                        # so joiners converge.
                        if self._maybe_join_push(pub, state, ver=idx):
                            last_pub_m = time.monotonic()
                        elif cfg.rebroadcast_idle_s > 0:
                            now_m = time.monotonic()
                            if now_m - last_pub_m >= cfg.rebroadcast_idle_s:
                                self._publish(pub, state, ver=idx)
                                last_pub_m = time.monotonic()
                                self.n_rebroadcasts += 1
                        self._note_ckpt(timer)
                        if telem_reg is not None:
                            now_m = time.monotonic()
                            if now_m - telem_last >= cfg.telemetry_interval_s:
                                telem_last = now_m
                                self._emit_telemetry(
                                    telem_reg, telem_pub, timer, idx
                                )
                        if feed.poll_sleep:
                            time.sleep(feed.poll_sleep)
                    continue
                with span("rng-split"):
                    batch, feed_secs = item
                    key, sub_key = jax.random.split(key)
                # What a compilation under either span is reported with.
                update = {"update": idx + chain}
                with span("program-record", args=update):
                    rc0 = self._perf.recompiles if self._perf is not None else 0
                    if self._perf is not None:
                        # Identity check after the first call; first sight of
                        # a (re)built train_step runs the one-time cost
                        # analysis and rebinds the recompile watch — BEFORE
                        # dispatch, so the donated buffers are still alive to
                        # lower against.
                        self._perf.capture(train_step, state, batch, sub_key)
                    backend.add_program(train_step, state, batch, sub_key)
                    book.declare("batch", batch, bound=cfg.learner_prefetch + RUN_AHEAD)
                # learner-step-time is the host time of an asynchronous
                # dispatch, not device time: it reads as the device's only
                # when the dispatch queue is full and the call blocks.
                with span(
                    "dispatch", args=update,
                    timer="learner-step-time", bucket=COMPUTE,
                ) as sp_step:
                    state, metrics = train_step(state, batch, sub_key)
                    ahead.append(jax.tree.leaves(metrics)[0])
                    # The update holds its batch for as long as it needs it:
                    # these names would hold it until the next pop, past the
                    # update's end and beside a checkpoint's snapshot.
                    batch = item = None
                    if self._perf is not None and self._perf.recompiles > rc0:
                        # A dispatch that retraced spent its span in XLA, not
                        # in useful device math — divert it out of compute.
                        sp_step.bucket = RECOMPILE
                with span("diag-fold"):
                    if track_nf:
                        # Lazy device-side add — no host sync per dispatch;
                        # the loss-log branch below reads it back.
                        nf_acc = nf_acc + metrics["nonfinite-updates"]
                    if diag_acc is not None and isinstance(metrics, dict):
                        # Detach diag BEFORE the loss logger's float() walk
                        # (it is a nested pytree, not a scalar) and fold it
                        # with this dispatch's per-row staleness — one async
                        # device program, zero host syncs.
                        diag = metrics.pop("diag", None)
                        if diag is not None:
                            vers = diag_vers.popleft() if diag_vers else None
                            n_rows = (
                                next(iter(diag["rows"].values())).shape[0]
                                if diag["rows"]
                                else 0
                            )
                            diag_acc.add(
                                diag, _stale_rows(idx, vers, n_rows)
                            )
                if self._inference is not None:
                    with span("inference-swap"):
                        # Snapshot (not reference): the NEXT dispatch donates
                        # this state's buffers, and the serve thread must
                        # never act on deleted arrays.
                        book.hold("inference-params")  # the swap in flight
                        self._inference.set_params(
                            self._actor_snapshot(state), version=idx + chain
                        )
                        book.drop("inference-params")  # the one it replaced
                        _stamp("inference-swap", idx + chain)
                with span("account"):
                    # The dispatch critical path — chip-wait + queue-wait +
                    # step, the throughput window — drives achieved FLOPs/s
                    # too.
                    # learner-batching-time is the feed-side host work (shm
                    # copies + assembly + H2D placement); with prefetch it
                    # overlaps the device step, and overlap shows as
                    # queue-wait << batching-time.
                    critical_secs = chip_secs + sp_wait.secs + sp_step.secs
                    if self._perf is not None:
                        self._perf.note(critical_secs)
                    timer.record("learner-batching-time", feed_secs)
                    timer.record_gauge("learner-queue-depth", feed.qsize())
                    book.count("batch", feed.held() + len(ahead))
                    timer.record(
                        "learner-throughput", critical_secs,
                        check_throughput=True,
                    )
                    prev_idx, idx = idx, idx + chain

                    progress = idx if anneal_absolute else idx - start_idx
                    if anneal_at is not None and progress >= anneal_at:
                        # Rebuild the step with the cold-phase coefficients
                        # (one extra jit compile; optimizer state carries
                        # over — the on-policy families use rmsprop, whose
                        # accumulator is lr-independent). std_floor/family
                        # changes are NOT supported here: workers build their
                        # own family from the original cfg and cannot
                        # re-floor mid-run.
                        cfg = cfg.replace(
                            entropy_coef=float(anneal["coef"]),
                            lr=float(anneal.get("lr", cfg.lr)),
                        )
                        self.cfg = cfg
                        train_step = _wrap(
                            spec.make_train_step(cfg, family), cfg
                        )
                        anneal_at = None  # fire once
                        print(
                            f"[learner] update {idx}: entropy_coef -> "
                            f"{cfg.entropy_coef}, lr -> {cfg.lr}", flush=True,
                        )

                if books is not None:
                    # The chip has its next update: now the last logged
                    # one's books. A rollback restores the committed state
                    # and throws this iteration's update away with the state
                    # it came from.
                    verdict = _close_books()
                    if verdict == "stop":
                        break
                    if verdict == "rolled":
                        continue
                if profiling is not False:
                    # Window is relative to THIS run's updates (resume-safe).
                    # start() returns None when a /prof or SIGUSR2 capture
                    # is already in flight — the window then waits for it.
                    rel = idx - start_idx
                    if profiling is None and rel >= cfg.profile_start:
                        with span("profiler-window"):
                            if prof_capture.start() is not None:
                                profiling = True
                    elif profiling and rel >= cfg.profile_start + cfg.profile_steps:
                        with span("profiler-window"):
                            jax.block_until_ready(metrics)
                            prof_capture.stop()
                            profiling = False
                with span("publish", bucket=WIRE):
                    # Main-lane broadcast cost only (one launch: the snapshot
                    # program); the publisher thread's D2H, device_get + send
                    # overlap the next steps, on a lane of their own.
                    if _crossed(prev_idx, idx, self.publish_interval):
                        self._publish(pub, state, ver=idx)
                        self._consume_join_flag()  # serves joiners too
                        last_pub_m = time.monotonic()
                    elif self._maybe_join_push(pub, state, ver=idx):
                        last_pub_m = time.monotonic()
                if (
                    telem_reg is not None
                    and time.monotonic() - telem_last >= cfg.telemetry_interval_s
                ):
                    with span("telemetry-emit"):
                        telem_last = time.monotonic()
                        self._emit_telemetry(telem_reg, telem_pub, timer, idx)
                with span("heartbeat"):
                    # Before the read-back, not after it: once a log-sync
                    # has returned the chip is empty, and nothing but the
                    # next dispatch stands between it and its work.
                    self._note_ckpt(timer)
                    if self.heartbeat is not None:
                        self.heartbeat.value = time.time()
                    sa = self.stat_array
                    solved = (
                        cfg.stop_at_reward is not None
                        and sa is not None
                        # window full: a real STAT_WINDOW-game mean, not a
                        # lucky few-episode start
                        and sa[SLOT_GAME_COUNT] >= STAT_WINDOW
                        and sa[SLOT_MEAN_REW] >= cfg.stop_at_reward
                    )
                    if solved:
                        logger.log_stat(
                            int(sa[SLOT_GAME_COUNT]), float(sa[SLOT_MEAN_REW])
                        )
                        logger.flush()
                        print(
                            f"[learner] fleet 50-game mean "
                            f"{sa[SLOT_MEAN_REW]:.1f} >= stop_at_reward "
                            f"{cfg.stop_at_reward}: solved, stopping at "
                            f"update {idx}", flush=True,
                        )
                save_due = ckpt is not None and _crossed(
                    prev_idx, idx, cfg.model_save_interval
                )
                if _crossed(prev_idx, idx, cfg.loss_log_interval):
                    with span("log-sync", args={"update": idx}) as sp_sync:
                        # The loop's one blocking read-back of the pipeline:
                        # wait for the update just dispatched, hand its diag
                        # sums over (the next diag-fold starts a new line's)
                        # and do nothing else in front of an empty chip —
                        # but ask where the books can be closed. Today's
                        # order — read, verify, save — where a state is
                        # about to be committed (never one the watchdog has
                        # not seen), where the loop is about to stop, and
                        # where no batch is ready: a line is never held for
                        # the feed.
                        jax.block_until_ready(metrics)
                        ahead.clear()
                        taken = diag_acc.take() if diag_acc is not None else None
                        if taken is not None:
                            book.declare("diag", taken)
                        if save_due:
                            cause = "save"
                        elif (
                            solved
                            or self._stopped()
                            or (
                                self.max_updates is not None
                                and idx - start_idx + chain > self.max_updates
                            )
                            or (budget is not None and budget.exhausted())
                        ):
                            cause = "stop"
                        elif feed.qsize() == 0:
                            cause = "empty feed"
                        else:
                            cause = None
                        books = (idx, metrics, nf_acc, taken, sp_sync)
                        _stamp("log-sync", idx)
                    if cause is not None:
                        verdict = _close_books(cause)
                        if verdict == "stop":
                            break
                        if verdict == "rolled":
                            # Skip this iteration's save branch: the
                            # restored index is already committed on
                            # disk, re-saving it would race the
                            # just-finished restore.
                            continue
                if save_due:
                    # Async mode: snapshot + enqueue only; the D2H, orbax
                    # write, commit marker, and GC run on the writer thread
                    # (lane "ckpt-writer"). This span is the synchronous
                    # remnant of the save, or the full blocking write when
                    # async is off.
                    with span("ckpt-save", bucket=CKPT):
                        ckpt.save(state, idx, meta=_ckpt_meta())
                        _stamp("ckpt-save", idx)
                if solved:
                    break
            if books is not None:
                # Stopped between a crossing and the dispatch its books
                # would have been closed behind.
                _close_books("stop")
            inline = self.n_log_inline
            print(
                f"[learner] logged updates: {self.n_log_behind_dispatch} "
                f"closed behind the next dispatch, {sum(inline.values())} in "
                f"line ({inline['save']} save due, {inline['stop']} stopping, "
                f"{inline['empty feed']} feed empty); the feed took "
                f"{self.n_feed['leased']} batches leased, "
                f"{self.n_feed['copied']} copied", flush=True,
            )
        finally:
            # The loop's end, for the memory book: what the shutdown makes
            # from here on (the last save's snapshot) is not the window's.
            _stamp("close", idx)
            # Feeder first (stops shm sampling), then the publisher (joins
            # its thread, flushing the final snapshot — the Pub socket is
            # only safe to close once no other thread can touch it).
            if self._inference is not None:
                self._inference.close()
            feed.close()
            if self._publisher is not None:
                self._publisher.close()
                print(
                    f"[learner] weight broadcast: {self._publisher.n_sent} of "
                    f"{self._publisher.n_snapshots} snapshots sent (the rest "
                    f"superseded in the slot), {self._publisher.n_bytes} bytes",
                    flush=True,
                )
            if prof_capture is not None:
                # Never leave a trace open (early exit / stop-event / crash)
                # and unhook from the crash path; idempotent with the
                # flight-recorder hook that covers non-finally death.
                prof_capture.close()
            if ckpt is not None:
                if idx > start_idx:
                    ckpt.save(state, idx, meta=_ckpt_meta())
                # close() drains the pending save (the run's final weights
                # are committed, not dropped) then joins the writer thread.
                ckpt.close()
                self._note_ckpt(timer)
            if telem_reg is not None:
                # Final snapshot (then the socket): the run's closing update
                # index reaches the aggregator even on early exit.
                self._emit_telemetry(telem_reg, telem_pub, timer, idx)
                telem_pub.close()
            tracer.close_export()  # trace.json's last state, then join
            pub.close()
            writer.close()
            backend.close()

    # ------------------------------------------------------------- batching
    def _assemble(self, raws: list):
        """One device-ready batch per dispatch: the single consumed batch
        (chain == 1), or K consumed batches stacked on the chained layout
        (``shard_chained_batch``'s contract: update axis replicated — the
        scan consumes it sequentially — batch axis sharded on "data")."""
        if self._chain_mesh is None:
            return self._to_batch(raws[0])
        from tpu_rl.parallel.dp import shard_chained_batch

        return shard_chained_batch(
            [self._to_batch(r) for r in raws], self._chain_mesh
        )

    def _next_batch(self, store, rng) -> dict | None:
        if is_off_policy(self.cfg.algo):
            return store.sample(self.cfg.batch_size, rng)
        return store.lease() if self._leased else store.consume()

    def _make_fetch(self, store, rng, chain: int = 1):
        """Raw-batch producer for the feed, with the off-policy update:data
        ratio gate folded in. The gate counts batches at FETCH time (not at
        update completion) so the prefetch pipeline cannot overdraw the data
        budget by pre-pulling samples the learner has not yet earned.

        An on-policy feed that holds one raw batch at a time (``chain`` 1)
        leases it from the store and places it from the shared memory; a
        chained dispatch holds ``chain`` of them at once, more than the store
        has generations, and a replay sample is a gather: both are copies."""
        self._leased = chain == 1 and not is_off_policy(self.cfg.algo)
        taken = "leased" if self._leased else "copied"
        gate = None
        if (
            is_off_policy(self.cfg.algo)
            and self.cfg.max_update_data_ratio is not None
        ):
            gate = UpdateRatioGate(self.cfg.max_update_data_ratio)
        self._feed_gate = gate  # introspection hook for tests

        def fetch():
            if gate is not None and not gate.ready(
                store.transitions_received()
            ):
                return None
            raw = self._next_batch(store, rng)
            if raw is not None:
                self.n_feed[taken] += 1
                if gate is not None:
                    gate.note_fetched()
            return raw

        return fetch

    def _make_feed(self, store, rng, chain: int):
        """The learner's data plane: prefetch pipeline (feeder thread,
        device-ready double buffering) or the inline synchronous equivalent.
        Both produce identical batches in identical order — the sampler RNG
        and the chain accumulation live in the shared fetch/assemble
        closures — so the A/B switch changes timing only."""
        fetch = self._make_fetch(store, rng, chain)
        release = store.release if self._leased else None
        if self.cfg.learner_prefetch > 0:
            return PrefetchPipeline(
                fetch,
                self._assemble_device,
                chain=chain,
                depth=self.cfg.learner_prefetch,
                stop_event=self.stop_event,
                tracer=self._tracer,
                release=release,
            )
        return SynchronousFeed(
            fetch,
            self._assemble_device,
            chain=chain,
            tracer=self._tracer,
            release=release,
        )

    def _assemble_device(self, raws: list):
        """Assemble + eager device placement with the step's input sharding,
        so the H2D transfer happens feed-side (overlapped under prefetch)
        instead of inside the jitted call's implicit transfer. Runs on the
        feeder thread under prefetch, inside the feed's ``assemble`` span
        (``data/prefetch.py``); the placement is its ``h2d-put`` child, so
        the overlap with the main lane's dispatch is visible.

        A leased batch is views of the store's shared memory, which the
        writer refills once the feed releases the lease: ``h2d-put`` then
        ends when the transfer does, not when it is issued, and on a backend
        whose device memory is the host's (the CPU's, where a placed array
        may alias the buffer it came from) the batch is placed from a copy."""
        import jax

        if self._leased and self._device.platform == "cpu":
            raws = [{k: np.array(v) for k, v in r.items()} for r in raws]
        self._pop_vers(raws)
        batch = self._assemble(raws)
        if self._place_global is not None or self._chain_mesh is not None:
            # Already placed during assembly: host_local_batch_to_global /
            # shard_chained_batch both produce global device arrays.
            return jax.block_until_ready(batch) if self._leased else batch
        with self._tracer.span("h2d-put", tid="feeder"):
            sharded = self._batch_sharding is not None
            placed = jax.device_put(
                batch, self._batch_sharding if sharded else self._device
            )
            return jax.block_until_ready(placed) if self._leased else placed

    def _pop_vers(self, raws: list) -> None:
        """Detach each raw batch's ``"ver"`` staleness sidecar (a non-batch
        key the Batch/multihost constructors must never see) and enqueue the
        dispatch's concatenated per-row versions for the diag fold. Runs on
        the feeder thread; the FIFO mirrors the feed queue's ordering
        (single producer, single consumer)."""
        vs = [
            r.pop("ver", None) if isinstance(r, dict) else None for r in raws
        ]
        if self._diag_vers is None:
            return
        if any(v is None for v in vs):
            self._diag_vers.append(None)
        else:
            self._diag_vers.append(
                np.concatenate([np.asarray(v).reshape(-1) for v in vs])
            )

    def _setup_multihost_feed(self, sharding) -> None:
        """On a multi-host mesh, each learner host feeds its OWN rows of the
        global batch (its storage process only sees local workers); batches
        must be placed as global arrays via the sharding's device->row map."""
        import jax

        if jax.process_count() > 1:
            self._place_global = sharding

    def _to_batch(self, raw: dict):
        from tpu_rl.types import BATCH_FIELDS, Batch, maybe_zero_carry

        raw = maybe_zero_carry(self.cfg, raw)
        if self._place_global is not None:
            from tpu_rl.parallel.multihost import host_local_batch_to_global

            return Batch(
                **host_local_batch_to_global(raw, self._place_global)
            )
        if self._batch_sharding is not None or self._leased:
            # Stays on the host: ``_assemble_device`` sends each chip its
            # rows. Through ``jnp.asarray`` the whole batch would land on
            # chip 0 first and be cut up there by slicing programs that
            # queue behind every update already dispatched, so chip 0 would
            # hold one whole batch more per update the loop is ahead by. A
            # leased batch is placed there too, under ``h2d-put``.
            return Batch(**{k: np.asarray(raw[k]) for k in BATCH_FIELDS})
        return Batch.from_mapping(raw)

    # ------------------------------------------------------------ broadcast
    def _actor_snapshot(self, state) -> dict:
        """Donation-proof device copy of the actor tree, shaped as the
        ``{"actor": ...}`` pytree ``family.act`` consumes (the same contract
        workers build from the model broadcast)."""
        actor = (
            state.actor_params
            if hasattr(state, "actor_params")
            else state.params["actor"]
        )
        return {"actor": snapshot_tree(actor)}

    def _publish(self, pub: Pub, state, ver: int = -1) -> None:
        """Ship the actor tree as host numpy (SAC broadcasts the actor only,
        reference ``sac/learning.py:145``), tagged with the update index
        (``ver``) that produced it — workers echo it so storage can measure
        policy staleness. With the async publisher the caller only snapshots
        (one launch); the D2H, the blocking device_get and the ZMQ send run on
        the publisher thread (lane "publisher"). Callers hold the main-lane span
        this belongs to (``publish``, ``idle-poll`` or ``rollback``). Where a
        broadcast was made the memory book is stamped (``publish``): the
        snapshot has just been allocated."""
        actor = (
            state.actor_params
            if hasattr(state, "actor_params")
            else state.params["actor"]
        )
        if self._broadcast_fits is None:
            import jax

            nbytes = sum(x.nbytes for x in jax.tree.leaves(actor))
            self._broadcast_fits = fits_frame(nbytes)
            if self._book is not None:
                self._book.declare("publish-snapshot", actor)
            if not self._broadcast_fits:
                if self.cfg.act_mode != "remote":
                    raise ValueError(
                        f"the actor tree ({nbytes} bytes) exceeds the model "
                        "broadcast's frame cap: no worker could receive it. "
                        "Set act_mode='remote' (the fleet acts through the "
                        "learner-side inference service)."
                    )
                print(
                    f"[learner] actor tree of {nbytes} bytes exceeds the "
                    "broadcast frame cap: no weights are broadcast "
                    "(act_mode='remote' serves them in-process)", flush=True,
                )
        if not self._broadcast_fits:
            self.n_publish_oversize += 1  # no snapshot, no pack, no send
            return
        if self._publisher is not None:
            self._publisher.publish(actor, ver, epoch=self.run_epoch)
        else:
            import jax

            pub.send(
                Protocol.Model,
                {
                    "actor": jax.device_get(actor),
                    "ver": ver,
                    "epoch": self.run_epoch,
                    "t_tx": time.time_ns(),
                },
            )
        if self._book is not None:
            self._book.stamp("publish", ver)

    def _consume_join_flag(self) -> bool:
        """Clear a pending join request and count it answered. A PUB frame
        reaches every connected SUB, so ANY broadcast serves the joiner —
        the update-driven publish consumes the flag too, not just the
        dedicated idle-path push (a busy learner publishing every update
        must not leave the flag stranded)."""
        sa = self.stat_array
        if sa is None or len(sa) <= SLOT_JOIN_REQ or sa[SLOT_JOIN_REQ] < 1.0:
            return False
        sa[SLOT_JOIN_REQ] = 0.0
        self.n_join_pushes += 1
        return True

    def _maybe_join_push(self, pub: Pub, state, ver: int) -> bool:
        """Storage raised the join flag (a NEW wid entered the membership
        table): push current weights+ver immediately so the joiner does not
        wait out rebroadcast_idle_s acting on a random/stale policy."""
        if not self._consume_join_flag():
            return False
        self._publish(pub, state, ver=ver)
        return True

    def _note_ckpt(self, timer: ExecutionTimer) -> None:
        """Fold checkpoint instrumentation into the loop's timer: wall
        seconds of saves committed since the last call (sync or async — the
        A/B observable) and the count still in flight."""
        ckpt = self._ckpt
        if ckpt is None:
            return
        for dur in ckpt.drain_save_secs():
            timer.record("learner-ckpt-time", dur)
        timer.record_gauge("learner-ckpt-pending", float(ckpt.pending))

    def _watchdog_tripped(
        self, watchdog, losses: dict, diag_doc: dict | None, nf_base: float
    ) -> bool:
        """Feed the divergence watchdog this log interval's signals (the
        loss-log read-back, the diag drain, the fleet's mean return) and say
        whether it tripped."""
        cfg = self.cfg
        sa_h = self.stat_array
        signals = {
            "loss": losses["loss"],
            "grad-norm": losses.get("grad-norm", 0.0),
        }
        if cfg.watchdog_diag and diag_doc is not None:
            # Algorithm-health channels: a KL spike is an upward anomaly
            # as-is; ESS collapses DOWNWARD, so it enters negated to spike
            # the z-score.
            g = diag_doc["global"]
            if "approx-kl" in g:
                signals["diag-approx-kl"] = float(g["approx-kl"])
            if "ess" in g:
                signals["diag-neg-ess"] = -float(g["ess"])
        if (
            sa_h is not None
            and len(sa_h) > SLOT_MEAN_REW
            and sa_h[SLOT_GAME_COUNT] > 0
        ):
            signals["mean-return"] = float(sa_h[SLOT_MEAN_REW])
        tripped = watchdog.observe(signals)
        # The guards contained these updates (params never touched), but a
        # sustained NaN stream means the data or optimizer state is poisoned
        # — count since the last rollback, trip immediately at the threshold.
        if watchdog.note_nonfinite(self.n_nonfinite_updates - nf_base):
            tripped = True
        return tripped

    def _rollback(
        self, ckpt, state, mesh, pub, fingerprint, key, reason: str
    ):
        """Watchdog-triggered restore of the PREVIOUS committed checkpoint
        (the newest may already contain the divergence). Bumps the run
        epoch so every in-flight pre-rollback rollout is fenced by storage
        exactly like post-crash frames, rebroadcasts the restored weights,
        and appends an audit record. Returns (state, idx, key) or None when
        nothing committed exists to restore."""
        import jax
        import jax.numpy as jnp

        # Drain in-flight async saves first: a save committing AFTER
        # discard_above would resurrect the diverged window on the next
        # newest-wins resume.
        ckpt.flush()
        restored = ckpt.restore_nth_latest(
            state, n=2, fingerprint=fingerprint, force=self.cfg.resume_force
        )
        if restored is None:
            print(
                f"[learner] watchdog tripped ({reason}) but no committed "
                "checkpoint exists to roll back to; continuing", flush=True,
            )
            return None
        state, r_idx, meta = restored
        ckpt.discard_above(r_idx)
        if mesh is not None:
            from tpu_rl.parallel.dp import replicate

            state = replicate(state, mesh)
        key_data = meta.get("key")
        if key_data is not None:
            try:
                key = jax.random.wrap_key_data(
                    jnp.asarray(key_data, dtype=jnp.uint32)
                )
            except (TypeError, ValueError):
                pass  # keep the live stream; the restore itself still holds
        # Epoch fence: every rollout produced against the rolled-back
        # policy (or assembled from pre-rollback frames) is now stale by
        # construction — same mechanism as the post-crash resume fence.
        self.run_epoch += 1
        sa = self.stat_array
        if sa is not None and len(sa) > SLOT_RUN_EPOCH:
            sa[SLOT_RUN_EPOCH] = float(self.run_epoch + 1)  # 0 = unknown
        self._publish(pub, state, ver=r_idx)
        self.n_rollbacks += 1
        self._record_rollback(r_idx, reason)
        print(
            f"[learner] rollback #{self.n_rollbacks}: {reason}; restored "
            f"committed idx {r_idx}, run epoch -> {self.run_epoch}",
            flush=True,
        )
        return state, r_idx, key

    def _record_rollback(self, idx: int, reason: str) -> None:
        """Append one rollback record to result_dir/learner_rollback.jsonl —
        the audit trail heal-smoke asserts against (same contract as
        :meth:`_record_resume`)."""
        from tpu_rl.obs.audit import append_jsonl

        append_jsonl(
            self.cfg.result_dir,
            "learner_rollback.jsonl",
            {
                "idx": idx,
                "epoch": self.run_epoch,
                "reason": reason,
                "nonfinite": self.n_nonfinite_updates,
            },
        )

    def _record_resume(self, idx: int) -> None:
        """Append one resume record to result_dir/learner_resume.jsonl —
        the audit trail resume-smoke asserts monotonicity against (child
        stdout is not capturable from the in-process smoke harness). The
        record shape lives in ``obs.audit.append_resume``, shared with the
        colocated loop (schema equality pinned by test)."""
        from tpu_rl.obs.audit import append_resume

        append_resume(self.cfg.result_dir, idx, self.run_epoch)

    def _emit_telemetry(self, reg, pub: Pub, timer: ExecutionTimer, idx: int
                        ) -> None:
        """Refresh the learner registry from the loop's own instruments and
        ship one snapshot. "learner-update-index" is the authoritative policy
        version the aggregator's staleness math ratchets on."""
        from tpu_rl.obs import LEARNER_VERSION_GAUGE

        reg.gauge(LEARNER_VERSION_GAUGE).set(idx)
        if self.ledger is not None:
            self.ledger.publish(reg)
        for name, val in timer.scalars().items():
            reg.gauge(name).set(val)
        reg.counter("learner-rebroadcasts").set_total(self.n_rebroadcasts)
        reg.gauge("learner-run-epoch").set(self.run_epoch)
        reg.counter("learner-join-pushes").set_total(self.n_join_pushes)
        reg.counter("learner-publish-oversize").set_total(self.n_publish_oversize)
        if self._publisher is not None:
            # sent / snapshots = the share of snapshots that reach the wire;
            # the rest were superseded in the latest-wins slot.
            reg.counter("learner-publish-snapshots").set_total(
                self._publisher.n_snapshots
            )
            reg.counter("learner-publish-sent").set_total(self._publisher.n_sent)
            reg.counter("learner-publish-bytes").set_total(self._publisher.n_bytes)
        # Self-healing plane: exported whenever the guards are compiled in
        # (update_guard default-on), so the shipped SLO example rule
        # `counter:learner-nonfinite-updates==0` always has data.
        if self.cfg.update_guard:
            reg.counter("learner-nonfinite-updates").set_total(
                self.n_nonfinite_updates
            )
        reg.counter("learner-rollbacks").set_total(self.n_rollbacks)
        # Logged updates whose books were closed behind the next dispatch,
        # and those closed in line at the crossing (a save due, a stop, an
        # empty feed): together, the logged updates.
        reg.counter("learner-log-behind-dispatch").set_total(
            self.n_log_behind_dispatch
        )
        reg.counter("learner-log-inline").set_total(
            sum(self.n_log_inline.values())
        )
        # Raw batches the store handed the feed by lease and by copy.
        reg.counter("learner-feed-leased").set_total(self.n_feed["leased"])
        reg.counter("learner-feed-copied").set_total(self.n_feed["copied"])
        perf = self._perf
        if perf is not None:
            # Performance plane: analytical FLOPs per dispatch, achieved
            # FLOPs/s over the dispatch window, MFU (omitted when the
            # device has no peak entry — CPU runs without
            # TPU_RL_PEAK_FLOPS), shape-drift retraces, and device-memory
            # watermarks. All refreshed on the emit cadence only.
            from tpu_rl.obs.perf import device_memory_bytes, process_self_stats

            reg.gauge("learner-flops-per-step").set(perf.flops_per_call)
            achieved = perf.achieved_flops_per_s()
            if achieved is not None:
                reg.gauge("learner-achieved-flops").set(achieved)
            mfu = perf.mfu()
            if mfu is not None:
                reg.gauge("learner-mfu").set(mfu)
            reg.counter("learner-xla-recompiles").set_total(perf.recompiles)
            # From the memory book's newest stamp: the loop reads the
            # runtime's books in one place (a backend without them: RSS).
            mem_used, mem_peak = device_memory_bytes(
                self._device, books=self._book.last_books
            )
            reg.gauge("learner-device-mem-bytes").set(mem_used)
            reg.gauge("learner-device-mem-peak-bytes").set(mem_peak)
            rss, n_fds = process_self_stats()
            reg.gauge("learner-rss-bytes").set(rss)
            reg.gauge("learner-open-fds").set(n_fds)
        sa = self.stat_array
        if sa is not None and len(sa) > SLOT_MODEL_LOADS:
            # Fleet-total corrupt-frame counter (the mailbox aggregate the
            # timer gauge above also mirrors) as a true counter, so SLO
            # `rate:` rules can differentiate it.
            reg.counter("transport-rejected-frames").set_total(
                float(sa[SLOT_REJECTED])
            )
        if self._ckpt is not None:
            reg.gauge("learner-ckpt-pending").set(float(self._ckpt.pending))
            reg.counter("learner-ckpt-saves").set_total(self._ckpt.n_saves)
        svc = self._inference
        if svc is not None:
            reg.counter("inference-requests").set_total(svc.n_requests)
            reg.counter("inference-replies").set_total(svc.n_replies)
            reg.counter("inference-batches").set_total(svc.n_batches)
            if svc.chaos is not None:
                reg.counter("inference-chaos-stalls").set_total(
                    svc.chaos.n_stalled
                )
                reg.counter("inference-chaos-refusals").set_total(
                    svc.chaos.n_refused
                )
            if svc.ledger is not None:
                # The serve thread's own lane (wait/flush buckets under the
                # "inference" prefix) — reported, never folded into the
                # learner's ledger above.
                svc.ledger.publish(reg)
            if svc.perf is not None:
                reg.gauge("inference-flops-per-step").set(
                    svc.perf.flops_per_call
                )
                achieved = svc.perf.achieved_flops_per_s()
                if achieved is not None:
                    reg.gauge("inference-achieved-flops").set(achieved)
            # Fast-path observables: summed per-bucket recompile watch,
            # param footprint, bucket dispatch histogram + counters.
            svc.publish_serving_metrics(reg)
        snap = reg.snapshot()
        # Top-level epoch echo (same convention as workers): storage
        # ratchets its stale-frame fence from whichever epoch source lands
        # first — the mailbox slot normally wins, this covers remote setups.
        snap["epoch"] = self.run_epoch
        pub.send(Protocol.Telemetry, snap)

    def _log_fleet_stat(self, logger: LearnerLogger) -> None:
        """Consume the stat mailbox if storage activated it (reference
        ``agents/learner.py:136-148``)."""
        sa = self.stat_array
        if sa is not None and sa[SLOT_ACTIVATE] >= 1.0:
            logger.log_stat(int(sa[SLOT_GAME_COUNT]), float(sa[SLOT_MEAN_REW]))
            if len(sa) > SLOT_MODEL_LOADS:
                # Fleet-health slots (storage._relay_stat): corrupt-frame
                # drops across every transport hop, and worker model-reload
                # totals — exported as timer gauges so they reach the same
                # dashboards as the loop timings.
                self.timer.record_gauge(
                    "transport-rejected-frames", float(sa[SLOT_REJECTED])
                )
                self.timer.record_gauge(
                    "worker-model-loads", float(sa[SLOT_MODEL_LOADS])
                )
            if len(sa) > SLOT_FORWARD_BYTES:
                # Relay health (storage._relay_stat slots 5/6): frames shed
                # by the manager's drop-oldest queue and wire bytes forwarded
                # to storage — the fan-in path's loss and volume odometers.
                self.timer.record_gauge(
                    "relay-dropped-frames", float(sa[SLOT_RELAY_DROPPED])
                )
                self.timer.record_gauge(
                    "manager-forward-bytes", float(sa[SLOT_FORWARD_BYTES])
                )
            sa[SLOT_ACTIVATE] = 0.0

    def _stopped(self) -> bool:
        return self.stop_event is not None and self.stop_event.is_set()


def learner_main(
    cfg: Config,
    handles: ShmHandles,
    model_port: int,
    stat_array,
    stop_event,
    heartbeat,
    max_updates=None,
    publish_interval: int = 1,
    seed: int = 0,
    inference_port: int | None = None,
    stat_port: int | None = None,
) -> None:
    """mp.Process target (reference ``run_learner``, ``main.py:189-226``)."""
    LearnerService(
        cfg,
        handles,
        model_port,
        stat_array,
        stop_event,
        heartbeat,
        max_updates,
        publish_interval,
        seed,
        inference_port=inference_port,
        stat_port=stat_port,
    ).run()
