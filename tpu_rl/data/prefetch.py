"""Pipelined learner feed: overlap the host data plane with device compute.

The learner's hot loop previously ran its entire data plane in series with
the device step — sample shared memory, assemble the batch, transfer it to
the device, and only then dispatch ``train_step`` — even though the on-chip
``@ref`` steps are sub-millisecond, so the chip idled while numpy copies and
H2D transfers ran. IMPALA's core argument is
that the learner must never starve (Espeholt et al., 1802.01561), and the
Podracer architectures get their throughput precisely by overlapping data
arrival with the update step (Hessel et al., 2104.06272).

:class:`PrefetchPipeline` is that overlap: a background feeder thread pulls
raw batches from the store, assembles them (carry zeroing, ``Batch``
construction, chained-dispatch stacking), and eagerly places them on device
so the NEXT dispatch's shm copy + H2D transfer runs concurrently with the
CURRENT ``train_step``. The learner pops device-resident batches from a
bounded queue (depth ~2: enough to hide feed latency, small enough to bound
both device memory — depth x batch bytes — and on-policy staleness, which
grows by at most ``depth`` batches relative to the synchronous feed). The
feeder takes the queue's slot *before* it fetches and places a batch, so
placed and not yet taken is at most ``depth``, the one in placement included:
a batch that has to wait waits in the store, not on the chip.

A store that hands batches over by lease (``OnPolicyStore.lease``: views of
shared memory, nothing copied) gives the feed its ``release``; the feed calls
it once the batch is placed and queued, and on its way out.

Contract (all tested in ``tests/test_prefetch.py``):

- **Ordering / no batch loss**: one feeder thread + a FIFO queue — batches
  reach the learner exactly in store-consumption order.
- **Deterministic shutdown**: ``close()`` (or the shared stop event) drains
  the feeder even when it is blocked on a full queue; ``close()`` joins.
- **Error propagation**: a feeder-thread exception re-raises out of the
  learner's next ``get()`` — never a silent hang.
- **RNG stream stability**: the replay sampler's ``np.random.Generator`` is
  only ever touched by the (single) feeder thread, so the draw sequence is
  identical to the synchronous feed's given the same fetch order.

:class:`SynchronousFeed` is the same interface with zero pipelining — the
``Config.learner_prefetch = 0`` A/B switch that restores the exact serial
semantics.

This module is host-only plumbing (threads + queue); JAX enters only through
the ``assemble`` callable the learner supplies, so the data layer keeps its
"never imports jax" property (see ``tpu_rl/config.py``).

Both feeds time their work with the spans of the ``TraceRecorder`` the learner
hands them (lane ``feeder``: ``queue-slot`` while the queue is full,
``fetch``, ``store-empty``, ``assemble`` — which encloses the ``h2d-put`` the
learner's ``assemble`` callable opens — and ``queue-put``, which the release
sits under); ``feed_secs``, the ``learner-batching-time`` sample, is the sum
of the fetch and assemble spans of one dispatch. Without a recorder (the unit tests) a
private one with no ring does the timing.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable

LANE = "feeder"


def _span_of(tracer):
    from tpu_rl.obs.trace import span_of  # not at import: obs pulls in jax

    return span_of(tracer)


class UpdateRatioGate:
    """Off-policy update:data ratio cap (the round-5 blocker, VERDICT.md
    "What's missing" #1).

    The replay sampler never waits for fresh data — ``ReplayStore.sample``
    answers as long as the ring holds ``batch_size`` rows — so a learner
    that outruns its actors free-runs at extreme update:data ratios
    (measured ~50:1 on the shared-core cluster, CLUSTER_R5_SAC.md) and
    re-fits early random experience. The gate blocks the NEXT update while

        (updates_planned + 1) / transitions_received > max_ratio

    i.e. ``max_ratio`` is the allowed updates per received transition
    (transitions = trajectory windows put x seq_len). ``updates_planned``
    counts batches *fetched* for training rather than updates completed, so
    a prefetching feed cannot overdraw the budget by its queue depth.

    Single-threaded by design: only the feed (feeder thread or the inline
    synchronous feed) calls it.
    """

    def __init__(self, max_ratio: float):
        if not max_ratio > 0:
            raise ValueError(f"max_update_data_ratio must be > 0, got {max_ratio}")
        self.max_ratio = float(max_ratio)
        self.updates_planned = 0

    def ready(self, transitions_received: int) -> bool:
        """May one more update's batch be fetched yet?"""
        if transitions_received <= 0:
            return False
        return (self.updates_planned + 1) <= self.max_ratio * transitions_received

    def note_fetched(self) -> None:
        """Record that one update's batch was actually fetched."""
        self.updates_planned += 1


class SynchronousFeed:
    """The unpipelined feed: fetch + assemble inline in ``get()``.

    Same interface as :class:`PrefetchPipeline` so ``LearnerService.run``
    is shaped identically either way. ``get`` accumulates toward a full
    chained dispatch across calls (returning None whenever the store has no
    window ready, so the caller can heartbeat), exactly like the pre-pipeline
    learner loop did.
    """

    poll_sleep = 0.002  # caller sleeps this on a None get (store starving)

    def __init__(
        self,
        fetch: Callable,
        assemble: Callable,
        chain: int = 1,
        tracer=None,
        release: Callable | None = None,
    ):
        self._fetch = fetch
        self._assemble = assemble
        self._release = release
        self._chain = max(1, chain)
        self._span = _span_of(tracer)
        self._pending: list = []
        self._secs = 0.0  # fetch+assemble seconds toward the next dispatch

    def get(self, timeout: float = 0.0):
        """One device-ready batch as ``(batch, feed_secs)``, or None when the
        store cannot yet fill the dispatch. ``timeout`` is accepted for
        interface parity and ignored (fetch never blocks)."""
        while len(self._pending) < self._chain:
            with self._span("fetch", tid=LANE) as sp:
                raw = self._fetch()
                sp.keep = raw is not None
            if raw is None:
                return None
            self._secs += sp.secs
            self._pending.append(raw)
        with self._span("assemble", tid=LANE) as sp:
            batch = self._assemble(self._pending)
        self._pending = []
        if self._release is not None:
            self._release()
        secs, self._secs = self._secs + sp.secs, 0.0
        return batch, secs

    def qsize(self) -> int:
        return 0

    def held(self) -> int:
        return 0  # a batch is made inside get() and handed over at once

    def close(self) -> None:  # interface parity; nothing to drain
        pass


class PrefetchPipeline:
    """Bounded-depth background feed of device-resident batches.

    ``fetch() -> raw | None`` pulls one update's raw batch from the store
    (None = not ready); ``assemble(list[raw]) -> batch`` turns ``chain``
    raws into ONE device-placed dispatch batch. Both run on the feeder
    thread, off the learner's critical path.
    """

    poll_sleep = 0.0  # get() already blocks on the queue

    def __init__(
        self,
        fetch: Callable,
        assemble: Callable,
        chain: int = 1,
        depth: int = 2,
        stop_event=None,
        idle_sleep: float = 0.002,
        name: str = "learner-prefetch",
        tracer=None,
        release: Callable | None = None,
    ):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._fetch = fetch
        self._assemble = assemble
        self._release = release
        self._chain = max(1, chain)
        self._span = _span_of(tracer)
        self._stop_event = stop_event
        self._idle_sleep = idle_sleep
        # The queue's bound is its slots: the feeder takes one before it
        # builds a dispatch, get() gives it back.
        self._slots = threading.Semaphore(depth)
        self._q: queue.Queue = queue.Queue()
        self._error: BaseException | None = None
        self._closed = threading.Event()
        self._dispatched = 0  # dispatch batches handed to the learner
        self._started = 0  # dispatch batches whose placement has begun
        self._thread = threading.Thread(target=self._run, name=name, daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- feeder
    def _stopped(self) -> bool:
        return self._closed.is_set() or (
            self._stop_event is not None and self._stop_event.is_set()
        )

    def _take_slot(self) -> bool:
        """Room in the queue for the dispatch about to be built; False when
        stopped first. A full queue must never deadlock shutdown."""
        if self._slots.acquire(blocking=False):
            return True
        with self._span("queue-slot", tid=LANE):
            while not self._stopped():
                if self._slots.acquire(timeout=0.05):
                    return True
        return False

    def _run(self) -> None:
        span = self._span
        pending: list = []
        feed_secs = 0.0
        empty = None  # the open store-empty span while polls come back empty
        slot = False  # a queue slot is held for the dispatch being built
        try:
            while not self._stopped():
                if not slot:
                    slot = self._take_slot()
                    continue
                with span("fetch", tid=LANE) as sp:
                    raw = self._fetch()
                    sp.keep = raw is not None
                if raw is None:
                    # store starving (or the update-ratio gate holding):
                    # idle time never counts toward the dispatch's feed
                    # time. One span per run of empty polls, not per poll.
                    if empty is None:
                        empty = span("store-empty", tid=LANE)
                        empty.__enter__()
                    time.sleep(self._idle_sleep)
                    continue
                if empty is not None:
                    empty.__exit__(None, None, None)
                    empty = None
                feed_secs += sp.secs
                pending.append(raw)
                if len(pending) < self._chain:
                    continue
                self._started += 1
                with span("assemble", tid=LANE) as sp:
                    batch = self._assemble(pending)
                pending = []
                with span("queue-put", tid=LANE):
                    self._q.put((batch, feed_secs + sp.secs))
                    slot = False
                    if self._release is not None:
                        self._release()
                feed_secs = 0.0
        except BaseException as e:  # noqa: BLE001 — re-raised in the learner
            self._error = e
        finally:
            if empty is not None:
                empty.__exit__(None, None, None)
            if self._release is not None:
                self._release()  # a lease never outlives the feeder

    # ------------------------------------------------------------ consumer
    def get(self, timeout: float = 0.05):
        """Pop the next ``(batch, feed_secs)``; None after ``timeout`` with
        nothing ready. Re-raises any feeder-thread exception."""
        if self._error is not None:
            raise self._error
        try:
            item = self._q.get(timeout=timeout)
        except queue.Empty:
            if self._error is not None:
                raise self._error
            return None
        self._slots.release()
        self._dispatched += 1
        return item

    def qsize(self) -> int:
        """Prefetched dispatches currently queued (the queue-depth gauge:
        ~depth means the feed is ahead of the chip, ~0 means behind)."""
        return self._q.qsize()

    def held(self) -> int:
        """Dispatch batches that hold device memory on the feed's side: in
        placement, or placed and not yet taken (at most ``depth``, by the
        slots). Two counters with one writer each — the feeder's and the
        consumer's — read without a lock: the learner's memory book counts
        with it, between dispatches."""
        return self._started - self._dispatched

    @property
    def dispatched(self) -> int:
        return self._dispatched

    def close(self, timeout: float = 10.0) -> None:
        """Deterministic shutdown: stop the feeder and join it. Batches still
        queued are dropped (bounded by ``depth``); pending feeder errors are
        NOT raised here — shutdown must always complete."""
        self._closed.set()
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():  # pragma: no cover — contract violation
            raise RuntimeError("prefetch feeder thread failed to stop")
