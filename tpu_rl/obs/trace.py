"""Span tracing: one primitive for a process's host lanes.

The ExecutionTimer answers "how long does X take on average"; it cannot
answer "where did THIS batch's time go" — whether the loop waited because
the feeder was assembling, blocked on shm, or idle. A
:class:`TraceRecorder` is that instrument: a bounded ring of complete spans
(name, start, duration, lane) exported as Chrome trace-event JSON (the
``chrome://tracing`` / Perfetto "X" phase format).

Two kinds of process use it:

- **worker / manager / storage** record sampled spans after the fact with
  :meth:`TraceRecorder.add`, stamped with ``perf_counter`` and anchored to
  the wall clock once (``wall_anchor_ns``) so ``obs/merge.py`` can lay the
  fleet's dumps on one axis. They never import jax through this module.
- **a process that owns a chip** (the learner) constructs the recorder with
  ``annotate=True``. ``with tracer.span(name, tid=lane)`` then does, in one
  call: enter a ``jax.profiler.TraceAnnotation`` named
  ``tpu_rl/<lane>/<name>`` — so whenever *any* profiler capture is open in
  the process (config window, SIGUSR2, ``GET /prof`` where the process
  serves it) the span is in the ``.xplane.pb`` beside the device ops, one
  ``XLine`` per OS thread; append to the ring; and
  — where the site names them — record the ``ExecutionTimer`` window and add
  to the ``GoodputLedger`` bucket. The ring is stamped with the profiler's
  clock (unix nanoseconds, ``time.time_ns``: what the capture's
  ``Task Environment`` plane states as its start), so ring and capture hold
  the same instants. Without a capture the annotation is inert (< 1 us).

Lanes of a chip owner (the learner): ``main`` (every statement between two
dispatches), ``feeder``, ``publisher``, ``ckpt-writer``, ``exporter``,
``profiler`` — and, for the time before the loop, two more:

- ``startup``: every statement of ``LearnerService.run`` from its entry to
  its loop lies under one of ``init-multihost``, ``imports``, ``mesh``,
  ``backend-open``, ``family``, ``train-state``, ``step-build``, ``restore``,
  ``place``, ``wire``, ``inference-start``, ``feed-start`` (in that order,
  once a process; the first broadcast between the last two is a
  ``main/publish``).
- ``xla``: every phase of a compilation that took at least 10 ms, named
  ``trace``, ``lower`` or ``backend``, with ``args`` ``fun`` (the program),
  ``cache`` (``hit`` / ``miss`` of the persistent compile cache, on backend
  phases) and ``thread`` — added by ``utils.platform.CompileClock`` from
  ``jax.monitoring``'s time spans, so it lies beside the span that caused it.

A long run's ring forgets its start, so the role's bring-up record keeps it:
``result_dir/backend-<role>.json`` gains ``startup`` when the first
``log-sync`` has returned (``run_entry_unix_s``, ``loop_entry_unix_s``,
``first_sync_end_unix_s``, ``ring_wrapped``, and ``spans``: every ring entry
that began by then as ``[lane, name, start_unix_s, seconds, args]``,
:meth:`TraceRecorder.entries`) and, at close, ``compiles`` (``programs``: per
program name its compilations, seconds tracing / lowering / in the backend,
cache hits and misses; ``events``: the timed phases of the whole run). **A
slow respawn is read from these two keys**: ``run_entry`` to ``loop_entry``
is the lane ``startup`` (which site took the seconds: ``restore`` is
orbax's import and the checkpoint's read, ``train-state`` the eager init it
overwrites, ``wire`` holds the tensorboard writer's import),
``loop_entry`` to ``first_sync_end`` the first update with its compilation,
and ``compiles.programs`` says for each program whether the persistent cache
answered (``hits``) or XLA compiled it again (``misses``, ``backend_s``).
Once the first logged update's books are closed (behind the dispatch that
follows the first ``log-sync``, or at the sync itself where nothing can hide
them) any compilation of 10 ms or more is one line of the role's log: ``[learner] compiled <program> in <s> s (cache hit|miss|off)
under main/<span> update <n>``.

**A counter sample** (:meth:`TraceRecorder.sample`) is the ring's other kind
of entry: one ``(name, value)`` at an instant of the ring's clock, on a lane
(``tid=``, spelled as ``span()`` and ``add()`` spell it). It lies in the same
ring, so the flight recorder's dump and ``trace.json`` hold it, exported as a
Chrome counter event (``"ph": "C"``) that Perfetto draws as a track under the
lanes and beside an open capture's device ops. The learner's memory book
(``utils.platform.MemoryBook``) samples ``device-mem`` — live bytes of its
fullest chip — wherever it stamps. :meth:`TraceRecorder.entries` lists spans
only.

Cost model: a span is one clock pair, one small object, one deque append
under a lock, and the annotation's enter/exit. The annotation's name is
built once per site (:meth:`TraceRecorder._site`), never per call.

The periodic ``trace.json`` export runs on a thread of its own
(:meth:`TraceRecorder.start_export`): it serialises only the spans recorded
since its last pass and joins cached fragments, so neither the main lane nor
the GIL pays a cost that grows with ring occupancy.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from collections import deque

ANNOTATION_PREFIX = "tpu_rl/"


class _Site:
    """What one call site of :meth:`TraceRecorder.span` always says."""

    __slots__ = ("name", "tid", "label", "timer_name", "bucket")

    def __init__(self, name, tid, timer_name, bucket):
        self.name = name
        self.tid = tid
        self.label = f"{ANNOTATION_PREFIX}{tid}/{name}"
        self.timer_name = timer_name
        self.bucket = bucket


class Span:
    """One open span. After the block, ``secs`` holds its duration.

    ``bucket``, ``timed`` and ``keep`` may be changed inside the block, for
    sites whose accounting is known only afterwards (a dispatch that
    recompiled goes to another bucket; a feed wait that found nothing is not
    a sample of the wait timer; a poll that came back empty is no ring
    entry)."""

    __slots__ = (
        "_rec", "_site", "_ann", "_args", "_outer", "t0", "secs", "bucket", "timed",
        "keep",
    )

    def __init__(self, rec, site, ann, args):
        self._rec = rec
        self._site = site
        self._ann = ann
        self._args = args
        self._outer = None
        self.t0 = 0.0
        self.secs = 0.0
        self.bucket = site.bucket
        self.timed = site.timer_name is not None
        self.keep = True

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        open_ = self._rec._open
        self._outer = open_.get(self._site.tid)
        open_[self._site.tid] = self
        self.t0 = self._rec.now()
        return self

    def __exit__(self, exc_type, exc, tb):
        rec = self._rec
        site = self._site
        self.secs = secs = rec.now() - self.t0
        rec._open[site.tid] = self._outer
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if self.keep:
            rec.add(site.name, self.t0, secs, site.tid, self._args)
        if self.timed and rec.timer is not None:
            rec.timer.record(site.timer_name, secs)
        if self.bucket is not None and rec.ledger is not None:
            rec.ledger.add(self.bucket, secs)
        return False


class TraceRecorder:
    """Ring buffer of completed spans, exportable as Chrome trace events."""

    def __init__(
        self,
        capacity: int = 4096,
        pid: int = 0,
        role: str = "",
        host: str | None = None,
        annotate: bool = False,
    ):
        self.capacity = int(capacity)  # 0: spans annotate and account only
        self.pid = int(pid)
        self.role = role
        self.host = socket.gethostname() if host is None else host
        self._events: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.n_recorded = 0
        self._sites: dict = {}
        self._open: dict = {}  # lane -> its innermost span still open
        # Set by the owner once it has them; a span site that names a timer
        # window or a ledger bucket records into these on exit.
        self.timer = None
        self.ledger = None
        self._annotation = None
        # One shared epoch so timestamps from every thread share an axis —
        # paired with a wall-clock anchor taken at the same instant so dumps
        # from different processes can be merged onto ONE fleet axis
        # (tpu_rl.obs.merge): a span's wall time is wall_anchor_ns + rel.
        if annotate:
            # A chip owner: the ring runs on the profiler's clock itself.
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation
            self.now = time.time
            self.wall_anchor_ns = time.time_ns()
            self._t0 = self.wall_anchor_ns / 1e9
        else:
            self.now = time.perf_counter
            self._t0 = time.perf_counter()
            self.wall_anchor_ns = time.time_ns()
        self._export: _Exporter | None = None

    # ---------------------------------------------------------------- record
    def add(
        self,
        name: str,
        start: float,
        dur: float,
        tid: str = "main",
        args: dict | None = None,
    ) -> None:
        """One completed span; ``start`` is a reading of :attr:`now`
        (``perf_counter`` unless the recorder annotates)."""
        with self._lock:
            self._events.append((name, start - self._t0, dur, tid, args))
            self.n_recorded += 1

    def sample(
        self, name: str, value: float, tid: str = "main", at: float | None = None
    ) -> None:
        """One counter sample: ``name`` read ``value`` at ``at`` (a reading of
        :attr:`now`; None: this instant). A ring entry whose duration is
        None, with the value where a span has its ``args``."""
        if at is None:
            at = self.now()
        with self._lock:
            self._events.append((name, at - self._t0, None, tid, value))
            self.n_recorded += 1

    def unix_s(self, at: float) -> float:
        """A reading of :attr:`now` as unix seconds."""
        return self.wall_anchor_ns / 1e9 + (at - self._t0)

    def span(
        self,
        name: str,
        tid: str = "main",
        args: dict | None = None,
        timer: str | None = None,
        bucket: int | None = None,
    ) -> Span:
        """``with tracer.span("dispatch", args=...) as sp:`` — ``tid`` is the
        lane (one per thread); ``timer`` and ``bucket`` are read the first
        time a (lane, name) is seen and belong to the site from then on.
        ``args`` go to the ring and, as the annotation's arguments, to the
        capture."""
        site = self._sites.get((tid, name))
        if site is None:
            site = self._site(name, tid, timer, bucket)
        ann = self._annotation
        if ann is not None:
            ann = ann(site.label, **args) if args else ann(site.label)
        return Span(self, site, ann, args)

    def _site(self, name, tid, timer, bucket) -> _Site:
        site = self._sites[(tid, name)] = _Site(name, tid, timer, bucket)
        return site

    def open_span(self, tid: str = "main") -> tuple[str, dict | None] | None:
        """``(name, args)`` of the lane's innermost span that is open now, or
        None: what a listener on another subject (a compilation) says it
        happened under."""
        sp = self._open.get(tid)
        return None if sp is None else (sp._site.name, sp._args)

    def __len__(self) -> int:
        return len(self._events)

    def entries(self) -> tuple[list, bool]:
        """Every span the ring holds as ``[lane, name, start_unix_s, seconds,
        args]`` — the form that outlives the ring in ``backend-<role>.json``
        — and whether the ring has already let go of any."""
        events, n = self.spans_since(0)
        anchor = self.wall_anchor_ns / 1e9
        return (
            [
                [tid, name, anchor + rel, dur, args]
                for name, rel, dur, tid, args in events
                if dur is not None  # a counter sample is no span
            ],
            n > len(events),
        )

    def spans_since(self, seq: int) -> tuple[list, int]:
        """Entries (spans and counter samples) recorded after the first
        ``seq`` (those the ring still holds), with the new count: the
        exporter's incremental read."""
        with self._lock:
            n = self.n_recorded
            fresh = min(n - seq, len(self._events))
            events = list(self._events)[-fresh:] if fresh > 0 else []
        return events, n

    # ---------------------------------------------------------------- export
    def _event(self, span: tuple, tids: dict) -> dict:
        name, rel, dur, tid, args = span
        if dur is None:  # a counter sample: ``args`` is its value
            return {
                "name": name,
                "ph": "C",
                "ts": rel * 1e6,
                "pid": self.pid,
                "tid": tids.setdefault(tid, len(tids)),
                "args": {name: args},
            }
        ev = {
            "name": name,
            "ph": "X",
            "ts": rel * 1e6,
            "dur": dur * 1e6,
            "pid": self.pid,
            "tid": tids.setdefault(tid, len(tids)),
        }
        if args:
            ev["args"] = args
        return ev

    def _metadata(self, tids: dict) -> list[dict]:
        out = []
        if self.role:
            out.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": self.pid,
                    "tid": 0,
                    "args": {"name": f"{self.role} {self.host}/{self.pid}"},
                }
            )
        # Thread-name metadata so the viewer shows "main"/"feeder" lanes.
        for tname, tid_i in tids.items():
            out.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": self.pid,
                    "tid": tid_i,
                    "args": {"name": tname},
                }
            )
        return out

    def _meta(self, extra_meta: dict | None) -> dict:
        meta = {
            "role": self.role,
            "pid": self.pid,
            "host": self.host,
            "wall_anchor_ns": self.wall_anchor_ns,
        }
        if extra_meta:
            meta.update(extra_meta)
        return meta

    def to_chrome(self, extra_meta: dict | None = None) -> dict:
        """Chrome trace-event JSON object format: complete ("X") events and
        counter ("C") events with microsecond timestamps, one named lane per
        recording thread. The
        top-level ``meta`` block (role/pid/host + the wall-clock anchor of
        the ring's epoch) is what makes dumps from different processes
        mergeable in principle — without it a ring's timestamps are an
        offset-unknown local axis."""
        with self._lock:
            events = list(self._events)
        tids: dict[str, int] = {}
        trace_events = [self._event(span, tids) for span in events]
        trace_events.extend(self._metadata(tids))
        return {
            "traceEvents": trace_events,
            "displayTimeUnit": "ms",
            "meta": self._meta(extra_meta),
        }

    def dump(self, path: str, extra_meta: dict | None = None) -> None:
        """Atomic write (tmp + rename) so a viewer never loads a torn file."""
        _write_atomic(path, json.dumps(self.to_chrome(extra_meta)))

    def start_export(self, path: str, period_s: float = 2.0) -> None:
        """Keep ``path`` current from a thread of this recorder's own, every
        ``period_s`` seconds while spans arrive. :meth:`close_export` writes
        the last state and joins."""
        if self._export is None and self.capacity > 0:
            self._export = _Exporter(self, path, period_s)

    def close_export(self) -> None:
        export, self._export = self._export, None
        if export is not None:
            export.close()


def span_of(tracer: TraceRecorder | None):
    """The ``span`` callable a component times itself with: its owner's
    recorder's, or — for an owner that has none (unit tests, the colocated
    loop) — that of a recorder without ring or annotation, which only
    measures."""
    return (tracer if tracer is not None else TraceRecorder(capacity=0)).span


def _write_atomic(path: str, text: str) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, path)


class _Exporter:
    """The recorder's ``trace.json`` writer thread. A pass serialises the
    spans recorded since the last one and keeps them as text fragments in a
    ring of the recorder's capacity; the file is those fragments joined, so a
    pass costs the new spans plus one C-level join and write, whatever the
    ring holds. Its own work is the ``trace-export`` span of the
    ``exporter`` lane."""

    def __init__(self, rec: TraceRecorder, path: str, period_s: float):
        self._rec = rec
        self._path = path
        self._period = period_s
        self._fragments: deque = deque(maxlen=rec.capacity)
        self._tids: dict[str, int] = {}
        self._seq = 0  # spans serialised so far
        self._mark = 0  # the recorder's count when the last pass ended
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="trace-export", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self._period):
            self.flush()

    def flush(self) -> None:
        rec = self._rec
        if rec.n_recorded == self._mark:
            return  # nothing but this thread's own span since the last pass
        with rec.span("trace-export", tid="exporter"):
            fresh, self._seq = rec.spans_since(self._seq)
            for span in fresh:
                self._fragments.append(json.dumps(rec._event(span, self._tids)))
            tail = [json.dumps(ev) for ev in rec._metadata(self._tids)]
            text = (
                '{"traceEvents": ['
                + ", ".join([*self._fragments, *tail])
                + '], "displayTimeUnit": "ms", "meta": '
                + json.dumps(rec._meta(None))
                + "}"
            )
            _write_atomic(self._path, text)
        self._mark = rec.n_recorded

    def close(self, timeout: float = 10.0) -> None:
        self._stop.set()
        self._thread.join(timeout=timeout)
        self.flush()
