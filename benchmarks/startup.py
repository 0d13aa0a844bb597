"""The chip owner's start-up, read from the record it leaves behind.

A ``LearnerService`` runs every statement before its loop inside a span of
its recorder's lane ``startup``, its compilations are spans of the lane
``xla`` (phase ``trace`` / ``lower`` / ``backend``, with the program's name,
the persistent cache's verdict and the thread), and when its first
``log-sync`` has returned it writes what its ring holds into
``backend-learner.json`` under ``startup``; at close it adds ``compiles``:
one aggregate per program and the timed phases of the whole run
(``tpu_rl/utils/platform.py``). ``harness.Run.paths`` is that file, so the
seven ``setup.*`` readers of ``benchmarks/metrics/`` need no capture and no
ring: an untraced run could be read as well.

The record's stamps are unix seconds of the program's clock; the benchmark's
axis is its own ``time.monotonic()`` (``Spec.t_start``, ``Seen.mono``). The
window's first ``learn.jsonl`` line carries both — the program's ``ts`` and
the instant the benchmark saw it — so their difference moves one onto the
other, a few milliseconds late at most (2 ms polling; ``clock_skew`` in the
result line bounds the drift between the two).

A program from before the record (or a chip owner that leaves none: the
colocated loop) gives None, and every reader then returns None.
"""

from __future__ import annotations

from dataclasses import dataclass

STARTUP, MAIN, XLA = "startup", "main", "xla"
# The sites of the lane ``startup`` that build what the loop runs on.
BUILD_SITES = ("family", "train-state", "step-build", "restore", "place")


def union_s(intervals) -> float:
    """Seconds covered by at least one of ``(start, end)``."""
    total, edge = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        if hi > edge:
            total += hi - max(lo, edge)
            edge = hi
    return total


@dataclass
class Phase:
    """One timed phase of one compilation, on the benchmark's axis."""

    kind: str  # trace | lower | backend
    fun: str
    start: float
    secs: float
    cache: str | None  # hit | miss | None (no verdict: not a backend phase, or no cache)
    thread: str


@dataclass
class Startup:
    t_start: float  # the benchmark process's start
    run_entry: float  # LearnerService.run entered
    loop_entry: float  # its loop entered
    first_sync_end: float  # its first log-sync returned
    window_start: float  # the measured window opened
    ring_wrapped: bool
    spans: list  # [lane, name, start, seconds, args] up to first_sync_end
    phases: list[Phase]  # of at least 10 ms, of the whole run
    programs: dict  # per program: count, trace_s, lower_s, backend_s, hits, misses
    notes: dict  # listener_calls, events_dropped

    def lane(self, name: str) -> list:
        return [s for s in self.spans if s[0] == name]

    def site_s(self, names) -> float:
        return sum(s[3] for s in self.lane(STARTUP) if s[1] in names)

    def phase_s(self, kinds) -> float:
        """Per thread the union of the phases of these kinds between
        ``run_entry`` and the window's start, summed over threads: a jitted
        callee's trace lies inside its caller's, so a sum would count it
        twice."""
        by_thread: dict[str, list] = {}
        for p in self.phases:
            if p.kind in kinds and self.run_entry <= p.start < self.window_start:
                by_thread.setdefault(p.thread, []).append(
                    (p.start, min(p.start + p.secs, self.window_start))
                )
        return sum(union_s(v) for v in by_thread.values())

    def top(self, kinds, n: int = 5) -> list[list]:
        """The ``n`` programs that cost most in these phases before the
        window, from the aggregate (which is never cut): ``[name, s]``."""
        cost = {
            name: sum(row[f"{k}_s"] for k in kinds) for name, row in self.programs.items()
        }
        return [
            [name, round(s, 3)]
            for name, s in sorted(cost.items(), key=lambda kv: -kv[1])[:n]
        ]

    def verdicts(self) -> tuple[int, int]:
        """Persistent-cache hits and misses before the window's start: the
        aggregate's totals less the verdicts of the timed phases after it
        (a retrieval under 10 ms after the window's start is counted; with
        ``no_compile_in_window`` those are the shutdown's few)."""
        hits = sum(r["hits"] for r in self.programs.values())
        misses = sum(r["misses"] for r in self.programs.values())
        late = [p.cache for p in self.phases if p.start >= self.window_start]
        return hits - late.count("hit"), misses - late.count("miss")

    def named_share(self) -> float:
        """Share of ``[run_entry, first_sync_end]`` that lies under a span of
        the lane ``startup`` or ``main`` (both are the main thread's)."""
        lo, hi = self.run_entry, self.first_sync_end
        named = union_s(
            (max(s[2], lo), min(s[2] + s[3], hi))
            for s in self.spans
            if s[0] in (STARTUP, MAIN) and s[2] < hi and s[2] + s[3] > lo
        )
        return named / (hi - lo)


def from_record(doc: dict, t_start: float, window_start: float, offset: float) -> Startup | None:
    """``doc`` is ``backend-<role>.json``; ``offset`` moves a unix stamp of
    the program onto the axis of ``t_start`` and ``window_start``."""
    rec, comp = doc.get("startup"), doc.get("compiles")
    if not rec or not comp:
        return None
    first_sync_end = rec["first_sync_end_unix_s"] + offset
    spans = [[lane, name, t + offset, secs, args] for lane, name, t, secs, args in rec["spans"]]
    # Up to the first sync the ring's entries (none is dropped there unless
    # the ring wrapped); after it the bounded list written at close.
    phases = [
        Phase(name, args["fun"], t, secs, args.get("cache"), args.get("thread", ""))
        for lane, name, t, secs, args in spans
        if lane == XLA
    ] + [
        Phase(kind, fun, t + offset, secs, cache, thread)
        for kind, fun, t, secs, cache, thread in comp["events"]
        if t + offset > first_sync_end
    ]
    return Startup(
        t_start=t_start,
        run_entry=rec["run_entry_unix_s"] + offset,
        loop_entry=rec["loop_entry_unix_s"] + offset,
        first_sync_end=first_sync_end,
        window_start=window_start,
        ring_wrapped=bool(rec["ring_wrapped"]),
        spans=spans,
        phases=phases,
        programs=comp["programs"],
        notes={k: comp.get(k) for k in ("listener_calls", "events_dropped")},
    )


def of_run(run) -> Startup | None:
    start = run.window.start
    return from_record(
        run.paths, run.spec.t_start, start.mono, start.mono - float(start.row["ts"])
    )
