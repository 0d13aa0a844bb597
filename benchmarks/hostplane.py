"""The program's host lanes beside the device trace: who held the chip up.

A process of ``tpu_rl`` that owns a chip runs every statement between two
dispatches inside a span (``tpu_rl/obs/trace.py``). A span is a
``jax.profiler.TraceAnnotation`` named ``tpu_rl/<lane>/<name>``, so a capture
holds it on the ``/host:CPU`` plane, one ``XLine`` per OS thread, in the same
``XLine.timestamp_ns + XEvent.offset_ps`` arithmetic as the device planes
``benchmarks/trace.py`` reads — and it is a ring entry stamped with the same
clock (unix nanoseconds), beside a ``capture`` entry that states when the
capture's session started. So the same spans are had two ways:

- :func:`parse` — from a capture's bytes (a file on disk: the recorded capture
  of the tests, ``--artifacts``, ``python3 benchmarks/hostplane.py dump``);
- :func:`of_run` — from the ring of the learner that has just run in this
  process (the ``learner_feed`` runner), because a runner deletes its capture
  before the metric readers see the run. ``tests/test_trace_lanes.py`` holds
  the two to each other.

Either way a :class:`Host` is laid over a ``benchmarks.trace.Trace``:

- :meth:`Host.clock_ok` is the causal check that guards the shared clock:
  every ``dispatch(update=i)`` span begins before the start of the step
  program's execution it launched, and every ``log-sync`` span ends no
  earlier than the end of the execution it waited for;
- :meth:`Host.idle_gaps` names each idle gap of the first device by the
  main-lane span that overlaps most of it — suffixed, where that is a wait
  for the feed, with what the feeder lane was doing (``feed-wait>assemble``);
  a gap no span covers, or any gap when the clocks fail the check, stays
  ``unattributed``;
- the per-layer metrics of ``benchmarks/metrics/{loop,publish,ckpt}.*`` and
  ``feed.starved_share`` are sums over the same spans inside the trace's
  window.
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass, field

if not __package__:  # run as a script: the checkout is not on the path yet
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import trace  # noqa: E402
from benchmarks.trace import _fields, _map_entry  # noqa: E402

PREFIX = "tpu_rl/"
HOST_PLANE = "/host:CPU"
ENVIRONMENT_PLANE = "Task Environment"
MAIN, FEEDER, PUBLISHER = "main", "feeder", "publisher"
# Main-lane spans in which the loop waits for data, not for itself.
FEED_WAITS = ("feed-wait", "idle-poll")
# Main-lane spans that block on the device: a read-back or a drain.
DEVICE_SYNCS = ("log-sync", "diag-drain", "profiler-window")
UNATTRIBUTED = "unattributed"


@dataclass
class Span:
    name: str
    start: float  # ns, on the axis of the capture's device events
    dur: float  # ns
    args: dict = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


def _overlap(s: Span, lo: float, hi: float) -> float:
    return max(0.0, min(s.end, hi) - max(s.start, lo))


def _top_level(spans: list[Span]) -> list[Span]:
    """Spans no other span of the lane encloses, in order of start."""
    out: list[Span] = []
    for s in sorted(spans, key=lambda s: (s.start, -s.dur)):
        if not out or s.start >= out[-1].end:
            out.append(s)
    return out


@dataclass
class Host:
    """The ``tpu_rl/*`` spans of one process, per lane, in order of start."""

    lanes: dict[str, list[Span]]

    def lane(self, name: str) -> list[Span]:
        return self.lanes.get(name, [])

    def main(self) -> list[Span]:
        return _top_level(self.lane(MAIN))

    # ------------------------------------------------------------- the clock
    def clock_ok(self, tr: trace.Trace) -> bool:
        """False unless host and device events can have happened in the
        order the program ran them (see the module's text). Dispatches are
        matched to executions in order: the i-th ``dispatch`` span of the
        capture launched the i-th start of the step program the capture saw.
        """
        dev = tr.devices[0]
        name = dev.step_name
        # Every execution, the first included: one the capture opened in the
        # middle of still ended when the device says it did.
        runs = sorted((m for m in dev.modules if m.name == name), key=lambda m: m.start)
        dispatches = [s for s in self.lane(MAIN) if s.name == "dispatch"]
        syncs = [s for s in self.lane(MAIN) if s.name == "log-sync"]
        if not runs or not dispatches:
            return False
        # The capture may have opened after a dispatch whose execution it
        # still saw: align on the right, where both saw everything.
        pairs = list(zip(reversed(dispatches), reversed(runs)))
        if any(d.start > m.start for d, m in pairs):
            return False
        for s in syncs:
            # the execution a log-sync waited for is the last one launched
            # before it began
            before = [m for d, m in pairs if d.end <= s.start]
            if before and s.end < max(m.end for m in before):
                return False
        return True

    # ------------------------------------------------------------- idle gaps
    def _name(self, lo: float, hi: float) -> str:
        best, cover = None, 0.0
        for s in self.main():
            o = _overlap(s, lo, hi)
            if o > cover:
                best, cover = s, o
        if best is None:
            return UNATTRIBUTED
        if best.name not in FEED_WAITS:
            return best.name
        lo, hi = max(lo, best.start), min(hi, best.end)
        feeding, cover = None, 0.0
        by_name: dict[str, float] = {}
        for s in _top_level(self.lane(FEEDER)):
            by_name[s.name] = by_name.get(s.name, 0.0) + _overlap(s, lo, hi)
        for key, o in by_name.items():
            if o > cover:
                feeding, cover = key, o
        return f"{best.name}>{feeding}" if feeding else best.name

    def idle_gaps(self, tr: trace.Trace, n: int = 10) -> list[list]:
        """The ``n`` longest idle gaps of the first device's window, as
        ``[name, seconds]``: ``Trace.breakdown``'s gaps, with names."""
        ok = self.clock_ok(tr)
        gaps = sorted(_gaps(tr.devices[0]), key=lambda g: g[0] - g[1])[:n]
        return [
            [self._name(lo, hi) if ok else UNATTRIBUTED, (hi - lo) / 1e9]
            for lo, hi in gaps
        ]

    def attributed_share(self, tr: trace.Trace) -> float | None:
        """Idle time of the first device that a named main-lane span
        overlaps, over its idle time. 0 when the clocks fail the check."""
        gaps = _gaps(tr.devices[0])
        idle = sum(hi - lo for lo, hi in gaps)
        if idle <= 0:
            return None
        if not self.clock_ok(tr):
            return 0.0
        spans = [(s.start, s.end) for s in self.main()]
        named = sum(
            trace.union_ns([(max(a, lo), min(b, hi)) for a, b in spans if b > lo and a < hi])
            for lo, hi in gaps
        )
        return named / idle

    # ---------------------------------------------------- sums over a window
    def lane_ns(self, tr: trace.Trace, lane: str, names=None, but=None) -> float:
        """Time inside the first device's window that the lane spent in its
        outermost spans: those in ``names`` if given, none of those in
        ``but``."""
        lo, hi = tr.devices[0].window
        return sum(
            _overlap(s, lo, hi)
            for s in _top_level(self.lane(lane))
            if (names is None or s.name in names) and (but is None or s.name not in but)
        )

    def per_update_ms(self, tr: trace.Trace, lane: str, names=None, but=None) -> float:
        return self.lane_ns(tr, lane, names, but) / 1e6 / tr.devices[0].n_steps


def _gaps(dev: trace.DeviceTrace) -> list[tuple[float, float]]:
    """The intervals of the window in which no op ran on the device."""
    lo, hi = dev.window
    gaps, edge = [], lo
    for s, e in sorted(trace.clip(dev.ops, lo, hi)):
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        gaps.append((edge, hi))
    return gaps


# -------------------------------------------------------------- from a capture
def _stat_value(fields: dict):
    """XStat: double=2, uint64=3, int64=4, str=5, bytes=6, ref=7."""
    for key in (3, 4):
        if key in fields:
            return fields[key]
    for key in (5, 6):
        if key in fields:
            return fields[key].decode(errors="replace")
    return None


def parse(data: bytes) -> Host | None:
    """The ``tpu_rl/*`` events of a capture's host plane, per lane, or None
    for a capture that holds none (a program from before the spans, a capture
    cut down to its device planes)."""
    lanes: dict[str, list[Span]] = {}
    for f, plane in _fields(data):
        if f != 1:
            continue
        name, lines, ev_names, stat_names = "", [], {}, {}
        for g, v in _fields(plane):  # XPlane, as in benchmarks/trace.py
            if g == 2:
                name = v.decode()
                if name != HOST_PLANE:
                    break
            elif g == 3:
                lines.append(v)
            elif g == 4:
                key, value = _map_entry(v)
                ev_names[key] = next(
                    (x.decode(errors="replace") for h, x in _fields(value) if h == 2), ""
                )
            elif g == 5:
                key, value = _map_entry(v)
                stat_names[key] = next(
                    (x.decode() for h, x in _fields(value) if h == 2), ""
                )
        if name != HOST_PLANE:
            continue
        for raw in lines:
            t0, events = 0, []
            for g, v in _fields(raw):  # XLine: timestamp_ns=3, events=4
                if g == 3:
                    t0 = v
                elif g == 4:
                    events.append(v)
            for ev in events:
                mid = off = dur = 0
                stats = []
                for g, v in _fields(ev):  # XEvent: 1, 2, 3 and stats=4
                    if g == 1:
                        mid = v
                    elif g == 2:
                        off = v
                    elif g == 3:
                        dur = v
                    elif g == 4:
                        stats.append(v)
                full = ev_names.get(mid, "")
                if not full.startswith(PREFIX):
                    continue
                _, lane, short = full.split("/", 2)
                args = {}
                for st in stats:
                    fields = dict(_fields(st))
                    args[stat_names.get(fields.get(1), "")] = _stat_value(fields)
                lanes.setdefault(lane, []).append(
                    Span(short, t0 + off / 1e3, dur / 1e3, args)
                )
    if not lanes:
        return None
    for spans in lanes.values():
        spans.sort(key=lambda s: s.start)
    return Host(lanes)


def load(path: str) -> Host | None:
    import gzip

    path = trace.first_xplane(path)
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        return parse(f.read())


# ------------------------------------------------------------- from the ring
def from_ring(doc: dict) -> Host | None:
    """The same lanes from a ``TraceRecorder.to_chrome()`` document of a
    chip owner, placed on the axis of its newest capture: the ring's spans
    are stamped in unix time, the capture's events count from its session's
    start, and the ring's ``capture`` entry (lane ``profiler``) says when
    that was. None without such an entry (no capture, or a program from
    before the spans), or when the ring has let go of the capture's spans."""
    events = [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    names = {
        e["tid"]: e["args"]["name"]
        for e in doc.get("traceEvents", [])
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    sessions = [
        e for e in events if e["name"] == "capture" and names.get(e["tid"]) == "profiler"
    ]
    if not sessions:
        return None
    session = max(sessions, key=lambda e: e["ts"])
    start_us, stop_us = session["ts"], session["ts"] + session["dur"]
    if min(e["ts"] for e in events) > start_us:
        return None  # the ring wrapped: spans of the capture are gone
    lanes: dict[str, list[Span]] = {}
    for e in events:
        if e is session or e["ts"] + e["dur"] < start_us or e["ts"] > stop_us:
            continue
        lanes.setdefault(names.get(e["tid"], str(e["tid"])), []).append(
            Span(e["name"], (e["ts"] - start_us) * 1e3, e["dur"] * 1e3, e.get("args") or {})
        )
    if MAIN not in lanes:
        return None
    for spans in lanes.values():
        spans.sort(key=lambda s: s.start)
    return Host(lanes)


_of_run: dict[int, Host | None] = {}


def remember(tr: trace.Trace, host: Host | None) -> None:
    """Say which host lanes go with a reduced trace (the eight readers of one
    run share what :func:`of_run` found; a test hands over a capture's)."""
    _of_run[id(tr)] = host


def of_run(run) -> Host | None:
    """The host lanes that go with ``run.trace``: the ring of the chip owner
    that ran in this process (its flight recorder holds it). None for an
    untraced run, a runner whose chip owner is another process, or a program
    without the spans — a metric reader then has nothing to read."""
    if run.trace is None:
        return None
    if id(run.trace) not in _of_run:
        host = None
        try:
            from tpu_rl.obs import flightrec

            tracer = getattr(flightrec.current(), "tracer", None)
            if tracer is not None:
                host = from_ring(tracer.to_chrome())
        except Exception:  # noqa: BLE001 — an older program: nothing to read
            host = None
        remember(run.trace, host)
    return _of_run[id(run.trace)]


def dump(path: str, out=sys.stdout) -> None:
    tr, host = trace.load(path), load(path)
    if tr is None or host is None:
        print("no TPU plane with a repeated program, or no tpu_rl/* spans", file=out)
        return
    print(f"clock_ok={host.clock_ok(tr)} attributed={host.attributed_share(tr)}", file=out)
    for lane, spans in host.lanes.items():
        total: dict[str, list] = {}
        for s in spans:
            c = total.setdefault(s.name, [0, 0.0])
            c[0] += 1
            c[1] += s.dur
        print(f"lane {lane}: {len(spans)} spans", file=out)
        for name, (c, ns) in sorted(total.items(), key=lambda kv: -kv[1][1]):
            print(f"  {ns / 1e6:10.3f} ms  x{c:<5d} {name}", file=out)
    for name, s in host.idle_gaps(tr, 20):
        print(f"  gap {s * 1e3:9.3f} ms  {name}", file=out)


if __name__ == "__main__":
    if len(sys.argv) != 3 or sys.argv[1] != "dump":
        sys.exit(__doc__)
    dump(sys.argv[2])
