"""Profiler trace -> numbers. The only place that reads an ``.xplane.pb``.

``jax.profiler`` writes one ``XSpace`` protobuf per capture. A TPU shows up as
one plane per chip (``/device:TPU:<n>``) whose lines include ``XLA Modules``
(one event per executed program) and ``XLA Ops`` (one event per HLO op,
control-flow ops enclosing their bodies). An op's JAX name stack
(``jit(train_step)/jvp(...)/block0/attn/attn_flash_pallas/...``), which is how
a ``jax.named_scope`` of the program is found again here, sits in the ``tf_op``
stat of the op's *event metadata*. ``jax.profiler.ProfileData`` shows an
event's own stats only, so this module reads the wire format itself (schema:
``tsl/profiler/protobuf/xplane.proto``; field numbers below), which also keeps
it free of JAX.

Definitions (``on-chip-measurement`` guide, section 4):

- the *step program* of a device is the module with the most total time;
- the *window* runs from the start of its second execution in the trace (the
  first may have begun before the capture) to the start of its last, so it
  holds a whole number of steady-state periods and does not depend on how long
  the profiler took to start or stop;
- *busy* is the union of the op intervals inside the window, *idle share* is
  1 - busy / window; both are averaged over the chips that ran the program;
- a scope's time is the union of the intervals of the ops under it (nested
  control-flow events are not counted twice);
- *exposed* collective time is the part of the collective ops' intervals
  during which no other op ran on that device.

    python3 benchmarks/trace.py dump <dir-or-xplane.pb>     look at one by hand
"""

from __future__ import annotations

import glob
import gzip
import os
import re
import sys
from dataclasses import dataclass, field
from functools import cached_property

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
)
CONTROL_FLOW = re.compile(r"^(while|conditional|call)([.\d]*)$")
NUMBERED = re.compile(r"[.\d]+$")  # fusion.152 -> fusion


def first_xplane(path: str) -> str:
    """``path`` itself, or the oldest ``*.xplane.pb`` under it: the learner
    opens a new capture each time its profiler window closes, and the first
    is the one the cell's traffic file placed."""
    if os.path.isfile(path):
        return path
    files = sorted(
        glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return files[0]


# ------------------------------------------------------------ wire format
def _varint(b: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return x, i


def _fields(b: bytes):
    """(field number, value) of one message: ints for varints, bytes for
    length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v = b[i : i + size]
            i += size
        elif wire == 1:
            v = b[i : i + 8]
            i += 8
        elif wire == 5:
            v = b[i : i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, v


def _map_entry(b: bytes) -> tuple[int, bytes]:
    key, value = 0, b""
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


@dataclass
class Event:
    name: str  # the HLO op's short name, e.g. fusion.222
    start: float  # ns
    dur: float  # ns
    scope: str = ""  # JAX name stack (tf_op)
    category: str = ""  # hlo_category

    @property
    def end(self) -> float:
        return self.start + self.dur


def _device_plane(b: bytes) -> "DeviceTrace | None":
    """XPlane: name=2, lines=3, event_metadata=4 (map), stat_metadata=5 (map)."""
    name, lines, ev_meta, stat_names = "", [], {}, {}
    for f, v in _fields(b):
        if f == 2:
            name = v.decode()
            if not DEVICE_PLANE.match(name):
                return None
        elif f == 3:
            lines.append(v)
        elif f == 4:
            key, value = _map_entry(v)
            ev_meta[key] = value
        elif f == 5:
            key, value = _map_entry(v)
            # XStatMetadata: id=1, name=2
            stat_names[key] = next(
                (x.decode() for g, x in _fields(value) if g == 2), ""
            )
    if not DEVICE_PLANE.match(name):
        return None
    dev = DeviceTrace(name)
    meta_cache: dict[int, tuple[str, str, str]] = {}

    def meta(mid: int) -> tuple[str, str, str]:
        """XEventMetadata: name=2, display_name=4, stats=5 (XStat:
        metadata_id=1, str_value=5) -> (short name, tf_op, hlo_category)."""
        if mid not in meta_cache:
            full = display = scope = category = ""
            for f, v in _fields(ev_meta.get(mid, b"")):
                if f == 2:
                    full = v.decode(errors="replace")
                elif f == 4:
                    display = v.decode(errors="replace")
                elif f == 5:
                    key, text = "", ""
                    for g, x in _fields(v):
                        if g == 1:
                            key = stat_names.get(x, "")
                        elif g == 5:
                            text = x.decode(errors="replace")
                    if key == "tf_op":
                        scope = text
                    elif key == "hlo_category":
                        category = text
            meta_cache[mid] = (display or full, scope, category)
        return meta_cache[mid]

    for raw in lines:
        # XLine: name=2, timestamp_ns=3, events=4
        lname, t0, events = "", 0, []
        for f, v in _fields(raw):
            if f == 2:
                lname = v.decode()
            elif f == 3:
                t0 = v
            elif f == 4:
                events.append(v)
        if lname not in (OPS_LINE, MODULES_LINE):
            continue
        out = []
        for ev in events:
            # XEvent: metadata_id=1, offset_ps=2, duration_ps=3
            mid = off = dur = 0
            for f, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
            short, scope, category = meta(mid)
            out.append(Event(short, t0 + off / 1e3, dur / 1e3, scope, category))
        if lname == OPS_LINE:
            dev.ops = out
        else:
            for m in out:  # jit_train_step(1619578...) -> jit_train_step
                m.name = re.sub(r"\(\d+\)$", "", m.name)
            dev.modules = out
    return dev


def read(data: bytes) -> "Trace | None":
    """XSpace: planes=1. None when no TPU plane ran a program twice (a metric
    reader then finds nothing to read)."""
    devices = []
    for f, v in _fields(data):
        if f == 1:
            dev = _device_plane(v)
            if dev is not None and dev.window is not None:
                devices.append(dev)
    return Trace(devices) if devices else None


def load(path: str) -> "Trace | None":
    path = first_xplane(path)
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        return read(f.read())


# ------------------------------------------------------------- reduction
def union_ns(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by possibly overlapping (start, end) pairs."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def subtract_ns(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Length of the union of ``a`` not covered by the union of ``b``."""
    return union_ns(a + b) - union_ns(b)


def clip(events: list[Event], lo: float, hi: float) -> list[tuple[float, float]]:
    return [
        (max(e.start, lo), min(e.end, hi))
        for e in events
        if e.end > lo and e.start < hi
    ]


def label(op: Event) -> str:
    """What an op belongs to, short enough for a ledger line and the same for
    every layer: the tail of its name stack with the pass it ran in, or its
    HLO category where it has no name stack (copies, layout changes)."""
    if not op.scope:
        return f"{op.category or 'op'}:{NUMBERED.sub('', op.name)}"[:96]
    parts, passes = [], "fwd"
    for p in op.scope.rstrip(":").split("/"):
        if p.startswith("transpose("):
            passes = "bwd"
        elif p.startswith(("jit(", "jvp(", "pjit")):
            continue
        else:
            parts.append(re.sub(r"\d+", "*", p))
    return (passes + " " + "/".join(parts[-3:]))[:96]


@dataclass
class DeviceTrace:
    name: str
    ops: list[Event] = field(default_factory=list)
    modules: list[Event] = field(default_factory=list)

    @cached_property
    def step_name(self) -> str | None:
        total: dict[str, float] = {}
        for m in self.modules:
            total[m.name] = total.get(m.name, 0.0) + m.dur
        return max(total, key=total.get) if total else None

    @cached_property
    def steps(self) -> list[Event]:
        """Executions of the step program whose start the capture saw: the
        first event is left out, because a capture that opens in the middle of
        an execution records it as starting when the capture did."""
        name = self.step_name
        runs = sorted(
            (m for m in self.modules if m.name == name), key=lambda m: m.start
        )
        return runs[1:]

    @property
    def window(self) -> tuple[float, float] | None:
        steps = self.steps
        if len(steps) < 2:
            return None
        return steps[0].start, steps[-1].start

    @property
    def n_steps(self) -> int:
        """Executions of the step program inside the window."""
        return max(0, len(self.steps) - 1)

    def covered_ns(self, pred=None) -> float:
        lo, hi = self.window
        ops = self.ops if pred is None else [o for o in self.ops if pred(o)]
        return union_ns(clip(ops, lo, hi))

    def exposed_collective_ns(self) -> float:
        lo, hi = self.window
        coll = [o for o in self.ops if COLLECTIVE.match(o.name)]
        rest = [
            o for o in self.ops
            if not COLLECTIVE.match(o.name) and not CONTROL_FLOW.match(o.name)
        ]
        return subtract_ns(clip(coll, lo, hi), clip(rest, lo, hi))


@dataclass
class Trace:
    devices: list[DeviceTrace]

    def _mean(self, f) -> float:
        vals = [f(d) for d in self.devices]
        return sum(vals) / len(vals)

    @property
    def window_s(self) -> float:
        return self._mean(lambda d: d.window[1] - d.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self._mean(lambda d: d.covered_ns()) / 1e9

    @property
    def n_steps(self) -> float:
        return self._mean(lambda d: d.n_steps)

    @property
    def step_device_ms(self) -> float:
        """Median device time of one execution of the step program."""
        # the last execution may be cut short by the end of the capture
        durs = sorted(m.dur for d in self.devices for m in d.steps[:-1])
        return durs[len(durs) // 2] / 1e6

    def scope_s(self, pattern: str) -> float | None:
        """Seconds under a named scope, forward and backward (the name stack
        of a transposed op still holds the scope); None if no op carries it."""
        rx = re.compile(pattern)
        if not any(rx.search(o.scope) for d in self.devices for o in d.ops):
            return None
        return self._mean(
            lambda d: d.covered_ns(lambda o: bool(rx.search(o.scope)))
        ) / 1e9

    @property
    def exposed_collective_s(self) -> float:
        return self._mean(lambda d: d.exposed_collective_ns()) / 1e9

    def breakdown(self, n: int = 10) -> dict:
        """Where the first device's window went: op time by ``label`` (self
        time: enclosing control-flow events are left out) and the longest
        idle gaps. Gaps are 'unattributed': the program's host spans are on a
        clock of their own until the tracing issue aligns them."""
        d = self.devices[0]
        lo, hi = d.window
        total: dict[str, float] = {}
        for o in d.ops:
            if CONTROL_FLOW.match(o.name) or o.end <= lo or o.start >= hi:
                continue
            key = label(o)
            total[key] = total.get(key, 0.0) + (min(o.end, hi) - max(o.start, lo))
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        gaps, edge = [], lo
        for s, e in sorted(clip(d.ops, lo, hi)):
            if s > edge:
                gaps.append(s - edge)
            edge = max(edge, e)
        if hi > edge:
            gaps.append(hi - edge)
        gaps.sort(reverse=True)
        return {
            "device_ops": [[name, ns / 1e9] for name, ns in top],
            "idle_gaps": [["unattributed", ns / 1e9] for ns in gaps[:n]],
        }


def dump(path: str, out=sys.stdout) -> None:
    tr = load(path)
    if tr is None:
        print("no TPU plane with a repeated program", file=out)
        return
    for d in tr.devices:
        lo, hi = d.window
        print(
            f"{d.name}: {len(d.modules)} modules, {len(d.ops)} ops, step "
            f"{d.step_name!r} x{d.n_steps}, window {(hi - lo) / 1e9:.6f} s, "
            f"busy {d.covered_ns() / 1e9:.6f} s, exposed collectives "
            f"{d.exposed_collective_ns() / 1e9:.6f} s", file=out,
        )
        names: dict[str, tuple[int, float]] = {}
        for m in d.modules:
            c, t = names.get(m.name, (0, 0.0))
            names[m.name] = (c + 1, t + m.dur)
        for name, (c, t) in sorted(names.items(), key=lambda kv: -kv[1][1])[:12]:
            print(f"  module {name}: x{c} {t / 1e6:.3f} ms", file=out)
    print(
        f"window_s={tr.window_s} busy_s={tr.busy_s} "
        f"step_device_ms={tr.step_device_ms}", file=out,
    )
    bd = tr.breakdown(30)
    for name, s in bd["device_ops"]:
        print(f"  {s * 1e3:10.3f} ms  {name}", file=out)
    print("  idle gaps (ms):", [round(s * 1e3, 3) for _, s in bd["idle_gaps"]], file=out)


if __name__ == "__main__":
    if len(sys.argv) != 3 or sys.argv[1] != "dump":
        sys.exit(__doc__)
    dump(sys.argv[2])
