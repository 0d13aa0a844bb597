"""What every runner shares: the cell's description, the record a run leaves
for the metric readers, the device check, and the measured window.

The window is read from ``result_dir/learn.jsonl``: the learner and the
colocated loop append one line right after a blocking device read-back every
``loss_log_interval`` updates, so a line's update index counts *completed*
updates. The benchmark stamps each line with its own clock when the line
appears (2 ms polling), and keeps the program's own ``ts`` only as a
cross-check (``Run.clock_skew``).

jax-free: the ``cli`` runner's process must never initialise a backend.
"""

from __future__ import annotations

import importlib.util
import json
import os
import socket
import time
from dataclasses import dataclass, field
from typing import Any, Callable

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
POLL_S = 0.002


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class RunFailed(RuntimeError):
    """The system under test stopped, hung or never reached its window."""


def check_device(platform: str, count: int, chips: int) -> None:
    """The one place that decides whether a device may be measured. There is
    no CPU mode; the rehearsal tests replace this function themselves."""
    if platform != "tpu" or count < chips:
        raise NoAccelerator(
            f"cell needs {chips} TPU chip(s); JAX reports {count} {platform!r} "
            "device(s)"
        )


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one file by path (metric readers have dots in their names)."""
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def free_port_block(n: int = 1) -> int:
    """A base port with ``n`` consecutive free ports."""
    for _ in range(200):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            base = s.getsockname()[1]
        if base + n >= 65536:
            continue
        socks = []
        try:
            for p in range(base + 1, base + n):
                sock = socket.socket()
                socks.append(sock)
                sock.bind(("127.0.0.1", p))
            return base
        except OSError:
            continue
        finally:
            for sock in socks:
                sock.close()
    raise RunFailed("no block of free ports")


def set_path(doc: dict, dotted: str, value: Any) -> None:
    """``--set a.b=1``: a sweep changes one parameter without a new file."""
    *parents, leaf = dotted.split(".")
    for p in parents:
        doc = doc.setdefault(p, {})
    doc[leaf] = value


@dataclass
class Spec:
    """One cell as the command line and the data files describe it."""

    cell: dict  # the BENCHMARK.json workloads entry
    config: dict  # benchmarks/configs/<config>.json
    traffic: dict  # benchmarks/traffic/<traffic>.json
    seed: int
    seconds: float
    trace: bool
    t_start: float  # time.monotonic() at process start
    artifacts: str | None = None  # keep logs, records and the trace here

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])

    @property
    def params(self) -> dict:
        """The program's configuration as this cell runs it: the config
        file's ``params`` with the traffic mix's ``params`` on top."""
        return {**self.config["params"], **self.traffic.get("params", {})}


@dataclass
class Seen:
    """One ``learn.jsonl`` line and when the benchmark saw it."""

    mono: float
    row: dict

    @property
    def idx(self) -> int:
        return int(self.row["idx"])


class LearnTail:
    """Follows ``learn.jsonl``, stamping each complete line on arrival."""

    def __init__(self, path: str):
        self.path = path
        self.rows: list[Seen] = []
        self._f = None

    def poll(self) -> None:
        if self._f is None:
            try:
                self._f = open(self.path)
            except FileNotFoundError:
                return
        while True:
            pos = self._f.tell()
            line = self._f.readline()
            if not line.endswith("\n"):
                self._f.seek(pos)  # a line still being written
                return
            self.rows.append(Seen(time.monotonic(), json.loads(line)))

    def close(self) -> None:
        if self._f is not None:
            self._f.close()


@dataclass
class Window:
    """First line after warm-up to the last line inside ``seconds``."""

    start: Seen
    end: Seen
    rows: list[Seen]  # the lines after ``start`` up to and including ``end``

    @property
    def seconds(self) -> float:
        return self.end.mono - self.start.mono

    @property
    def updates(self) -> int:
        return self.end.idx - self.start.idx

    @property
    def clock_skew(self) -> float:
        """The program's own stamps against the benchmark's, as a share."""
        own = float(self.end.row["ts"]) - float(self.start.row["ts"])
        return abs(own - self.seconds) / self.seconds


def measure(
    tail: LearnTail,
    warmup_pairs: int,
    seconds: float,
    alive: Callable[[], bool],
    warmup_timeout_s: float,
    on_start: Callable[[], None] | None = None,
    on_poll: Callable[[], None] | None = None,
) -> Window:
    """Block until the window has passed. ``alive`` says whether the system
    under test still runs; ``on_start`` is called once when warm-up ends;
    ``on_poll`` lets the caller sample other files on the window's cadence."""
    t_give_up = time.monotonic() + warmup_timeout_s
    while len(tail.rows) < warmup_pairs:
        tail.poll()
        if not alive():
            raise RunFailed("the system stopped before its warm-up ended")
        if time.monotonic() > t_give_up:
            raise RunFailed(
                f"no {warmup_pairs} learn.jsonl lines in {warmup_timeout_s:.0f} s"
            )
        time.sleep(POLL_S)
    start = tail.rows[warmup_pairs - 1]
    deadline = start.mono + seconds
    if on_start is not None:
        on_start()
    while time.monotonic() < deadline:
        tail.poll()
        if on_poll is not None:
            on_poll()
        if not alive():
            raise RunFailed("the system stopped inside the measured window")
        time.sleep(POLL_S)
    tail.poll()
    rows = [r for r in tail.rows[warmup_pairs:] if r.mono <= deadline]
    if not rows:
        raise RunFailed(f"no update completed in the {seconds:.0f} s window")
    return Window(start, rows[-1], rows)


@dataclass
class Run:
    """What a run leaves behind; every metric reader takes one."""

    spec: Spec
    window: Window
    transitions_per_update: int
    bytes_per_update: int  # host -> device, from the program's BatchLayout
    device: dict  # platform, kind, count, memory_peak_bytes
    parity: dict  # system vs plain reference, with its verdict
    losses_finite: bool
    failed_updates: int  # non-finite, guarded-out or rolled-back
    recompiles: int  # compilations inside the window
    paths: dict  # kernel paths + Mosaic calls of the main program
    # mean seconds per dispatch of the program's own host timers
    timers: dict = field(default_factory=dict)
    # first and last telemetry.json document seen inside the window
    telemetry: tuple[dict, dict] | None = None
    trace: Any = None  # benchmarks.trace.Trace of a traced run, reduced
    notes: dict = field(default_factory=dict)

    @property
    def updates_per_s(self) -> float:
        return self.window.updates / self.window.seconds

    def correct(self) -> tuple[bool, dict]:
        # The traffic mix may move a shape across the program's kernel gate.
        want = self.spec.traffic.get(
            "expect_paths", self.spec.config.get("expect_paths", [])
        )
        checks = {
            "device": self.device["platform"] == "tpu"
            and self.device["count"] >= self.spec.chips,
            "no_compile_in_window": self.recompiles == 0,
            "losses_finite": self.losses_finite,
            "kernel_path": all(p in self.paths.get("paths", ()) for p in want)
            and (
                not any(p.endswith("_pallas") for p in want)
                or self.paths.get("mosaic_calls", 0) > 0
            ),
            "parity": bool(self.parity.get("ok")),
            **{k: bool(v) for k, v in self.notes.get("checks", {}).items()},
        }
        return all(checks.values()), checks


# ------------------------------------------------------------- telemetry.json
def series(doc: dict, role: str, kind: str, name: str) -> dict[tuple, tuple]:
    """``{source: (ts, value)}`` of one counter or gauge over the sources of
    one role in a telemetry.json document."""
    out = {}
    for src in doc.get("sources", []):
        if src.get("role") != role:
            continue
        for n, _labels, value in src.get(kind, []):
            if n == name:
                key = (src.get("host"), src.get("pid"), src.get("wid"))
                out[key] = (float(src["ts"]), float(value))
    return out


def counter_rate(telemetry, role: str, name: str) -> float | None:
    """Sum over a role's sources of Δcounter / Δts between the first and the
    last document of the window, each source on its own clock."""
    if telemetry is None:
        return None
    first = series(telemetry[0], role, "counters", name)
    last = series(telemetry[1], role, "counters", name)
    rates = [
        (last[k][1] - first[k][1]) / (last[k][0] - first[k][0])
        for k in first
        if k in last and last[k][0] > first[k][0]
    ]
    return sum(rates) if rates else None


def gauge_max(doc: dict, role: str, name: str) -> float | None:
    vals = [
        v
        for kind in ("gauges", "counters")
        for _ts, v in series(doc, role, kind, name).values()
    ]
    return max(vals) if vals else None
