"""Live performance plane: MFU/FLOPs, recompiles, device memory, profiler.

``compiled.cost_analysis()`` gives FLOPs-per-step and, over a device time,
MFU — offline, one program at a time. This module puts those instruments
into the running fleet so every role with telemetry on reports them
continuously:

- :class:`PerfTracker` — attach to a jitted entry point (learner
  ``train_step``, the colocated fused program, the inference ``act`` step).
  On first sight of a callable it does a ONE-TIME AOT ``lower().compile().
  cost_analysis()`` to capture analytical FLOPs per dispatched call (the AOT
  executable is separate from the call cache, so this costs one extra
  compile — acceptable one-time, and only when telemetry is on), then
  derives achieved FLOPs/s and MFU from a rolling window of dispatch
  intervals. Recompiles are counted from the callable's jit cache size
  (``_cache_size()``): after warmup the cache holds exactly one entry per
  seen signature, so ``cache_size - 1`` IS the number of shape-drift
  retraces — a far sharper signal than process-wide compile events, which
  fire several times per trace. Rebinding a rebuilt callable (the learner's
  anneal switch) freezes the old count and restarts the baseline, so
  expected rebuilds don't masquerade as drift.
- :func:`device_peak_flops` / :data:`PEAK_FLOPS` — the single source of
  truth for bf16 peak by device kind, so live and offline MFU can never
  disagree on the denominator. A TPU
  whose kind is not in the table is an error. ``TPU_RL_PEAK_FLOPS`` (env,
  FLOPs/s per device) is the CPU-smoke denominator only — it's what lets
  CPU smokes exercise the MFU path, and it is ignored on any other backend.
- :func:`device_memory_books` — the one reader of ``device.memory_stats()``:
  per device the live bytes, the two lifetime peaks (live buffers; the
  programs' reserved scratch) and the limit, None where a backend keeps no
  books. :func:`device_memory_bytes` — the gauges' in-use/peak watermarks
  from one such row (the peak adds the two books); backends that keep none
  (CPU) fall back to process RSS with a module-tracked high-water mark.
- :func:`process_self_stats` — RSS + open-fd count from ``/proc/self``
  (no psutil), cheap enough to refresh on the telemetry emit cadence.
- :class:`ProfilerCapture` — the one gate every profiler path goes
  through: the learner's config window, ``/prof?ms=N`` on the telemetry
  HTTP server, and ``SIGUSR2`` (mirroring the flight recorder's SIGUSR1).
  Captures are serialized (an overlapping request is refused, HTTP 409),
  bounded, land under ``result_dir``, and ``stop_trace()`` is guaranteed on
  fatal exceptions via the flight-recorder crash hook.

jax imports are lazy: constructing registries/aggregators must not drag the
backend into processes that don't own one.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

from tpu_rl.obs import flightrec

# bf16 peak FLOPs/s per chip by device kind (public spec sheets). MFU is
# reported against bf16 peak regardless of compute dtype (standard MFU
# convention); the CPU has no entry -> None -> mfu omitted.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v6": 918e12,  # Trillium
}


def device_peak_flops(device=None) -> float | None:
    """Peak bf16 FLOPs/s for one device. On the CPU: the
    ``TPU_RL_PEAK_FLOPS`` env var (float, per-device — the documented
    denominator for smoke-testing the MFU path) or None. On an accelerator:
    the table entry, and a ``device_kind`` the table does not know is an
    error, never an override or a silent None."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        env = os.environ.get("TPU_RL_PEAK_FLOPS")
        return float(env) if env else None
    kind = device.device_kind
    for k, v in PEAK_FLOPS.items():
        if kind.startswith(k) or k in kind:
            return v
    raise ValueError(
        f"no peak-FLOPs entry for device_kind {kind!r}; add it to "
        "tpu_rl.obs.perf.PEAK_FLOPS with its source"
    )


def compiled_flops(compiled) -> float:
    """Analytical FLOPs of an AOT-compiled program (0.0 when the backend
    reports none). XLA counts a scan/while body ONCE regardless of trip
    count, so a chained learner program's count already IS per-dispatch."""
    try:
        cost = compiled.cost_analysis() or {}
    except Exception:  # noqa: BLE001 — backends may not implement it
        return 0.0
    return float(cost.get("flops", 0.0) or 0.0)


# ------------------------------------------------------------ process stats
_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096
_rss_peak = 0.0  # fallback high-water mark for backends without memory_stats


def process_self_stats() -> tuple[float, int]:
    """(RSS bytes, open fd count) from ``/proc/self`` — no psutil. Returns
    (0.0, 0) where /proc is absent; callers still set the gauges so the
    series exists."""
    rss = 0.0
    try:
        with open("/proc/self/statm") as f:
            rss = float(int(f.read().split()[1])) * _PAGE_SIZE
    except (OSError, ValueError, IndexError):
        pass
    try:
        n_fds = len(os.listdir("/proc/self/fd"))
    except OSError:
        n_fds = 0
    return rss, n_fds


def device_memory_books(devices) -> list:
    """The runtime's own books of each of ``devices``, as ``(bytes_in_use,
    peak_bytes_in_use, peak_bytes_reserved, bytes_limit | None)`` — or None
    for a device whose backend keeps none (the CPU's ``memory_stats()`` is
    None or absent). The TPU runtime keeps two: live buffers
    (``bytes_in_use`` and its lifetime peak: parameters, optimizer state,
    batches, snapshots) and the scratch reserved for running programs
    (``peak_bytes_reserved``: activations and temporaries); a runtime with
    one book reads 0 for the second. This is the repo's one call site of
    ``device.memory_stats()``: the learner's memory book
    (``utils.platform.MemoryBook``) and the gauges below both read here."""
    rows = []
    for device in devices:
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 — not part of the stable device API
            stats = None
        if not stats:
            rows.append(None)
            continue
        in_use = int(stats.get("bytes_in_use", 0))
        limit = stats.get("bytes_limit")
        rows.append(
            (
                in_use,
                int(stats.get("peak_bytes_in_use", in_use)),
                int(stats.get("peak_bytes_reserved", 0)),
                None if limit is None else int(limit),
            )
        )
    return rows


def device_memory_bytes(device=None, books=None) -> tuple[float, float]:
    """(bytes in use, peak bytes) of one device: from ``books`` (one row of
    :func:`device_memory_books`, where the caller has just read one), else
    from the role's first device. Both of the runtime's books peak while an
    update runs, so the peak is their sum. Backends that keep no books (CPU)
    fall back to process RSS, with the peak tracked as a module-level
    high-water mark so the watermark semantics survive the fallback."""
    global _rss_peak
    if books is None:
        if device is None:
            import jax

            device = jax.devices()[0]
        books = device_memory_books((device,))[0]
    if books is not None:
        in_use, peak_in_use, peak_reserved, _limit = books
        return float(in_use), float(peak_in_use) + float(peak_reserved)
    rss, _ = process_self_stats()
    _rss_peak = max(_rss_peak, rss)
    return rss, _rss_peak


# ------------------------------------------------------------- perf tracker
class _JitWatch:
    """Recompile counter for one jitted callable, from its jit cache size.
    ``_cache_size()`` is private API — hasattr-gated; without it the count
    degrades to 0 rather than lying."""

    def __init__(self, fn):
        self.fn = fn
        self._offset = 0  # recompiles frozen from earlier bindings

    def _current(self) -> int:
        size = getattr(self.fn, "_cache_size", None)
        if size is None:
            return 0
        try:
            return max(0, int(size()) - 1)  # first entry is the warmup trace
        except Exception:  # noqa: BLE001 — private API, fail to zero
            return 0

    def rebind(self, fn) -> None:
        """Point at a rebuilt callable (expected recompile, e.g. the
        learner's anneal switch): freeze the old binding's drift count,
        restart the baseline."""
        if fn is self.fn:
            return
        self._offset += self._current()
        self.fn = fn

    @property
    def recompiles(self) -> int:
        return self._offset + self._current()


class PerfTracker:
    """Live FLOPs/MFU/recompile accounting for ONE jitted entry point.

    Loop protocol (all telemetry-gated — the tracker is simply ``None``
    when the plane is off, one ``is None`` check on the hot path):

    - ``capture(fn, *args)`` each iteration before dispatch: an identity
      check when nothing changed; first sight of a (new) callable runs the
      one-time AOT cost analysis and (re)binds the recompile watch.
    - ``note(dt)`` with the wall-clock dispatch interval. Donated buffers
      serialize consecutive dispatches, so in steady state the interval
      approaches the device step time; it is a host clock all the same, and
      the benchmark's ``step.mfu`` comes from the device trace (ROADMAP D7).
    - read ``flops_per_call`` / ``achieved_flops_per_s()`` / ``mfu()`` /
      ``recompiles`` at emit cadence.
    """

    def __init__(
        self,
        n_devices: int | None = None,
        peak_flops: float | None = None,
        window: int = 100,
    ):
        if n_devices is None:
            import jax

            n_devices = len(jax.devices())
        self.n_devices = int(n_devices)
        self.peak = peak_flops if peak_flops is not None else device_peak_flops()
        self.flops_per_call = 0.0
        self._dts: deque[float] = deque(maxlen=int(window))
        self._watch: _JitWatch | None = None

    def capture(self, fn, *args, **kwargs) -> bool:
        """Bind ``fn`` (idempotent per callable); on a new binding, run the
        one-time cost analysis against the given example args. Returns True
        when a capture actually ran."""
        if self._watch is not None:
            if self._watch.fn is fn:
                return False
            self._watch.rebind(fn)
        else:
            self._watch = _JitWatch(fn)
        try:
            self.flops_per_call = compiled_flops(
                fn.lower(*args, **kwargs).compile()
            )
        except Exception:  # noqa: BLE001 — accounting must never kill a role
            self.flops_per_call = 0.0
        return True

    def note(self, dt_s: float) -> None:
        if dt_s > 0:
            self._dts.append(float(dt_s))

    @property
    def recompiles(self) -> int:
        return self._watch.recompiles if self._watch is not None else 0

    def achieved_flops_per_s(self) -> float | None:
        if not self._dts or self.flops_per_call <= 0:
            return None
        total = sum(self._dts)
        if total <= 0:
            return None
        return self.flops_per_call * len(self._dts) / total

    def mfu(self) -> float | None:
        achieved = self.achieved_flops_per_s()
        if achieved is None or not self.peak:
            return None
        return achieved / (self.peak * self.n_devices)


def maybe_perf_tracker(cfg) -> PerfTracker | None:
    """The role-side constructor: a tracker when the telemetry plane is on,
    else None (hot paths guard on ``is None``, never on a config read)."""
    if not getattr(cfg, "telemetry_enabled", False):
        return None
    return PerfTracker()


# --------------------------------------------------------- profiler capture
def _options():
    """What a capture records. The Python tracer is off: at its default
    level every Python call of every thread is an event, which slows the
    host loop the capture is there to observe (PERF.md section 5 has the
    traced-vs-untraced periods per option). ``TraceAnnotation``s — the
    ``tpu_rl/<lane>/<name>`` spans of ``obs/trace.py`` — and the device
    planes need host tracer level 1 only."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    return options


def session_span_ns(trace_dir: str) -> tuple[int, int] | None:
    """(start, stop) of a finished capture in unix nanoseconds, from the
    ``Task Environment`` plane of its ``.xplane.pb``. Every timestamp in
    the capture is relative to that start, and a ``TraceAnnotation`` is
    stamped with ``time.time_ns()``'s clock: with the start, spans a
    ``TraceRecorder`` stamped outside the capture land on its axis."""
    import glob

    import jax

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        return None
    for plane in jax.profiler.ProfileData.from_file(files[0]).planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            start = stats.get("profile_start_time")
            stop = stats.get("profile_stop_time")
            if start and stop:
                return int(start), int(stop)
    return None


class ProfilerCapture:
    """Serialized ``jax.profiler`` trace capture into ``out_dir``.

    One instance per role process gates every capture path — the learner's
    config window (``start()``/``stop()``), HTTP ``/prof?ms=N``
    (:meth:`capture_async`), and SIGUSR2 — so traces never interleave. A
    request while one is in flight is refused (the HTTP layer maps that to
    409). A crash hook registered with the flight recorder guarantees
    ``stop_trace()`` runs on fatal exceptions, so the capture that was
    meant to explain the crash survives it.

    To see host and device on one timeline, open the capture's directory in
    TensorBoard's profile plugin or Perfetto: the ``tpu_rl/<lane>/<name>``
    spans of ``obs/trace.py`` are on the host threads' lines, the device ops
    on the chips' planes, on one clock.
    """

    def __init__(self, out_dir: str, default_ms: int = 500, tracer=None):
        self.out_dir = out_dir
        self.default_ms = int(default_ms)
        self._lock = threading.Lock()
        self._active: str | None = None  # trace dir while capturing
        self.n_captures = 0
        # The chip owner's TraceRecorder: each finished capture is entered in
        # its ring as the span "capture" of the lane "profiler", from the
        # session's own start to its stop, so a ring dump carries the offset
        # between the capture's time axis and the clock of its spans.
        self._tracer = tracer
        flightrec.add_crash_hook(self._crash_stop)

    @property
    def active(self) -> bool:
        return self._active is not None

    def start(self, tag: str = "window") -> str | None:
        """Begin an unbounded capture (caller stops it); None if busy."""
        import jax

        with self._lock:
            if self._active is not None:
                return None
            path = os.path.join(
                self.out_dir, f"prof-{tag}-{time.strftime('%Y%m%d-%H%M%S')}"
            )
            os.makedirs(path, exist_ok=True)
            try:
                jax.profiler.start_trace(path, profiler_options=_options())
            except Exception:  # noqa: BLE001 — profiling is best-effort
                return None
            self._active = path
        return path

    def stop(self) -> str | None:
        """Flush and end the in-flight capture; None when idle. Never
        raises — this runs on crash paths."""
        import jax

        with self._lock:
            if self._active is None:
                return None
            path = self._active
            try:
                jax.profiler.stop_trace()
                self.n_captures += 1
                session = (
                    session_span_ns(path) if self._tracer is not None else None
                )
                if session is not None:
                    start, stop_ns = session
                    self._tracer.add(
                        "capture", start / 1e9, (stop_ns - start) / 1e9,
                        tid="profiler", args={"dir": path},
                    )
            except Exception:  # noqa: BLE001
                path = None
            finally:
                # Cleared last: unlocked ``active`` readers must never see
                # False while the trace is still flushing / uncounted.
                self._active = None
        return path

    def capture_async(self, ms: int | None = None) -> tuple[bool, str]:
        """Bounded background capture: (True, trace dir) when started,
        (False, reason) when one is already in flight. Powers ``/prof``
        and SIGUSR2."""
        ms = self.default_ms if ms is None else max(1, int(ms))
        path = self.start(tag=f"{ms}ms")
        if path is None:
            return False, "capture in progress"

        def _run():
            time.sleep(ms / 1000.0)
            self.stop()

        threading.Thread(target=_run, name="prof-capture", daemon=True).start()
        return True, path

    def _crash_stop(self) -> None:
        self.stop()

    def close(self) -> None:
        """Stop any in-flight capture and unhook from the crash path."""
        self.stop()
        flightrec.remove_crash_hook(self._crash_stop)

    def install_sigusr2(self) -> bool:
        """Mirror the flight recorder's SIGUSR1: ``kill -USR2 <pid>`` grabs
        a bounded capture from a live process. Main-thread-only (Python's
        signal API); returns whether the handler landed."""
        if threading.current_thread() is not threading.main_thread():
            return False
        import signal

        def _on_signal(signum, frame):
            self.capture_async()

        try:
            signal.signal(signal.SIGUSR2, _on_signal)
        except (ValueError, OSError, AttributeError):
            return False
        return True
