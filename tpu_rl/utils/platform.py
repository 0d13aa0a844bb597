"""Process bring-up: which backend a role may run on, what it records about
it, and where its compiles are cached.

One process owns a chip. The accelerator-owning roles (learner, colocated /
sebulba loop, an on-chip inference replica) open a :class:`BackendRecord`
first; it places the compile cache, refuses a CPU backend nobody asked for,
and logs and records the devices the role runs on. CPU roles (storage,
manager, workers, fleet replicas 1..N-1) are spawned with
``JAX_PLATFORMS=cpu`` in their environment (``runtime.runner.Supervisor``),
which stock JAX honours.

The record (``result_dir/backend-<role>.json``) holds, beside the devices and
the main program's kernel paths: ``startup`` (the recorder's ring up to the
first ``log-sync``), ``compiles`` (:class:`CompileClock`) and ``memory``
(:class:`MemoryBook`: who held the chip's memory, stamped where tree-sized
buffers change hands; ``benchmarks/MEMORY.md`` has the schema).

jax imports are lazy: this module is imported by supervisors that must never
initialise a backend.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time

# Fixed in-checkout cache location (a directory that moves never hits):
# <repo>/.jax_cache.
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

# Pallas TPU kernels lower to this custom-call target; scan / XLA paths never
# emit it. Kernel and fallback paths are wrapped in the named scopes below
# (models/cells.py, models/mamba2.py: ``ssd_pallas`` inside
# ``ssd_scan`` when the scan took its kernels, ops/gated_delta.py: ``gdn_pallas``
# inside ``gdn_scan`` likewise, ops/moe.py: ``moe_gmm_pallas`` inside
# ``moe_experts`` likewise, and ``moe_row_add_pallas`` where a trip's
# rows are added into the tokens by ops/pallas_moe.py's kernel,
# models/glm4_moe_lite.py: ``mla`` around the latent-attention mixer,
# models/lfm2_moe.py: ``shortconv`` around the gated short convolution,
# models/evabyte.py: ``eva`` around the EVA mixer and ``eva_pool`` around its
# chunk pooling,
# models/ling_flash.py: ``kda`` around the per-channel delta-rule mixer and
# ops/kda.py's ``kda_scan`` around its chunked scan, ``kda_pallas`` inside it
# when the scan took its kernels (ops/pallas_kda.py),
# ops/pallas_act.py, parallel/sequence.py: ``attn_bwd_pallas`` inside
# ``attn_flash_pallas`` where the backward is ops/pallas_attn_bwd.py's walk
# over the band's tiles), so one lowered
# module answers both "what did the gate choose" and "did Mosaic get it".
_MOSAIC_TARGET = "tpu_custom_call"
_FILE_LOCATION = re.compile(r'"[^"\n]*":\d+:\d+')  # loc("/path/file.py":12:3 to :40)
_PATH_SCOPES = re.compile(
    r"\b(lstm_pallas|lstm_scan|act_pallas|attn_flash_pallas|attn_bwd_pallas|attn_full|attn_window"
    r"|attn_global|attn_rope|mla|shortconv|eva|eva_pool|ssd_scan|ssd_pallas|gdn_scan|gdn_pallas|moe_experts"
    r"|kda|kda_scan|kda_pallas"
    r"|moe_gmm_pallas|moe_row_add_pallas)\b"
)


def cpu_requested() -> bool:
    """True when this process (and whatever it spawns) was pinned to the CPU
    on purpose: ``JAX_PLATFORMS=cpu`` in the environment — what tests,
    smokes, ``make ci`` and every ``cpu_only`` supervisor child run under.
    jax-free, so supervisors can ask too."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def enable_compile_cache() -> str | None:
    """Place JAX's persistent compile cache. Call before the first compile.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing is set
    in code. Otherwise the cache goes to one fixed directory inside the
    checkout. CPU-pinned processes get no in-code cache: XLA:CPU's AOT loader
    logs a multi-KB machine-feature error per cache hit, and the CPU programs
    here (worker act steps) compile in under a second.

    Returns the directory in use, or None."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if cpu_requested():
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    # The @ref programs compile in well under the default 1 s threshold.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return _CACHE_DIR


# jax.monitoring's three compile phases, by the name they go under here.
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_VERDICTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
# A phase this long is a span of its own (lane ``xla`` of the role's
# recorder, ``compiles.events`` of its record); a shorter one only enters its
# program's aggregate. The eager init of a catalog model emits thousands of
# sub-millisecond phases, which would wash the ring: one smallthinker set-up
# makes 2,945 listener calls and 18-22 ring entries (tf-longctx: 1,959 and
# 15-19; my chip runs, PR 34, PERF.md section 5).
XLA_SPAN_MIN_S = 0.010
MAX_COMPILE_EVENTS = 512
_JIT_WRAP = re.compile(r"^p?jit\((.*)\)$")


class CompileClock:
    """What this process compiled since construction, from ``jax.monitoring``:
    one aggregate per program name (``programs``: backend compilations,
    seconds tracing, lowering and in the backend — a cache retrieval is
    inside the last — persistent-cache hits and misses) and, for every phase
    of at least ``XLA_SPAN_MIN_S``, a timed event: in ``events`` (the first
    ``MAX_COMPILE_EVENTS``) and, with a recorder, as a span of its lane
    ``xla`` with ``args`` ``fun`` / ``cache`` / ``thread``. A cache verdict
    has no name of its own: it belongs to the backend phase that closes next
    on its thread. Once ``announce`` is set, a backend compilation of that
    length also prints one line: program, seconds, verdict, and the ``main``
    span (with its ``update``) it happened under."""

    def __init__(self, tracer=None, role: str = ""):
        import jax

        self.programs: dict[str, dict] = {}
        self.events: list[list] = []
        self.n_events_dropped = 0
        self.n_calls = 0  # listener calls, whatever their length
        self.announce = False
        self._tracer = tracer
        self._role = role
        self._lock = threading.Lock()
        self._verdicts = threading.local()  # .seen: since the last backend phase
        jax.monitoring.register_event_time_span_listener(self._phase)
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event: str, **_kw) -> None:
        verdict = _CACHE_VERDICTS.get(event)
        if verdict is not None:
            self._verdicts.__dict__.setdefault("seen", []).append(verdict)

    def _phase(self, event: str, start: float, end: float, fun_name: str = "", **_kw) -> None:
        kind = _COMPILE_PHASES.get(event)
        if kind is None:
            return
        # The tracer says "f" where the lowering and the backend say "jit(f)".
        wrapped = _JIT_WRAP.match(fun_name)
        name = wrapped.group(1) if wrapped else fun_name
        secs = end - start
        verdicts = self._verdicts.__dict__.pop("seen", ()) if kind == "backend" else ()
        verdict = verdicts[-1] if verdicts else None
        thread = threading.current_thread().name
        with self._lock:
            self.n_calls += 1
            row = self.programs.get(name)
            if row is None:
                row = self.programs[name] = {
                    "count": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
                    "hits": 0, "misses": 0,
                }
            row[f"{kind}_s"] += secs
            if kind == "backend":
                row["count"] += 1
                row["hits"] += verdicts.count("hit")
                row["misses"] += verdicts.count("miss")
            if secs < XLA_SPAN_MIN_S:
                return
            if len(self.events) < MAX_COMPILE_EVENTS:
                self.events.append([kind, name, start, secs, verdict, thread])
            else:
                self.n_events_dropped += 1
        tracer = self._tracer
        if tracer is not None:
            # jax stamps time.time(); a chip owner's ring runs on that clock.
            tracer.add(
                kind, tracer.now() - (time.time() - start), secs, tid="xla",
                args={"fun": name, "cache": verdict, "thread": thread},
            )
        if self.announce and kind == "backend":
            under = tracer.open_span("main") if tracer is not None else None
            where = "no open main span"
            if under is not None:
                where = f"under main/{under[0]} update {(under[1] or {}).get('update')}"
            print(
                f"[{self._role}] compiled {name} in {secs:.3f} s "
                f"(cache {verdict or 'off'}) {where}",
                flush=True,
            )

    def stats(self) -> dict:
        with self._lock:
            rows = list(self.programs.values())
        return {
            "compile_s": round(sum(r["backend_s"] for r in rows), 3),
            "cache_hits": sum(r["hits"] for r in rows),
            "cache_misses": sum(r["misses"] for r in rows),
        }

    def record(self) -> dict:
        """``compiles`` of ``backend-<role>.json``: the aggregate, never cut,
        and the timed events ``[phase, program, start_unix_s, seconds,
        verdict, thread]``."""
        with self._lock:
            return {
                "listener_calls": self.n_calls,
                "programs": {k: dict(v) for k, v in self.programs.items()},
                "events": [list(e) for e in self.events],
                "events_dropped": self.n_events_dropped,
            }

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_time_span_listener(self._phase)
        jax.monitoring.unregister_event_listener(self._event)


# The owners a chip owner's memory book knows, in the order of a stamp's
# ``alive`` counts.
MEMORY_OWNERS = (
    "train-state", "batch", "publish-snapshot", "inference-params",
    "ckpt-snapshot", "diag",
)
MAX_LOOP_STAMPS = 256  # kept stamps of the loop; the start-up's are all kept
DEVICE_MEM = "device-mem"  # the recorder's counter track


def shard_nbytes(tree) -> int:
    """Bytes ``tree`` holds on one device: per leaf its first addressable
    shard's ``nbytes`` (a replicated leaf is whole on every chip, a sharded
    one holds its part), a host leaf's own."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        total += int(shards[0].data.nbytes if shards else getattr(leaf, "nbytes", 0))
    return total


class _Owner:
    __slots__ = ("bytes_each", "bound", "alive", "alive_max")

    def __init__(self):
        self.bytes_each = None  # until the program has made one and sized it
        self.bound = None  # what the program's own design holds ``alive`` to
        self.alive = 0
        self.alive_max = 0


class MemoryBook:
    """Who holds a chip owner's device memory, on its recorder's clock.

    Two kinds of entry. **Owners** are what the program itself allocates in
    tree-sized pieces (``MEMORY_OWNERS``): the program says how large one is
    (``declare``: the leaves' own ``nbytes`` on one device) and counts them
    up and down where it makes one and lets go of it (``hold`` / ``drop``, or
    ``count`` where the number is read off a queue). **Stamps** are readings
    of the runtime's books (``obs.perf.device_memory_books``, the fullest
    device's row) taken at the exit of the sites where such a piece changes
    hands — never per dispatch: ``[site, unix_s, update | None,
    bytes_in_use, peak_bytes_in_use, peak_bytes_reserved]`` with the owners
    alive at that instant, and a sample of the recorder's ``device-mem``
    counter track. A backend without books stamps ``None`` in the runtime's
    three columns (never the process's RSS) and still counts its owners.

    The record (:meth:`record`, ``memory`` of ``backend-<role>.json``) keeps
    every start-up stamp (lane ``startup``) and of the others a bounded set:
    the first, the first of each site, each that set a new maximum of
    ``bytes_in_use``, each at which a lifetime peak rose, and the last.
    ``window`` is what the loop held once it ran: from the first
    ``log-sync`` to the stamp ``close`` (the loop's end: what the shutdown
    makes after it — a last save's snapshot — is stamped and kept, and is
    not the window's), the stamp with the most live bytes and its owners;
    whether a lifetime peak rose since (then the runtime's peak is the
    window's own, not the set-up's); ``scratch_bytes``, the reserved book
    when the first update had finished, and whether this role raised it over
    the ``run`` stamp's reading.

    ``stamp``, ``hold``, ``drop`` and ``count`` are called between
    dispatches: beyond the runtime's own answer and the ring entry they
    allocate nothing unless the stamp is kept (``_keep``)."""

    SYNC, CLOSE = "log-sync", "close"

    def __init__(self, devices, tracer=None):
        from tpu_rl.obs.perf import device_memory_books

        self._devices = tuple(devices)
        self._tracer = tracer
        self._read = device_memory_books
        self._lock = threading.Lock()
        self._owners = {name: _Owner() for name in MEMORY_OWNERS}
        self._stamps: list[tuple] = []  # kept rows
        self._alive: list[tuple] = []  # the owners alive at each kept row
        self._sites: set[str] = set()
        self.n_stamps = 0
        self.n_dropped = 0  # qualified for keeping past MAX_LOOP_STAMPS
        self._n_loop_kept = 0
        self._max_in_use = -1  # over the stamps outside the lane startup
        self._peaks = (-1, -1)  # the lifetime peaks as last read
        self.last = None  # the newest stamp, kept or not
        self._last_kept = True
        self.last_books = None  # its row as the runtime gave it (the gauges')
        self.bytes_limit = None
        self._run = None  # the stamp "run": before the role allocated a byte
        self._sync0 = None  # the first log-sync's stamp
        self._window = None  # (row, alive) of the most live bytes since
        self._end_peaks = None  # the lifetime peaks at the stamp "close"

    # ---------------------------------------------------------------- owners
    def declare(self, name: str, tree, bound=None) -> None:
        """Size one piece of owner ``name`` from the one just made (once;
        later calls are no-ops)."""
        owner = self._owners[name]
        if owner.bytes_each is None:
            owner.bytes_each = shard_nbytes(tree)
            owner.bound = bound

    def hold(self, name: str, n: int = 1) -> None:
        owner = self._owners[name]
        with self._lock:
            owner.alive += n
            if owner.alive > owner.alive_max:
                owner.alive_max = owner.alive

    def drop(self, name: str, n: int = 1) -> None:
        owner = self._owners[name]
        with self._lock:
            owner.alive = max(0, owner.alive - n)

    def count(self, name: str, alive: int) -> None:
        """Set how many of ``name`` are alive (read off the program's own
        queues by the one thread that can see them)."""
        owner = self._owners[name]
        with self._lock:
            owner.alive = alive
            if alive > owner.alive_max:
                owner.alive_max = alive

    # ---------------------------------------------------------------- stamps
    def stamp(self, site: str, update: int | None = None, tid: str = "main") -> None:
        tracer = self._tracer
        at = time.time() if tracer is None else tracer.now()
        books = None
        for row in self._read(self._devices):  # the fullest device's
            if row is not None and (books is None or row[0] > books[0]):
                books = row
        if books is None:
            in_use = peak = reserved = None
        else:
            in_use, peak, reserved, self.bytes_limit = books
            if tracer is not None:
                tracer.sample(DEVICE_MEM, in_use, tid=tid, at=at)
        unix = at if tracer is None else tracer.unix_s(at)
        row = (site, unix, update, in_use, peak, reserved)
        with self._lock:
            self.n_stamps += 1
            self.last, self.last_books = row, books
            startup = tid == "startup"
            first_sync = self._sync0 is None and site == self.SYNC
            keep = startup or first_sync or site not in self._sites
            if books is not None:
                if peak > self._peaks[0] or reserved > self._peaks[1]:
                    self._peaks = (max(peak, self._peaks[0]), max(reserved, self._peaks[1]))
                    keep = True
                if not startup and in_use > self._max_in_use:
                    self._max_in_use = in_use
                    keep = True
            in_window = first_sync or (
                self._sync0 is not None and self._end_peaks is None
            )
            top = in_window and (
                self._window is None
                or (in_use is not None and in_use > self._window[0][3])
            )
            if in_window and site == self.CLOSE:
                self._end_peaks = self._peaks
            self._last_kept = False
            if keep or top:
                self._keep(row, startup, first_sync, keep, top)

    def _keep(self, row, startup, first_sync, keep, top) -> None:
        """The cold part of a stamp, under the lock: the owners alive at it,
        what the window remembers, and the row's place in the record."""
        alive = tuple(self._owners[name].alive for name in MEMORY_OWNERS)
        if row[0] == "run" and self._run is None:
            self._run = row
        if first_sync:
            self._sync0 = row
        if top:
            self._window = (row, alive)
        if not keep:
            return
        if not startup:
            if self._n_loop_kept >= MAX_LOOP_STAMPS:
                self.n_dropped += 1
                return
            self._n_loop_kept += 1
        self._sites.add(row[0])
        self._stamps.append(row)
        self._alive.append(alive)
        self._last_kept = True

    # ---------------------------------------------------------------- record
    def record(self) -> dict:
        """``memory`` of ``backend-<role>.json`` as it stands."""
        with self._lock:
            stamps = [list(r) for r in self._stamps]
            alive = list(self._alive)
            if self.last is not None and not self._last_kept:
                stamps.append(list(self.last))
                alive.append(tuple(self._owners[n].alive for n in MEMORY_OWNERS))
            owners = {
                name: {
                    "bytes_each": o.bytes_each,
                    "alive_max": o.alive_max,
                    "bound": o.bound,
                    "alive": [a[i] for a in alive],
                }
                for i, (name, o) in enumerate(self._owners.items())
            }
            window = None
            if self._sync0 is not None and self._window is not None:
                top, top_alive = self._window
                sync_peak, sync_reserved = self._sync0[4], self._sync0[5]
                peaks = self._end_peaks or self._peaks
                rose = sync_peak is not None and peaks[0] > sync_peak
                window = {
                    "first_sync_unix_s": self._sync0[1],
                    "stamp": list(top),
                    "alive": dict(zip(MEMORY_OWNERS, top_alive)),
                    "in_use_peak_rose": rose,
                    "reserved_peak_rose": (
                        sync_reserved is not None and peaks[1] > sync_reserved
                    ),
                    # The loop's live peak: the runtime's own where it rose
                    # inside the window (exact), else the fullest stamp (a
                    # lower bound: the set-up's peak hides the loop's).
                    "live_peak_bytes": peaks[0] if rose else top[3],
                    "scratch_bytes": sync_reserved,
                    "raised_by_learner": (
                        None if sync_reserved is None or self._run is None
                        or self._run[5] is None else sync_reserved > self._run[5]
                    ),
                }
            return {
                "devices": len(self._devices),
                "bytes_limit": self.bytes_limit,
                "stamps": stamps,
                "stamps_taken": self.n_stamps,
                "stamps_dropped": self.n_dropped,
                "owners": owners,
                "window": window,
            }


def _local_devices(mesh) -> list:
    """The devices of ``mesh`` this process can read the books of; without a
    mesh the first device, which a one-chip role runs on."""
    import jax

    if mesh is None:
        return jax.devices()[:1]
    return [d for d in mesh.devices.flat if d.process_index == jax.process_index()]


def backend_info(mesh=None) -> dict:
    """The devices this process runs on, as JAX reports them."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "process_count": jax.process_count(),
        "mesh": dict(mesh.shape) if mesh is not None else None,
    }


def require_accelerator(role: str, cpu_ok: bool) -> None:
    """``learner_device="auto"`` means the accelerator. JAX's own fallback
    hands a process with no reachable chip the CPU and says nothing; an
    accelerator-owning role refuses it unless the CPU was asked for."""
    import jax

    if jax.default_backend() == "cpu" and not (cpu_ok or cpu_requested()):
        raise RuntimeError(
            f"[{role}] no accelerator: JAX fell back to the CPU backend. "
            "This role owns the chip; to run it on the CPU on purpose set "
            "learner_device='cpu' or JAX_PLATFORMS=cpu."
        )


def program_paths(lowered) -> dict:
    """Which kernel/fallback paths a lowered jit program took (the named
    scopes around each dispatch) and how many Mosaic custom calls it holds."""
    text = lowered.as_text(debug_info=True)
    # the scopes are read from the name stacks, not from the file locations beside
    # them: ``ops/kda.py`` would read as the scope ``kda``, and a cached trace of a
    # jnp helper keeps the call site it was first traced from, whatever program
    # lowers it next
    named = _FILE_LOCATION.sub("", text)
    return {
        "paths": sorted(set(_PATH_SCOPES.findall(named))),
        "mosaic_calls": text.count(_MOSAIC_TARGET),
    }


def _executable_memory(lowered) -> tuple | None:
    """The compiler's own sizes of the program ``lowered`` became — from the
    executable the jit call already holds, never by compiling: a dispatch
    compiles the lowering it finds in jit's cache (this one, made from the
    same arguments just before it) and leaves the executable on it. Where
    this JAX keeps it elsewhere, or the program has not run, None."""
    computation = getattr(lowered, "_lowering", None)
    if getattr(computation, "_executable", None) is None:
        return None
    try:
        sizes = lowered.compile().memory_analysis()
        wrapped = _JIT_WRAP.match(getattr(computation, "_name", ""))
        return wrapped.group(1) if wrapped else "", {
            "temp_bytes": int(sizes.temp_size_in_bytes),
            "argument_bytes": int(sizes.argument_size_in_bytes),
            "output_bytes": int(sizes.output_size_in_bytes),
            "alias_bytes": int(sizes.alias_size_in_bytes),
        }
    except Exception:  # noqa: BLE001 — a backend without the analysis
        return None


class BackendRecord:
    """An accelerator-owning role's bring-up, opened first thing after any
    multihost init: compile cache, device check, one start-up log line, and
    the same facts in ``result_dir/backend-<role>.json`` — rewritten as the
    run learns which kernel paths its main program took, when its first
    update has finished on the device (``startup``: the recorder's ring so
    far, which a long run's ring forgets) and, at close, what it spent
    compiling (three totals and ``compiles``, :meth:`CompileClock.record`;
    the main program's row gains ``memory``: the compiler's temporary,
    argument, output and alias sizes, read from the executable the first
    dispatch left behind, no second compilation).
    ``chip_smoke.py`` asserts on the file instead of trusting an exit code;
    the benchmark's ``setup.*`` metrics read ``startup`` and ``compiles``.
    ``tracer`` is the role's :class:`~tpu_rl.obs.trace.TraceRecorder`, if it
    has one: compilations then are spans of its lane ``xla`` too. ``memory``
    is the role's :class:`MemoryBook` over the devices of ``mesh`` this
    process addresses (its first device without one); a role that stamps it
    (the learner) finds ``memory`` in the record wherever ``startup`` or
    ``compiles`` is written."""

    def __init__(self, role: str, cfg, mesh=None, tracer=None):
        cache = enable_compile_cache()
        self._tracer = tracer
        self._clock = CompileClock(tracer, role)
        require_accelerator(role, cpu_ok=(cfg.learner_device == "cpu"))
        self._result_dir = cfg.result_dir
        self.info = {"role": role, **backend_info(mesh), "compile_cache": cache}
        self.memory = MemoryBook(_local_devices(mesh), tracer)
        self._lowered = None  # the main program's lowering, until it has run
        self._program_memory = None  # (its name, the compiler's sizes)
        print(
            f"[{role}] backend {self.info['platform']} device_kind "
            f"{self.info['device_kind']!r} devices "
            f"{self.info['device_count']} mesh {self.info['mesh']} "
            f"compile_cache {cache}",
            flush=True,
        )
        self._write()

    def add_program(self, jitted, *args) -> None:
        """Record, once, the kernel paths of the role's main program (one
        extra trace of ``jitted`` on its first dispatch's arguments). A
        no-op without a ``result_dir`` to record into, so loops call it
        unconditionally before each dispatch."""
        if self._result_dir is None or "paths" in self.info:
            return
        # Kept until the first update has finished: the dispatch that follows
        # compiles this very lowering, and its executable then answers
        # ``memory_analysis()`` without another compilation.
        self._lowered = jitted.lower(*args)
        self.info.update(program_paths(self._lowered))
        print(
            f"[{self.info['role']}] program paths {self.info['paths']} "
            f"mosaic_calls {self.info['mosaic_calls']}",
            flush=True,
        )
        self._write()

    def record_startup(
        self, run_entry: float, loop_entry: float, first_sync_end: float
    ) -> None:
        """Called when the role's first blocking read-back has returned (the
        first instant the program knows an update finished on the device),
        with three unix stamps: from here on a compilation is news (the
        clock announces it), and ``startup`` — every span the ring holds
        that began by ``first_sync_end`` (the caller may have dispatched
        again since), the start-up's lanes ``startup`` and ``xla`` among them
        — is written into the record, once."""
        if self._clock is None or self._clock.announce:
            return
        self._clock.announce = True
        self._program_memory = _executable_memory(self._lowered)
        self._lowered = None
        if self._result_dir is None or self._tracer is None:
            return
        spans, wrapped = self._tracer.entries()
        spans = [s for s in spans if s[2] <= first_sync_end]
        self.info["startup"] = {
            "run_entry_unix_s": run_entry,
            "loop_entry_unix_s": loop_entry,
            "first_sync_end_unix_s": first_sync_end,
            "ring_wrapped": wrapped,
            "spans": spans,
        }
        self._note_memory()
        self._write()

    def close(self) -> None:
        """Idempotent (loops close on every exit path)."""
        if self._clock is None:
            return
        compiles = self._clock.record()
        if self._program_memory is not None:
            name, sizes = self._program_memory
            if name in compiles["programs"]:
                compiles["programs"][name]["memory"] = sizes
        self.info.update(self._clock.stats(), compiles=compiles)
        self._clock.close()
        self._clock = None
        self._note_memory()
        self._write()

    def _note_memory(self) -> None:
        if self.memory.n_stamps:  # a role that keeps the book
            self.info["memory"] = self.memory.record()

    def _write(self) -> None:
        if self._result_dir is None:
            return
        os.makedirs(self._result_dir, exist_ok=True)
        path = os.path.join(
            self._result_dir, f"backend-{self.info['role']}.json"
        )
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(self.info, f, indent=1)
        os.replace(tmp, path)


def force_cpu(n_devices: int) -> None:
    """Re-point this process at ``n_devices`` virtual CPU devices, even when
    a backend is already live (``__graft_entry__.dryrun_multichip`` runs
    after the caller may have compiled on the default backend). Everything
    else pins the CPU with ``JAX_PLATFORMS=cpu`` before jax starts."""
    import jax
    import jax.extend.backend as jeb

    jeb.clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", int(n_devices))
    got = len(jax.devices())
    if got != int(n_devices):
        raise RuntimeError(
            f"requested {n_devices} CPU devices but backend created {got}"
        )
