"""Process bring-up: which backend a role may run on, what it records about
it, and where its compiles are cached.

One process owns a chip. The accelerator-owning roles (learner, colocated /
sebulba loop, an on-chip inference replica) open a :class:`BackendRecord`
first; it places the compile cache, refuses a CPU backend nobody asked for,
and logs and records the devices the role runs on. CPU roles (storage,
manager, workers, fleet replicas 1..N-1) are spawned with
``JAX_PLATFORMS=cpu`` in their environment (``runtime.runner.Supervisor``),
which stock JAX honours.

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
# ops/pallas_act.py, parallel/sequence.py: ``attn_bwd_pallas`` inside
# ``attn_flash_pallas`` where the backward is ops/pallas_attn_bwd.py's walk
# over the band's tiles), so one lowered
# module answers both "what did the gate choose" and "did Mosaic get it".
_MOSAIC_TARGET = "tpu_custom_call"
_PATH_SCOPES = re.compile(
    r"\b(lstm_pallas|lstm_scan|act_pallas|attn_flash_pallas|attn_bwd_pallas|attn_full|attn_window"
    r"|attn_global|attn_rope|mla|shortconv|eva|eva_pool|ssd_scan|ssd_pallas|gdn_scan|gdn_pallas|moe_experts"
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
    return {
        "paths": sorted(set(_PATH_SCOPES.findall(text))),
        "mosaic_calls": text.count(_MOSAIC_TARGET),
    }


class BackendRecord:
    """An accelerator-owning role's bring-up, opened first thing after any
    multihost init: compile cache, device check, one start-up log line, and
    the same facts in ``result_dir/backend-<role>.json`` — rewritten as the
    run learns which kernel paths its main program took, when its first
    update has finished on the device (``startup``: the recorder's ring so
    far, which a long run's ring forgets) and, at close, what it spent
    compiling (three totals and ``compiles``, :meth:`CompileClock.record`).
    ``chip_smoke.py`` asserts on the file instead of trusting an exit code;
    the benchmark's ``setup.*`` metrics read ``startup`` and ``compiles``.
    ``tracer`` is the role's :class:`~tpu_rl.obs.trace.TraceRecorder`, if it
    has one: compilations then are spans of its lane ``xla`` too."""

    def __init__(self, role: str, cfg, mesh=None, tracer=None):
        cache = enable_compile_cache()
        self._tracer = tracer
        self._clock = CompileClock(tracer, role)
        require_accelerator(role, cpu_ok=(cfg.learner_device == "cpu"))
        self._result_dir = cfg.result_dir
        self.info = {"role": role, **backend_info(mesh), "compile_cache": cache}
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
        self.info.update(program_paths(jitted.lower(*args)))
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
        self._write()

    def close(self) -> None:
        """Idempotent (loops close on every exit path)."""
        if self._clock is None:
            return
        self.info.update(self._clock.stats(), compiles=self._clock.record())
        self._clock.close()
        self._clock = None
        self._write()

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
