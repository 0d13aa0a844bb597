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

# Fixed in-checkout cache location (a directory that moves never hits):
# <repo>/.jax_cache.
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

# Pallas TPU kernels lower to this custom-call target; scan / XLA paths never
# emit it. Kernel and fallback paths are wrapped in the named scopes below
# (models/cells.py, models/granite_hybrid.py: ``ssd_pallas`` inside
# ``ssd_scan`` when the scan took its kernels, ops/moe.py: ``moe_gmm_pallas``
# inside ``moe_experts`` likewise, and ``moe_row_add_pallas`` where a trip's
# rows are added into the tokens by ops/pallas_moe.py's kernel,
# ops/pallas_act.py, parallel/sequence.py), so one lowered
# module answers both "what did the gate choose" and "did Mosaic get it".
_MOSAIC_TARGET = "tpu_custom_call"
_PATH_SCOPES = re.compile(
    r"\b(lstm_pallas|lstm_scan|act_pallas|attn_flash_pallas|attn_full|attn_window|attn_global"
    r"|attn_rope|ssd_scan|ssd_pallas|moe_experts|moe_gmm_pallas|moe_row_add_pallas)\b"
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


class CompileClock:
    """Backend-compile seconds (cache retrievals included) and persistent
    cache hits/misses of this process, from ``jax.monitoring`` events, since
    construction."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def stats(self) -> dict:
        return {
            "compile_s": round(self.seconds, 3),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
        }

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
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
    run learns which kernel paths its main program took and, at close, what
    it spent compiling. ``chip_smoke.py`` asserts on the file instead of
    trusting an exit code."""

    def __init__(self, role: str, cfg, mesh=None):
        cache = enable_compile_cache()
        self._clock = CompileClock()
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

    def close(self) -> None:
        """Idempotent (loops close on every exit path)."""
        if self._clock is None:
            return
        self.info.update(self._clock.stats())
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
