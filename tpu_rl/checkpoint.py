"""Crash-atomic, async checkpoint / resume via orbax.

Capability parity with the reference's ``torch.save`` every
``model_save_interval`` updates + newest-file-wins resume
(``/root/reference/agents/learner_module/ppo/learning.py:113-119``,
``utils/utils.py:93-98``, ``main.py:128-146``), upgraded twice over:

**Atomicity.** The reference (and our first cut) could crash mid-write and
leave a torn checkpoint that the newest-index scan would happily restore.
Here a save is a two-phase commit: orbax writes the tree into its final
``{model_dir}/{algo}_{idx}`` directory, and only after
``wait_until_finished()`` is a ``COMMITTED`` marker file atomically placed
*inside* that directory (tmp + ``os.replace``). Every read path — worker
warm-start (:func:`restore_actor_params`), learner resume
(:meth:`Checkpointer.restore_run`), GC — filters on the marker, so a torn
save is simply invisible: readers fall back to the previous committed index.
The marker doubles as the run-meta record (update idx, run epoch, learner
PRNG key, config fingerprint), widening the payload from "train state" to
"full run state" — a resumed run continues its RNG stream and update index
instead of restarting them, and refuses to load a checkpoint produced by a
structurally different config unless forced.

**Asynchrony.** ``save()`` can hand the work to a background thread (the
PR-1 ``AsyncPublisher`` recipe): the caller takes a device-side snapshot
(``jnp.copy`` — donation-proof — plus ``copy_to_host_async``) and returns;
the thread does the blocking D2H ``device_get``, the orbax write, the
commit, and the GC. Saves are latest-wins: a newer snapshot replaces a
queued-but-unstarted older one (counted in ``n_skipped``). Wall time per
committed save is surfaced via :meth:`Checkpointer.drain_save_secs` so the
learner can publish the sync-vs-async A/B as a telemetry timer.

Directory naming keeps the reference's ``{algo}_{idx}`` convention so
"newest index wins" is preserved.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp

# The fingerprinted field list lives in config.py (jax-free) so
# Config.validate can enforce the population plane's searchable-field rule
# against it; re-exported here under the historical name.
from tpu_rl.config import FINGERPRINT_FIELDS as _FINGERPRINT_FIELDS
from tpu_rl.obs.trace import span_of

# Marker filename inside a committed checkpoint dir. Its presence is the
# commit point; its content is the run-meta JSON. Orbax ignores foreign
# files in the directory on restore (probed against orbax 0.11.32).
COMMIT_MARKER = "COMMITTED"


def resume_fingerprint(cfg) -> str:
    """Stable hash of the structure-defining config subset. Stored in every
    commit marker; checked on resume (``Config.resume_force`` overrides)."""
    sub = {k: getattr(cfg, k) for k in _FINGERPRINT_FIELDS}
    blob = json.dumps(sub, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def is_committed(path: str) -> bool:
    return os.path.exists(os.path.join(path, COMMIT_MARKER))


def read_meta(path: str) -> dict:
    """Run-meta of a committed checkpoint dir; {} when absent/corrupt (a
    truncated marker is treated as not-quite-committed metadata, but the
    tree itself is orbax-complete by write ordering, so readers may still
    use it with default meta)."""
    try:
        with open(os.path.join(path, COMMIT_MARKER)) as f:
            meta = json.load(f)
        return meta if isinstance(meta, dict) else {}
    except (OSError, ValueError):
        return {}


def _ckpt_dirs(
    model_dir: str, algo: str, committed_only: bool = True
) -> list[tuple[int, str]]:
    """[(idx, path)] of existing checkpoints, sorted by idx (reference index
    parser ``utils/utils.py:93-98``). By default only COMMITTED dirs are
    visible — torn/in-flight saves do not exist as far as readers know."""
    if not os.path.isdir(model_dir):
        return []
    out = []
    pat = re.compile(re.escape(algo) + r"_(\d+)$")
    for name in os.listdir(model_dir):
        m = pat.match(name)
        if not m:
            continue
        path = os.path.join(model_dir, name)
        if committed_only and not is_committed(path):
            continue
        out.append((int(m.group(1)), path))
    return sorted(out)


def latest_committed(model_dir: str, algo: str) -> tuple[int, str] | None:
    """(idx, path) of the newest committed checkpoint, or None."""
    found = _ckpt_dirs(os.path.abspath(model_dir), algo)
    return found[-1] if found else None


def copy_committed(
    src_path: str,
    dst_model_dir: str,
    algo: str,
    dst_idx: int,
    meta_overrides: dict | None = None,
) -> str:
    """Cross-member checkpoint copy preserving two-phase commit semantics —
    the PBT exploit step (``tpu_rl.population``): a loser member adopts the
    winner's newest COMMITTED tree as ``{dst_model_dir}/{algo}_{dst_idx}``.

    The copy re-enacts the write ordering of :meth:`Checkpointer._write`:
    the orbax tree files are copied WITHOUT the marker, then the marker —
    the source's run-meta with ``meta_overrides`` applied (the exploit sets
    ``idx``/``epoch``/lineage keys) — is placed last via tmp + fsync +
    ``os.replace``. A crash or SIGKILL at ANY point mid-copy therefore
    leaves an uncommitted dir that no reader ever sees: the destination
    member's next resume falls back to its own previous committed
    checkpoint, and the debris is swept by ``Checkpointer._clean_torn`` at
    its next init. Pure host-side file I/O — callers (the controller) never
    need the destination member's train-state structure.
    """
    if not is_committed(src_path):
        raise ValueError(f"source checkpoint {src_path} is not committed")
    dst_path = os.path.join(
        os.path.abspath(dst_model_dir), f"{algo}_{dst_idx}"
    )
    shutil.rmtree(dst_path, ignore_errors=True)  # stale torn debris only
    os.makedirs(os.path.dirname(dst_path), exist_ok=True)
    shutil.copytree(
        src_path,
        dst_path,
        ignore=shutil.ignore_patterns(COMMIT_MARKER, f".{COMMIT_MARKER}.tmp"),
    )
    meta = {**read_meta(src_path), **(meta_overrides or {}), "idx": dst_idx}
    tmp = os.path.join(dst_path, f".{COMMIT_MARKER}.tmp")
    with open(tmp, "w") as f:
        json.dump(meta, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, os.path.join(dst_path, COMMIT_MARKER))
    return dst_path


def restore_actor_params(model_dir: str, algo: str):
    """Actor parameter tree of the NEWEST *committed* checkpoint, as host
    numpy arrays wrapped ``{"actor": ...}`` (the worker acting contract), or
    None when no committed checkpoint exists.

    This is the worker warm-start path: the reference loads the newest
    checkpoint into every worker at spawn (``/root/reference/main.py:247-252``
    via the newest-file scan ``:128-146``) so actors start from the trained
    policy instead of random init. Template-free raw restore: callers (the
    worker role) don't build a learner train state just to know its structure.

    Falls back newest→oldest on restore failure: a spawning worker can lose
    the race with the learner's GC (the dir it listed vanishes) — the next
    older committed checkpoint is the correct answer, not a crash.
    """
    found = _ckpt_dirs(os.path.abspath(model_dir), algo)
    if not found:
        return None
    import numpy as np
    import orbax.checkpoint as ocp

    with ocp.PyTreeCheckpointer() as ckpt:
        for _idx, path in reversed(found):
            try:
                # Explicit numpy restore: the learner saved device arrays
                # whose recorded sharding names ITS devices (the chip); the
                # caller is a CPU process that must not need them.
                tree = ckpt.metadata(path).item_metadata.tree
                raw = ckpt.restore(
                    path,
                    restore_args=jax.tree.map(
                        lambda _: ocp.RestoreArgs(restore_type=np.ndarray),
                        tree,
                    ),
                )
            except Exception:
                continue  # lost a GC race or damaged tree: try the previous
            # TrainState nests under "params"/"actor"; SACState keeps
            # "actor_params".
            params = raw.get("params")
            actor = params.get("actor") if isinstance(params, dict) else None
            if actor is None:
                actor = raw.get("actor_params")
            if actor is not None:
                return {"actor": actor}
    return None


LANE = "ckpt-writer"


def _snapshot(state: Any) -> Any:
    """Donation-proof device-side copy with D2H started in the background
    (the AsyncPublisher recipe): the caller's buffers may be donated to the
    next train step, so the background writer must own its own."""

    def snap(x):
        if isinstance(x, jax.Array):
            y = jnp.copy(x)
            try:
                y.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass  # committed arrays on some backends; device_get covers it
            return y
        return x

    return jax.tree_util.tree_map(snap, state)


class Checkpointer:
    """Single-writer checkpoint manager (lives in the learner process).

    ``async_save=False`` (the default, and the direct-caller/test contract)
    keeps ``save()`` blocking-but-atomic. The learner service passes
    ``Config.ckpt_async`` to move the D2H + disk write off the update loop.
    """

    def __init__(
        self,
        model_dir: str,
        algo: str,
        keep: int = 5,
        async_save: bool = False,
        tracer=None,
        book=None,
    ):
        # The owner's TraceRecorder: a save's blocking D2H and its disk write
        # are the spans "ckpt-d2h" / "ckpt-write" of the lane "ckpt-writer".
        self._span = span_of(tracer)
        # The owner's MemoryBook: an async save's device snapshot is one
        # "ckpt-snapshot" from save() until the writer has it on the host,
        # and the books are stamped there ("ckpt-d2h").
        self._book = book
        self.model_dir = os.path.abspath(model_dir)
        self.algo = algo
        self.keep = max(1, int(keep))
        self.async_save = bool(async_save)
        os.makedirs(self.model_dir, exist_ok=True)
        self._clean_torn()
        import orbax.checkpoint as ocp

        if jax.process_count() > 1:
            # Single-writer contract in a multiprocess runtime (pod-Anakin:
            # the chief saves, every host restores through its own handle).
            # Default orbax inserts cross-host barriers around every
            # save/restore, so a chief-gated save would deadlock the pod —
            # scope the barrier set to this process alone. (Re-checked on
            # orbax 0.11.32: without the scoping the pod's first save never
            # commits, tests/test_colocated_multihost.py.)
            from orbax.checkpoint import options as ocp_options

            mp = ocp_options.MultiprocessingOptions(
                primary_host=jax.process_index(),
                active_processes={jax.process_index()},
                barrier_sync_key_prefix=f"tpu_rl_p{jax.process_index()}",
            )
            self._ckpt = ocp.StandardCheckpointer(multiprocessing_options=mp)
        else:
            self._ckpt = ocp.StandardCheckpointer()
        # --- async machinery (idle unless async_save) ---
        self._cond = threading.Condition()
        self._queued: tuple[Any, int, dict] | None = None
        self._inflight = False
        self._stop = False
        self._error: Exception | None = None
        self._durations: list[float] = []
        self._thread: threading.Thread | None = None
        # --- introspection ---
        self.n_saves = 0  # committed saves
        self.n_skipped = 0  # latest-wins drops of queued-but-unstarted saves
        self.last_save_secs = 0.0

    # ------------------------------------------------------------- lifecycle
    def _clean_torn(self) -> None:
        """Remove torn dirs left by a crash mid-save. Safe: this process is
        the only writer (the supervisor guarantees the previous learner
        incarnation is dead before respawn), and no reader ever sees an
        uncommitted dir."""
        for _idx, path in _ckpt_dirs(
            self.model_dir, self.algo, committed_only=False
        ):
            if not is_committed(path):
                shutil.rmtree(path, ignore_errors=True)

    def _ensure_thread(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="ckpt-writer", daemon=True
            )
            self._thread.start()

    def _raise_pending_error(self) -> None:
        with self._cond:
            err, self._error = self._error, None
        if err is not None:
            raise RuntimeError("async checkpoint save failed") from err

    # ------------------------------------------------------------------ save
    def save(self, state: Any, idx: int, meta: dict | None = None) -> str:
        """Save the full train-state pytree as ``{model_dir}/{algo}_{idx}``
        with run-meta ``meta`` committed alongside. Blocking when
        ``async_save`` is off; otherwise snapshots device-side and returns
        (an error from a previous background save re-raises here)."""
        self._raise_pending_error()
        path = os.path.join(self.model_dir, f"{self.algo}_{idx}")
        meta = dict(meta or {})
        if not self.async_save:
            t0 = time.perf_counter()
            self._write(self._to_host(state), idx, meta)
            self._record(time.perf_counter() - t0)
            return path
        snap = _snapshot(state)
        book = self._book
        if book is not None:
            book.declare("ckpt-snapshot", snap)
            book.hold("ckpt-snapshot")
        self._ensure_thread()
        with self._cond:
            if self._queued is not None:
                self.n_skipped += 1  # latest wins: newer snapshot replaces
                if book is not None:
                    book.drop("ckpt-snapshot")
            self._queued = (snap, idx, meta)
            snap = None  # the writer's from here on: this frame must not hold it
            self._cond.notify_all()
        return path

    def _run(self) -> None:
        while True:
            with self._cond:
                while self._queued is None and not self._stop:
                    self._cond.wait()
                if self._queued is None:  # stop, nothing pending
                    return
                snap, idx, meta = self._queued
                self._queued = None
                self._inflight = True
            t0 = time.perf_counter()
            try:
                host_state = self._to_host(snap)
                # The snapshot is device memory (a copy of the train state):
                # let go of it once it is on the host — not after the write,
                # and not when the next save rebinds the name: this frame
                # would hold it until then, beside the next snapshot.
                snap = None
                if self._book is not None:
                    self._book.drop("ckpt-snapshot")
                    self._book.stamp("ckpt-d2h", idx, tid=LANE)
                self._write(host_state, idx, meta)
                dur: float | None = time.perf_counter() - t0
            except Exception as e:  # surfaced on the next save()/flush()
                dur = None
                with self._cond:
                    self._error = e
            snap = host_state = None
            with self._cond:
                self._inflight = False
                if dur is not None:
                    self._record(dur)
                self._cond.notify_all()

    def _to_host(self, state: Any) -> Any:
        with self._span("ckpt-d2h", tid=LANE):
            return jax.device_get(state)

    def _write(self, host_state: Any, idx: int, meta: dict) -> None:
        """The two-phase commit: orbax tree write, then the atomic marker."""
        with self._span("ckpt-write", tid=LANE):
            path = os.path.join(self.model_dir, f"{self.algo}_{idx}")
            self._ckpt.save(path, host_state, force=True)
            self._ckpt.wait_until_finished()
            meta.setdefault("idx", idx)
            meta.setdefault("algo", self.algo)
            meta.setdefault("saved_at", time.time())
            tmp = os.path.join(path, f".{COMMIT_MARKER}.tmp")
            with open(tmp, "w") as f:
                json.dump(meta, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, os.path.join(path, COMMIT_MARKER))
            self._gc()

    def _record(self, dur: float) -> None:
        self.n_saves += 1
        self.last_save_secs = dur
        self._durations.append(dur)

    # ----------------------------------------------------------- observation
    @property
    def pending(self) -> int:
        """Saves accepted but not yet committed (0-2: one queued + one in
        flight) — the ``learner-ckpt-pending`` gauge."""
        with self._cond:
            return (self._queued is not None) + self._inflight

    def drain_save_secs(self) -> list[float]:
        """Wall seconds of saves committed since the last drain — feeds the
        ``learner-ckpt-time`` timer regardless of which thread did the
        write."""
        with self._cond:
            out, self._durations = self._durations, []
        return out

    def flush(self, timeout: float | None = None) -> None:
        """Block until every accepted save is committed (async mode)."""
        with self._cond:
            deadline = None if timeout is None else time.monotonic() + timeout
            while self._queued is not None or self._inflight:
                left = None if deadline is None else deadline - time.monotonic()
                if left is not None and left <= 0:
                    break
                self._cond.wait(timeout=left)
        self._raise_pending_error()

    # --------------------------------------------------------------- restore
    def latest_idx(self) -> int | None:
        found = _ckpt_dirs(self.model_dir, self.algo)
        return found[-1][0] if found else None

    def restore_latest(self, template: Any) -> tuple[Any, int] | None:
        """Newest-*committed*-index-wins restore into the structure of
        ``template``. Returns (state, idx) or None when no committed
        checkpoint exists."""
        out = self.restore_run(template)
        return (out[0], out[1]) if out is not None else None

    def restore_run(
        self,
        template: Any,
        fingerprint: str | None = None,
        force: bool = False,
    ) -> tuple[Any, int, dict] | None:
        """Full-run resume: (state, idx, meta) of the newest committed
        checkpoint, or None. When ``fingerprint`` is given and the stored
        one disagrees, refuses (RuntimeError) unless ``force`` — restoring
        an optimizer/params tree produced by a structurally different
        config is silent corruption, not resume."""
        found = _ckpt_dirs(self.model_dir, self.algo)
        if not found:
            return None
        idx, path = found[-1]
        meta = read_meta(path)
        stored = meta.get("fingerprint")
        if fingerprint is not None and stored is not None and stored != fingerprint:
            if not force:
                raise RuntimeError(
                    f"checkpoint {path} was written by a different config "
                    f"(fingerprint {stored} != {fingerprint}); pass "
                    "--resume-force to override"
                )
            print(
                f"[checkpoint] WARNING: fingerprint mismatch ({stored} != "
                f"{fingerprint}) overridden by resume_force",
                flush=True,
            )
        restored = self._ckpt.restore(
            path, jax.tree_util.tree_map(lambda x: x, template)
        )
        return restored, idx, meta

    def restore_nth_latest(
        self,
        template: Any,
        n: int = 1,
        fingerprint: str | None = None,
        force: bool = False,
    ) -> tuple[Any, int, dict] | None:
        """Restore the ``n``-th newest committed checkpoint (``n=1`` is the
        newest — equivalent to :meth:`restore_run`; ``n=2`` the previous).
        The watchdog rollback path uses ``n=2``: the newest commit may
        already contain the divergence it is rolling back from. ``n`` past
        the oldest clamps to the oldest committed checkpoint. Same
        fingerprint-refusal contract as :meth:`restore_run`."""
        found = _ckpt_dirs(self.model_dir, self.algo)
        if not found:
            return None
        idx, path = found[max(0, len(found) - max(1, int(n)))]
        meta = read_meta(path)
        stored = meta.get("fingerprint")
        if fingerprint is not None and stored is not None and stored != fingerprint:
            if not force:
                raise RuntimeError(
                    f"checkpoint {path} was written by a different config "
                    f"(fingerprint {stored} != {fingerprint}); pass "
                    "--resume-force to override"
                )
            print(
                f"[checkpoint] WARNING: fingerprint mismatch ({stored} != "
                f"{fingerprint}) overridden by resume_force",
                flush=True,
            )
        restored = self._ckpt.restore(
            path, jax.tree_util.tree_map(lambda x: x, template)
        )
        return restored, idx, meta

    def discard_above(self, idx: int) -> int:
        """Remove every COMMITTED checkpoint with index > ``idx``; returns
        how many were removed. The rollback path calls this (after
        :meth:`flush`, so no in-flight save can commit a newer dir behind
        our back) — without it the next newest-wins resume would faithfully
        reload the divergence that was just rolled back."""
        removed = 0
        for ck_idx, path in _ckpt_dirs(self.model_dir, self.algo):
            if ck_idx > idx:
                shutil.rmtree(path, ignore_errors=True)
                removed += 1
        return removed

    # -------------------------------------------------------------------- gc
    def _gc(self) -> None:
        """Bound disk usage (the reference keeps every checkpoint forever).
        Operates on COMMITTED dirs only: an uncommitted dir is either a
        concurrent in-flight save (deleting it would corrupt the write) or
        torn debris already invisible to readers (cleaned at next init) —
        and the newest committed checkpoint is never removed (keep >= 1),
        so a restore that just listed it cannot have it deleted mid-read
        except for dirs that stopped being newest, which the readers'
        newest→oldest retry loop absorbs."""
        found = _ckpt_dirs(self.model_dir, self.algo)
        for _idx, path in found[: -self.keep]:
            shutil.rmtree(path, ignore_errors=True)

    def close(self) -> None:
        """Flush pending saves, stop the writer thread, release orbax."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            # The writer drains the queued save before honoring stop.
            self._thread.join(timeout=120.0)
            self._thread = None
        self._ckpt.close()
        self._raise_pending_error()
