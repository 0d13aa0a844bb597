"""Shared-memory trajectory stores shared by the storage and learner processes.

Capability parity with the reference's flat ``mp.Array`` blocks + ``sh_data_num``
counter (``/root/reference/agents/storage_module/shared_batch.py:19-107``),
re-designed around the two access patterns it conflates:

- **OnPolicyStore** (capacity = ``batch_size``, reference
  ``reset_shared_on_policy_memory``): single writer, single reader, two
  generations of ``capacity`` slots. The writer fills one; full, it is sealed
  and the writer moves to the other if that one is free. The reader *leases*
  the oldest sealed generation — views of the shared memory, no copy, no lock
  held while it reads — and releases it when it is done (``consume`` is lease,
  copy, release). A writer never touches a sealed generation, so a handed-over
  batch cannot be torn. The reference's reader resets the counter while the
  writer may be mid-write (benign race, SURVEY.md §5.2); that race is left
  only where ``consume(need=k)`` takes a generation the writer is still
  filling, and there the writer validates an epoch counter after its slot
  write and re-writes if a consume intervened.
- **ReplayStore** (capacity = ``buffer_size``, reference
  ``reset_shared_buffer_memory``): ring overwrite + uniform sampling. The
  reference samples slots that are concurrently being overwritten
  (``agents/learner.py:168-195``); here each slot carries a seqlock version
  (even = stable, odd = write in progress) and the sampler retries torn reads.

Data lives in one ``mp.Array("f")`` per field, viewed as
``(capacity, seq_len, width)`` numpy arrays — same memory layout as the
reference's flat blocks, so the driver-visible capability (zero-copy IPC of
assembled trajectories) is identical.
"""

from __future__ import annotations

import multiprocessing as mp
from dataclasses import dataclass

import numpy as np

from tpu_rl.data.layout import BatchLayout
from tpu_rl.types import BATCH_FIELDS


@dataclass
class ShmHandles:
    """Raw multiprocessing primitives; picklable into child processes via
    ``mp.Process`` args (the reference's ``shm_ref`` dict,
    ``shared_batch.py:19-64``)."""

    arrays: dict  # field -> mp.Array("f", capacity * seq * width)
    versions: mp.Array  # per-slot seqlock counters ("L", capacity)
    count: mp.Value  # OnPolicy: filled slots this generation; Replay: total puts
    gen: mp.Value  # OnPolicy consume generation
    lock: mp.Lock
    capacity: int
    # Per-slot policy version of the window's OLDEST contributing tick
    # (-1 = unknown), the staleness sidecar of the learning-dynamics plane
    # (tpu_rl.obs.learn). Optional (default None) so handle pickles from
    # before this field keep constructing.
    vers: mp.Array | None = None
    # OnPolicy: the arrays hold ``generations`` x ``capacity`` slots, and
    # ``ring`` counts the generations ever (sealed, released). Defaults as
    # ``vers``: a replay ring has one generation and reads no ``ring``.
    generations: int = 1
    ring: mp.Array | None = None


SEALED, RELEASED = range(2)  # ShmHandles.ring


def alloc_handles(
    layout: BatchLayout, capacity: int, ctx=None, generations: int = 2
) -> ShmHandles:
    """Allocate from an explicit mp context — default spawn, matching the
    runner's start method (reference ``main.py:64``); fork-context primitives
    cannot be passed into spawn children. ``capacity`` is one batch; an
    on-policy store holds ``generations`` of them (the one being filled and
    the one being handed over), a replay ring is allocated with 1."""
    ctx = ctx or mp.get_context("spawn")
    rows = generations * capacity
    arrays = {
        f: ctx.Array("f", rows * layout.seq_len * layout.width(f), lock=False)
        for f in BATCH_FIELDS
    }
    vers = ctx.Array("q", rows, lock=False)
    np.frombuffer(vers, dtype=np.int64)[:] = -1  # -1 = version unknown
    return ShmHandles(
        arrays=arrays,
        versions=ctx.Array("L", capacity, lock=False),
        count=ctx.Value("q", 0, lock=False),
        gen=ctx.Value("q", 0, lock=False),
        lock=ctx.Lock(),
        capacity=capacity,
        vers=vers,
        generations=generations,
        ring=ctx.Array("q", 2, lock=False),
    )


class _StoreBase:
    """Numpy views over the handles; construct one per process (views bind to
    the inherited shared buffers, reference ``SMInterFace``,
    ``shared_batch.py:75-107``)."""

    def __init__(self, handles: ShmHandles, layout: BatchLayout):
        self.h = handles
        self.layout = layout
        self.capacity = handles.capacity
        self.generations = handles.generations
        self.views = {
            f: np.frombuffer(handles.arrays[f], dtype=np.float32).reshape(
                self.generations * handles.capacity,
                layout.seq_len,
                layout.width(f),
            )
            for f in BATCH_FIELDS
        }
        self.versions = np.frombuffer(handles.versions, dtype=np.uint64)
        self.slot_vers = (
            np.frombuffer(handles.vers, dtype=np.int64)
            if getattr(handles, "vers", None) is not None
            else None
        )

    def _write_slot(self, slot: int, window: dict) -> None:
        for f in BATCH_FIELDS:
            self.views[f][slot] = window[f]

    def _read_slots(self, idx: np.ndarray | slice) -> dict[str, np.ndarray]:
        return {f: self.views[f][idx].copy() for f in BATCH_FIELDS}

    def _write_vers(self, slots, vers: list | None, off: int, k: int) -> None:
        """Stamp the staleness sidecar for ``k`` slots (``vers[off:off+k]``,
        or -1 when the caller carries none)."""
        if self.slot_vers is None:
            return
        self.slot_vers[slots] = (
            vers[off : off + k] if vers is not None else -1
        )


class OnPolicyStore(_StoreBase):
    """Fill-then-hand-over batch store (single writer, single reader) of
    ``generations`` x ``capacity`` slots: generation ``g`` is the rows
    ``[g * capacity, (g + 1) * capacity)`` of every view. The writer fills
    generation ``sealed % generations`` and seals it when it is full; the
    reader takes generation ``released % generations`` once it is sealed.
    ``sealed - released`` generations are with the reader or waiting for it,
    so the writer is refused exactly when that is all of them."""

    def __init__(self, handles: ShmHandles, layout: BatchLayout):
        super().__init__(handles, layout)
        if handles.ring is None:
            raise ValueError(
                "these handles carry no generation ring: allocate them with "
                "alloc_handles"
            )
        self.ring = np.frombuffer(handles.ring, dtype=np.int64)
        self._lease: int | None = None  # the generation this instance holds

    def _rows(self, gen: int, lo: int, hi: int) -> slice:
        return slice(gen * self.capacity + lo, gen * self.capacity + hi)

    # ---------------------------------------------------------------- writer
    # put() retry bound: a partial consume can reset the filling generation
    # mid-write, forcing a re-write; each retry needs a fresh consume to
    # intervene, so in practice one retry suffices. The cap makes the
    # no-livelock contract explicit.
    MAX_PUT_RETRIES = 8

    def _claim(self) -> tuple[int, int, int] | None:
        """(epoch, filling generation, next free slot), or None when every
        generation is sealed: with the reader, or waiting for it."""
        h, ring = self.h, self.ring
        with h.lock:
            if ring[SEALED] - ring[RELEASED] >= self.generations:
                return None
            return h.gen.value, int(ring[SEALED] % self.generations), h.count.value

    def _publish(self, epoch: int, filled: int) -> bool:
        """Make the slots below ``filled`` the reader's, and seal the
        generation once that is all of them. False if a partial consume took
        the generation meanwhile: the write has to be made again."""
        h = self.h
        with h.lock:
            if h.gen.value != epoch:
                return False
            if filled == self.capacity:
                self.ring[SEALED] += 1
                filled = 0
            h.count.value = filled
            return True

    def put(self, window: dict, ver: int = -1) -> bool:
        """Write one (seq, width)-per-field trajectory window. Returns False
        when no generation is free (caller drops or retries later, matching
        the reference's ``num < mem_size`` guard, ``learner_storage.py:139``)
        or — bounded-retry contract — when partial consumes keep invalidating
        the write ``MAX_PUT_RETRIES`` times. ``ver`` is the window's
        policy-version sidecar (-1 = unknown)."""
        return self.put_many([window], [ver]) == 1

    def put_many(self, windows: list[dict], vers: list | None = None) -> int:
        """Write a burst of trajectory windows, each once, into its slot.
        Returns how many were accepted — the tail past the last free
        generation is rejected, preserving window order, so callers requeue
        ``windows[accepted:]`` exactly as they would a single rejected put.
        ``vers`` (aligned with ``windows``) stamps each slot's
        policy-version sidecar."""
        written = 0
        while written < len(windows):
            for _ in range(self.MAX_PUT_RETRIES):
                claim = self._claim()
                if claim is None:
                    return written
                epoch, gen, slot = claim
                k = min(len(windows) - written, self.capacity - slot)
                base = gen * self.capacity + slot
                for i in range(k):
                    self._write_slot(base + i, windows[written + i])
                self._write_vers(slice(base, base + k), vers, written, k)
                if self._publish(epoch, slot + k):
                    written += k
                    break
                # A partial consume reset the generation mid-burst: re-write
                # (this is the race the reference ignores).
            else:
                return written
        return written

    # ---------------------------------------------------------------- reader
    @property
    def size(self) -> int:
        """Windows written and not yet released: the sealed generations (a
        leased one among them) and the filled part of the one being filled."""
        h, ring = self.h, self.ring
        with h.lock:
            return int(ring[SEALED] - ring[RELEASED]) * self.capacity + h.count.value

    def lease(self) -> dict[str, np.ndarray] | None:
        """The oldest sealed generation as ``field -> (capacity, seq, width)``
        *views* of the shared memory plus the ``ver`` sidecar's, or None when
        none is sealed. No copy, and no lock while the caller reads: the
        writer stays out of the generation until :meth:`release`. One lease
        at a time; a reader that died with one leaves the generation sealed,
        and the next reader's lease hands it out again."""
        if self._lease is not None:
            raise RuntimeError("lease() with the previous lease still out")
        h, ring = self.h, self.ring
        with h.lock:
            if ring[SEALED] == ring[RELEASED]:
                return None
            self._lease = int(ring[RELEASED] % self.generations)
        rows = self._rows(self._lease, 0, self.capacity)
        out = {f: self.views[f][rows] for f in BATCH_FIELDS}
        if self.slot_vers is not None:
            # Staleness sidecar: per-row policy version, a NON-batch key
            # (Batch.from_mapping keys off BATCH_FIELDS and drops it).
            out["ver"] = self.slot_vers[rows]
        return out

    def release(self) -> None:
        """Give the leased generation back to the writer. Without a lease
        out, nothing: a feed releases on its way out whatever it held."""
        if self._lease is None:
            return
        with self.h.lock:
            self.ring[RELEASED] += 1
        self._lease = None

    def consume(self, need: int | None = None) -> dict[str, np.ndarray] | None:
        """If at least ``need`` (default: capacity) trajectories are ready,
        copy them out, reset their generation, and return ``field -> (n, seq,
        width)`` arrays; else None (reference gate ``sh_data_num >=
        batch_size`` + ``reset_data_num``, ``agents/learner.py:250-262``).
        A sealed generation goes first, whole, copied outside the lock; with
        none sealed and ``need`` below capacity, the filled part of the
        generation the writer is in."""
        need = self.capacity if need is None else need
        leased = self.lease()
        if leased is not None:
            out = {k: v.copy() for k, v in leased.items()}
            self.release()
            return out
        h = self.h
        with h.lock:
            if self.ring[SEALED] != self.ring[RELEASED]:
                return None  # sealed since the look above: the next call's
            n = h.count.value
            if n < need:
                return None
            rows = self._rows(int(self.ring[SEALED] % self.generations), 0, n)
            out = self._read_slots(rows)
            if self.slot_vers is not None:
                out["ver"] = self.slot_vers[rows].copy()
            h.gen.value += 1
            h.count.value = 0
        return out


class ReplayStore(_StoreBase):
    """Overwriting ring + uniform sampler (SAC replay). Single writer, any
    number of sampling readers."""

    # ---------------------------------------------------------------- writer
    def put(self, window: dict, ver: int = -1) -> bool:
        h = self.h
        with h.lock:
            total = h.count.value
        slot = total % self.capacity
        self.versions[slot] += 1  # odd: write in progress
        self._write_slot(slot, window)
        self._write_vers(slice(slot, slot + 1), [ver], 0, 1)
        self.versions[slot] += 1  # even: stable
        with h.lock:
            h.count.value = total + 1
        return True

    def put_many(self, windows: list[dict], vers: list | None = None) -> int:
        """Ring-write a burst of windows with one fancy-indexed write per
        field per chunk. Chunked to ``capacity`` so the slot set within a
        write stays duplicate-free; across chunks the ring overwrite order
        matches sequential :meth:`put` calls. Always accepts everything
        (the ring never rejects), returning ``len(windows)``."""
        h = self.h
        done = 0
        while done < len(windows):
            chunk = windows[done : done + self.capacity]
            k = len(chunk)
            with h.lock:
                total = h.count.value
            slots = (total + np.arange(k)) % self.capacity
            self.versions[slots] += 1  # odd: writes in progress
            for f in BATCH_FIELDS:
                self.views[f][slots] = [w[f] for w in chunk]
            self._write_vers(slots, vers, done, k)
            self.versions[slots] += 1  # even: stable
            with h.lock:
                h.count.value = total + k
            done += k
        return len(windows)

    # ---------------------------------------------------------------- reader
    @property
    def size(self) -> int:
        with self.h.lock:
            return min(self.h.count.value, self.capacity)

    @property
    def total_puts(self) -> int:
        """Trajectory windows EVER written (monotonic; the ring overwrites
        but ``count`` never resets) — the data-arrival odometer behind the
        off-policy update:data ratio gate."""
        with self.h.lock:
            return self.h.count.value

    def transitions_received(self) -> int:
        """Environment transitions ever received = windows x seq_len."""
        return self.total_puts * self.layout.seq_len

    def sample(
        self, batch: int, rng: np.random.Generator, max_retries: int = 8
    ) -> dict[str, np.ndarray] | None:
        """Uniform sample of ``batch`` trajectories; None until the ring holds
        at least ``batch`` (the reference latches "start once full",
        ``agents/learner.py:369-389`` — we only require >= batch). Torn slots
        (overwritten mid-read) are re-drawn via the seqlock; if a consistent
        sample cannot be assembled within the retry budget, returns None
        (callers treat it as "not ready") — a torn trajectory is NEVER
        returned, unlike the reference sampler (``agents/learner.py:168-195``).

        Vectorized: each retry round is one fancy-index copy per field over
        the still-pending rows plus two vector version reads (the round-1
        implementation looped slot-by-slot in Python — O(batch) interpreter
        iterations per learner update)."""
        n = self.size
        if n < batch:
            return None
        idx = rng.integers(0, n, size=batch)
        out = {
            f: np.empty(
                (batch, self.layout.seq_len, self.layout.width(f)), np.float32
            )
            for f in BATCH_FIELDS
        }
        if self.slot_vers is not None:
            out["ver"] = np.full(batch, -1, np.int64)
        pending = np.arange(batch)
        for _ in range(max_retries):
            sel = idx[pending]
            v1 = self.versions[sel].copy()
            chunk = {f: self.views[f][sel] for f in BATCH_FIELDS}  # copies
            # The sidecar rides inside the same seqlock bracket as the
            # field reads, so a sampled row's version is never torn either.
            sv = (
                self.slot_vers[sel].copy()
                if self.slot_vers is not None
                else None
            )
            v2 = self.versions[sel].copy()
            ok = (v1 % 2 == 0) & (v2 == v1)
            done = pending[ok]
            for f in BATCH_FIELDS:
                out[f][done] = chunk[f][ok]
            if sv is not None:
                out["ver"][done] = sv[ok]
            pending = pending[~ok]
            if pending.size == 0:
                return out
            idx[pending] = rng.integers(0, n, size=pending.size)  # re-draw
        return None  # retry budget exhausted; sample again later


def make_store(cfg, layout: BatchLayout, handles: ShmHandles | None = None):
    """Store factory keyed on the algo's on/off-policy nature (reference
    switcher ``main.py:310-321``). Pass ``handles`` in child processes."""
    from tpu_rl.config import is_off_policy

    off_policy = is_off_policy(cfg.algo)
    capacity = cfg.buffer_size if off_policy else cfg.batch_size
    if handles is None:
        handles = alloc_handles(
            layout, capacity, generations=1 if off_policy else 2
        )
    cls = ReplayStore if off_policy else OnPolicyStore
    return cls(handles, layout)
