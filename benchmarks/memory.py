"""The chip owner's memory, read from the record it leaves behind.

A ``LearnerService`` keeps a memory book (``tpu_rl/utils/platform.py``
``MemoryBook``): the runtime's own books — live bytes, their lifetime peak,
the lifetime peak of the scratch reserved for running programs — stamped at
the exit of every site where a tree-sized buffer is made or let go, and the
pieces the program itself holds (``owners``: the train state, placed
batches, the broadcast's snapshots, the inference service's parameters, a
save's snapshot, the diag sums) sized from their leaves and counted where
they are made and dropped. It writes the book into ``backend-learner.json``
under ``memory`` when its first ``log-sync`` has returned and again at close.
``harness.Run.paths`` is that file, so the seven ``mem.*`` readers of
``benchmarks/metrics/`` need no capture and no ring (``benchmarks/MEMORY.md``
has the schema and how to read it by hand).

``peak_hbm_gib`` adds two lifetime peaks of the whole process; these readers
say what the *learner's loop* held at once (``window``) and what the process
had already set before the learner allocated a byte (the stamp ``run``).

A program from before the book, a chip owner that keeps none (the colocated
loop), a run that never reached its first ``log-sync`` and a backend without
books (the CPU: the runtime's columns are null) give None, and every reader
then returns None.
"""

from __future__ import annotations

from dataclasses import dataclass

GIB = 2**30
SITE, UNIX_S, UPDATE, IN_USE, PEAK_IN_USE, PEAK_RESERVED = range(6)
SNAPSHOT_OWNERS = ("publish-snapshot", "inference-params", "ckpt-snapshot")


@dataclass
class Memory:
    stamps: list  # [site, unix_s, update, bytes_in_use, peak_bytes_in_use, peak_bytes_reserved]
    owners: dict  # name -> bytes_each, alive_max, bound, alive (at every stamp)
    window: dict  # the loop's fullest stamp from the first log-sync on
    bytes_limit: int | None
    peak_hbm_bytes: int | None  # what the benchmark's own read-out added up

    def stamp(self, site: str) -> list | None:
        """The first stamp of ``site``."""
        return next((s for s in self.stamps if s[SITE] == site), None)

    def each(self, owner: str) -> int:
        return self.owners[owner]["bytes_each"] or 0

    def at_peak(self, owner: str) -> int:
        """Bytes ``owner`` held at the window's fullest stamp."""
        return self.each(owner) * self.window["alive"].get(owner, 0)

    @property
    def top(self) -> list:
        return self.window["stamp"]

    @property
    def named_bytes(self) -> int:
        return sum(self.at_peak(name) for name in self.owners)

    @property
    def window_peak_bytes(self) -> int:
        """What one chip held at once while the loop ran: its live peak and
        the scratch of the running update beside it."""
        return self.window["live_peak_bytes"] + self.window["scratch_bytes"]

    @property
    def saved_in_loop(self) -> bool:
        """A save was due inside the loop (the site ``ckpt-save`` stamps only
        there; the save at shutdown has a ``ckpt-d2h`` alone)."""
        return self.stamp("ckpt-save") is not None


def from_record(doc: dict, peak_hbm_bytes: int | None = None) -> Memory | None:
    """``doc`` is ``backend-<role>.json``."""
    rec = (doc or {}).get("memory")
    if not rec or not rec.get("window") or rec["window"].get("scratch_bytes") is None:
        return None
    return Memory(
        stamps=rec["stamps"],
        owners=rec["owners"],
        window=rec["window"],
        bytes_limit=rec.get("bytes_limit"),
        peak_hbm_bytes=peak_hbm_bytes,
    )


def of_run(run) -> Memory | None:
    return from_record(run.paths, run.device.get("memory_peak_bytes"))
