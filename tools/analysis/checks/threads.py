"""Thread discipline: the INVENTORY below declares every background-thread
entry point in the repo (the target function handed to ``threading.Thread``
or an in-thread request handler). Inside an entry function, any attribute
write (``self.x = ...``, ``obj.x += ...``) is a cross-thread publication and
must be either:

- lexically inside a ``with`` block whose context expression names a lock or
  condition (identifier containing "lock" or "cond"), or
- an attribute named in the entry's allowlist, each justified inline below.

Scope is the entry function itself (including nested defs/lambdas) — the
same single-function scope the seqlock and mailbox comments reason about.
Helpers called from the thread are owned by it and reviewed at their call
sites; widening to whole-call-graph analysis would drown the signal in
thread-owned state.
"""

from __future__ import annotations

import ast
from pathlib import Path

from tools.analysis.engine import Finding, REPO_ROOT, iter_functions, parse_file

NAME = "threads"

# file -> {entry qualname -> allowed attribute names}.
INVENTORY: dict[str, dict[str, frozenset[str]]] = {
    "tpu_rl/runtime/learner_service.py": {
        # _error: single-writer slot; publish() re-raises it from the update
        # loop after join(), so the GIL-atomic store needs no lock.
        "AsyncPublisher._run": frozenset({"_error"}),
    },
    "tpu_rl/data/prefetch.py": {
        # _error: single-writer slot drained by the consumer after the
        # sentinel; queue handoff orders the publication.
        # keep: a flag of the feeder's own open Span (obs/trace.py), an
        # object no other thread ever holds.
        # _started: the feeder's own monotonic counter of batches whose
        # placement has begun; the learner's memory book reads it (held()),
        # where a torn read is one batch off in one stamp.
        "PrefetchPipeline._run": frozenset({"_error", "keep", "_started"}),
    },
    "tpu_rl/obs/trace.py": {
        # The trace.json writer: its cursor and fragments are its own
        # (flush() runs here, and once more in close() after the join); it
        # reads the ring under the recorder's lock.
        "_Exporter._run": frozenset(),
    },
    "tpu_rl/checkpoint.py": {
        # Every shared write happens under self._cond by construction.
        "Checkpointer._run": frozenset(),
    },
    "tpu_rl/runtime/sebulba.py": {
        # Actor lane: publication is the BoundedPipe plus the params/stats
        # slots, and every slot write sits under self._lane_lock.
        "SebulbaLoop._actor_loop": frozenset(),
    },
    "tpu_rl/runtime/inference_service.py": {
        # _jnp: imported once at thread start, read-only afterwards.
        # error: single-writer slot; the runner reads it post-join.
        # n_flush_full/n_flush_deadline: serve-thread-owned monotonic
        # counters; the learner loop reads them for telemetry only, where a
        # torn read is a one-snapshot off-by-one, not a correctness hazard.
        # perf: GIL-atomic reference store at thread start (None until the
        # PerfTracker exists); the learner's telemetry emit only reads it,
        # and a pre-capture sighting just exports zero FLOPs for one tick.
        # buckets: the resolved bucket ladder, stored once before warmup
        # (GIL-atomic list reference, never mutated after); telemetry emits
        # read it to label per-bucket counters, and a pre-store sighting
        # sees the empty placeholder — zero rows for one tick, not a race.
        "InferenceService._serve": frozenset(
            {"_jnp", "error", "n_flush_full", "n_flush_deadline", "perf",
             "buckets"}
        ),
    },
    "tpu_rl/obs/exporters.py": {
        # Stdlib-threaded request handler; it must stay read-only over the
        # aggregator, hence the empty allowlist.
        "TelemetryHTTPServer.__init__.Handler.do_GET": frozenset(),
    },
}

_LOCKISH = ("lock", "cond", "mutex")


def _lock_guarded(with_node: ast.With) -> bool:
    for item in with_node.items:
        for sub in ast.walk(item.context_expr):
            name = None
            if isinstance(sub, ast.Attribute):
                name = sub.attr
            elif isinstance(sub, ast.Name):
                name = sub.id
            if name is not None and any(t in name.lower() for t in _LOCKISH):
                return True
    return False


def _attr_write_targets(node: ast.stmt) -> list[ast.Attribute]:
    targets: list[ast.expr] = []
    if isinstance(node, ast.Assign):
        targets = list(node.targets)
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        targets = [node.target]
    out: list[ast.Attribute] = []
    for t in targets:
        if isinstance(t, ast.Attribute):
            out.append(t)
        elif isinstance(t, (ast.Tuple, ast.List)):
            out.extend(e for e in t.elts if isinstance(e, ast.Attribute))
    return out


def _visit(
    fn: ast.AST, allowed: frozenset[str], qualname: str, path: str
) -> list[Finding]:
    findings: list[Finding] = []

    def walk(node: ast.AST, guarded: bool) -> None:
        for child in ast.iter_child_nodes(node):
            child_guarded = guarded
            if isinstance(child, ast.With):
                child_guarded = guarded or _lock_guarded(child)
            if isinstance(child, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                for attr in _attr_write_targets(child):
                    if attr.attr in allowed or guarded:
                        continue
                    findings.append(
                        Finding(
                            NAME, "TH001", path, child.lineno, qualname,
                            f"attribute write .{attr.attr} on a thread entry "
                            "path without a lock/cond guard or an inventory "
                            "allowlist entry (checks/threads.py)",
                        )
                    )
            walk(child, child_guarded)

    walk(fn, False)
    return findings


def scan_file(
    path: str | Path, inventory: dict[str, frozenset[str]], rel_path: str
) -> list[Finding]:
    tree = parse_file(path)
    fns = dict(iter_functions(tree))
    findings: list[Finding] = []
    for qualname, allowed in sorted(inventory.items()):
        fn = fns.get(qualname)
        if fn is None:
            findings.append(
                Finding(
                    NAME, "TH000", rel_path, 1, qualname,
                    "thread-inventory entry not found in file (renamed? "
                    "update INVENTORY in checks/threads.py)",
                )
            )
            continue
        findings.extend(_visit(fn, allowed, qualname, rel_path))
    return findings


def run(root: Path = REPO_ROOT) -> list[Finding]:
    findings: list[Finding] = []
    for rel_path, inventory in INVENTORY.items():
        findings.extend(scan_file(root / rel_path, inventory, rel_path))
    return findings
