"""Hot-path purity: the manifest below names the per-frame functions the
tracemalloc tests pin (tests/test_chaos.py, tests/test_obs.py). Two tiers:

- ``strict``: no string formatting, no logging/print, no comprehensions,
  no non-empty container displays, no known-allocating helpers. These run
  per frame/record at relay rate; a stray f-string is a measured regression.
- ``fmt``: formatting/logging only (f-strings, ``.format``, ``%``-format,
  ``print``, logger calls). For the worker tick, whose JOB is building the
  per-tick payload dict — container allocation is intrinsic there, but
  string rendering belongs in the cold fault helpers.

Empty displays (``parts = []``) are allowed in both tiers: they are the
idiomatic zero-cost accumulator init, not a per-element allocation.
"""

from __future__ import annotations

import ast
from pathlib import Path

from tools.analysis.engine import (
    Finding,
    REPO_ROOT,
    iter_functions,
    parse_file,
    terminal_name,
)

NAME = "hotpath"

STRICT = "strict"
FMT = "fmt"

# qualname -> tier, per file. Keep in lockstep with the tracemalloc pins:
# adding a pin without a manifest entry leaves the path unchecked statically.
MANIFEST: dict[str, dict[str, str]] = {
    "tpu_rl/runtime/transport.py": {
        "Pub.send_raw": STRICT,
        "Sub.recv_raw": STRICT,
        "_RingWriter.write": STRICT,
        "_RingReader.read": STRICT,
        "ShmPub.send_raw": STRICT,
        "ShmConsumer.drain_frames": STRICT,
    },
    "tpu_rl/runtime/manager.py": {
        "Manager._pump": STRICT,
        "Manager._ingest": STRICT,
    },
    "tpu_rl/runtime/storage.py": {
        "LearnerStorage._ingest": STRICT,
        "LearnerStorage._epoch_admit": STRICT,
        "LearnerStorage._touch_member": STRICT,
        "LearnerStorage._poll_epoch": STRICT,
        "LearnerStorage._ingress_admit": STRICT,
        "MembershipTable.strike": STRICT,
        "MembershipTable.is_quarantined": STRICT,
        "MembershipTable.probe_clear": STRICT,
    },
    "tpu_rl/heal/ingress.py": {
        "IngressGuard.tick_clean": STRICT,
    },
    "tpu_rl/chaos/inject.py": {
        "DataChaos.on_tick": STRICT,
    },
    "tpu_rl/data/assembler.py": {
        "RolloutAssembler.push_tick": STRICT,
    },
    "tpu_rl/obs/goodput.py": {
        # The ledger tick rides every role's main loop (storage: per
        # recv/ingest pass): one float add, no allocation.
        "GoodputLedger.add": STRICT,
    },
    "tpu_rl/obs/trace.py": {
        # The span primitive rides every statement group of the learner's
        # loop and its feeder/publisher/writer lanes: a clock pair, one small
        # object, one ring tuple. The annotation's name is built once per
        # site in the cold _site(), never here.
        "TraceRecorder.span": STRICT,
        "TraceRecorder.add": STRICT,
        "TraceRecorder.sample": STRICT,
        "Span.__enter__": STRICT,
        "Span.__exit__": STRICT,
    },
    "tpu_rl/utils/platform.py": {
        # The memory book's stamp and the owners' count-up / count-down sit
        # beside the learner's dispatch (publish, log-sync, ckpt-save; the
        # batch count once a dispatch): the runtime's own answer, one row and
        # the ring's counter sample, nothing else. What a kept stamp costs
        # more (the owners alive at it) is the cold _keep().
        "MemoryBook.stamp": STRICT,
        "MemoryBook.hold": STRICT,
        "MemoryBook.drop": STRICT,
        "MemoryBook.count": STRICT,
    },
    "tpu_rl/data/prefetch.py": {
        "PrefetchPipeline.held": STRICT,
    },
    "tpu_rl/runtime/worker.py": {
        "Worker.run": FMT,
    },
    "tpu_rl/runtime/sebulba.py": {
        # The lane seam: both sides cross it once per produced batch, and
        # any blocking inside is *measured* (queue-wait) — allocation here
        # would pollute the backpressure signal itself.
        "BoundedPipe.put": STRICT,
        "BoundedPipe.get": STRICT,
    },
    "tpu_rl/obs/learn.py": {
        # The learning-dynamics fold rides every learner dispatch (one
        # extra device program, zero syncs — the whole plane's overhead
        # contract, tests/test_learn_diag.py); the host-side wrapper must stay
        # allocation-free so the cost is the device fold alone. drain() is
        # cold (log cadence) and deliberately NOT pinned.
        "DiagAccumulator.add": STRICT,
    },
}

# Helpers whose call is an allocation/serialization bomb regardless of tier.
ALLOCATING_HELPERS = frozenset({"deepcopy", "dumps", "format_map", "getLogger"})

# Receivers whose method calls are logging, not data flow.
_LOGGER_NAMES = frozenset({"logging", "logger", "log"})


def _visit(fn: ast.AST, tier: str, qualname: str, path: str) -> list[Finding]:
    findings: list[Finding] = []

    def add(code: str, node: ast.AST, msg: str) -> None:
        findings.append(
            Finding(NAME, code, path, getattr(node, "lineno", 0), qualname, msg)
        )

    for node in ast.walk(fn):
        if isinstance(node, ast.JoinedStr):
            add("HP001", node, "f-string allocates per call on a hot path")
        elif isinstance(node, ast.Call):
            t = terminal_name(node.func)
            if t == "format" and isinstance(node.func, ast.Attribute):
                add("HP002", node, "str.format allocates per call on a hot path")
            elif t == "print":
                add("HP006", node, "print on a hot path (I/O + formatting)")
            elif (
                isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in _LOGGER_NAMES
            ):
                add("HP006", node, f"logging call {node.func.value.id}.{t} on a hot path")
            elif t in ALLOCATING_HELPERS:
                add("HP007", node, f"known-allocating helper {t}() on a hot path")
        elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod):
            if isinstance(node.left, ast.Constant) and isinstance(node.left.value, str):
                add("HP003", node, "%-format allocates per call on a hot path")
        elif tier == STRICT:
            if isinstance(
                node, (ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp)
            ):
                add("HP004", node, "comprehension allocates per call on a hot path")
            elif isinstance(node, (ast.List, ast.Set)) and node.elts:
                add("HP005", node, "non-empty container literal on a hot path")
            elif isinstance(node, ast.Dict) and node.keys:
                add("HP005", node, "non-empty dict literal on a hot path")
    return findings


def scan_file(
    path: str | Path, manifest: dict[str, str], rel_path: str
) -> list[Finding]:
    """Check the manifest entries of one file. A missing qualname is itself
    a finding (HP000): a rename must not silently drop coverage."""
    tree = parse_file(path)
    fns = dict(iter_functions(tree))
    findings: list[Finding] = []
    for qualname, tier in sorted(manifest.items()):
        fn = fns.get(qualname)
        if fn is None:
            findings.append(
                Finding(
                    NAME, "HP000", rel_path, 1, qualname,
                    "hot-path manifest entry not found in file "
                    "(renamed? update the manifest in checks/hotpath.py)",
                )
            )
            continue
        findings.extend(_visit(fn, tier, qualname, rel_path))
    return findings


def run(root: Path = REPO_ROOT) -> list[Finding]:
    findings: list[Finding] = []
    for rel_path, manifest in MANIFEST.items():
        findings.extend(scan_file(root / rel_path, manifest, rel_path))
    return findings
