"""Protocol/mailbox consistency.

Wire side (``tpu_rl/runtime/protocol.py``):

- PC001: every ``struct.Struct`` named in STRUCT_DECLS must have a declared
  ``*_BYTES`` constant equal to ``struct.calcsize`` of its format — the
  static twin of the import-time asserts, so the mismatch is also visible
  without importing (and the constant can't be deleted).
- PC002: every ``Protocol.X`` named in the ``TRACE_KINDS`` allowlist must be
  a member of the ``Protocol`` enum (peek's accepted set is the enum itself,
  so this pins the allowlist inside what peek accepts).
- PC003: ``Protocol`` enum values must be unique and contiguous from 0 —
  ``TRACE_KINDS_MASK`` and the native validator index bitmask tables by
  proto byte.
- PC004: the Model layout (``[proto, head, leaf 0, ..., leaf n-1]``, marked
  by ``Codec.PARTS`` in the same 12-byte ``_HEADER`` — PC001 covers it, no
  struct of its own): ``PARTS_KINDS``, the only kinds that may carry more
  than three parts, must name members of the ``Protocol`` enum and share
  none with ``TRACE_KINDS`` (a third part is a trailer or a leaf, never
  either), and the ``Codec`` enum's values must be unique and fit the
  header's one byte.

Mailbox side (``tpu_rl/runtime/mailbox.py`` + every reader/writer):

- PC010: ``SLOT_*`` values unique and contiguous from 0, ``STAT_SLOTS`` ==
  slot count.
- PC011: no bare integer index into the stat mailbox array — readers and
  writers must spell the named constant, the whole point of the module.
- PC012: every ``SLOT_*`` constant is referenced (as a name, not an import)
  in at least two modules outside mailbox.py — one writer side and one
  reader side. A deleted reference that orphans a slot to a single side
  fails here.
"""

from __future__ import annotations

import ast
import struct
from pathlib import Path

from tools.analysis.engine import Finding, REPO_ROOT, parse_file, rel

NAME = "protocol"

PROTOCOL_FILE = "tpu_rl/runtime/protocol.py"
# struct.Struct assign name -> declared byte-count constant name.
STRUCT_DECLS = {"_HEADER": "HEADER_BYTES", "_TRAILER": "TRAILER_BYTES"}
ENUM_NAME = "Protocol"
ALLOWLIST_NAME = "TRACE_KINDS"
PARTS_NAME = "PARTS_KINDS"
CODEC_ENUM = "Codec"

MAILBOX_FILE = "tpu_rl/runtime/mailbox.py"
SLOT_PREFIX = "SLOT_"
SLOT_TOTAL = "STAT_SLOTS"
# Names the stat mailbox array travels under at read/write sites.
MAILBOX_ARRAY_NAMES = frozenset({"sa", "stat_array"})
# Modules scanned for bare indices and slot cross-references.
SLOT_USER_DIR = "tpu_rl"
# Slots written and read through one shared helper each side still need two
# distinct modules touching them; mailbox.py itself never counts.
MIN_SLOT_MODULES = 2


def _const_int_assigns(tree: ast.Module, prefix: str | None = None) -> dict[str, tuple[int, int]]:
    """Module-level ``NAME = <int literal>`` -> (value, lineno)."""
    out: dict[str, tuple[int, int]] = {}
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, int)
            and not isinstance(node.value.value, bool)
        ):
            name = node.targets[0].id
            if prefix is None or name.startswith(prefix) or name == SLOT_TOTAL:
                out[name] = (node.value.value, node.lineno)
    return out


def check_protocol_file(
    path: str | Path,
    rel_path: str,
    struct_decls: dict[str, str] = STRUCT_DECLS,
) -> list[Finding]:
    tree = parse_file(path)
    findings: list[Finding] = []

    # name -> (format string, lineno) for X = struct.Struct("...") assigns.
    structs: dict[str, tuple[str, int]] = {}
    consts = _const_int_assigns(tree)
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and isinstance(node.value, ast.Call)
            and isinstance(node.value.func, ast.Attribute)
            and node.value.func.attr == "Struct"
            and node.value.args
            and isinstance(node.value.args[0], ast.Constant)
            and isinstance(node.value.args[0].value, str)
        ):
            structs[node.targets[0].id] = (node.value.args[0].value, node.lineno)

    for sname, cname in sorted(struct_decls.items()):
        if sname not in structs:
            findings.append(
                Finding(
                    NAME, "PC001", rel_path, 1, sname,
                    f"expected wire struct {sname} = struct.Struct(...) not found",
                )
            )
            continue
        fmt, line = structs[sname]
        if cname not in consts:
            findings.append(
                Finding(
                    NAME, "PC001", rel_path, line, sname,
                    f"declared byte constant {cname} for {sname} is missing",
                )
            )
            continue
        declared, _ = consts[cname]
        actual = struct.calcsize(fmt)
        if actual != declared:
            findings.append(
                Finding(
                    NAME, "PC001", rel_path, line, sname,
                    f"struct.calcsize({fmt!r}) == {actual} but {cname} == "
                    f"{declared}: format and declared size drifted",
                )
            )

    # Protocol enum members.
    members, enum_line = _enum_members(tree, ENUM_NAME)
    if not members:
        findings.append(
            Finding(
                NAME, "PC003", rel_path, enum_line, ENUM_NAME,
                f"enum {ENUM_NAME} with integer members not found",
            )
        )
    else:
        values = sorted(v for v, _ in members.values())
        if values != list(range(len(values))):
            findings.append(
                Finding(
                    NAME, "PC003", rel_path, enum_line, ENUM_NAME,
                    f"{ENUM_NAME} values {values} are not unique+contiguous "
                    "from 0 (proto-byte-indexed tables would misroute)",
                )
            )

    # TRACE_KINDS allowlist members must exist on the enum.
    traced = _kinds(tree, ALLOWLIST_NAME)
    if traced is None:
        findings.append(
            Finding(
                NAME, "PC002", rel_path, 1, ALLOWLIST_NAME,
                f"trace allowlist {ALLOWLIST_NAME} not found",
            )
        )
    for attr, line in traced or ():
        if attr not in members:
            findings.append(
                Finding(
                    NAME, "PC002", rel_path, line, ALLOWLIST_NAME,
                    f"{ALLOWLIST_NAME} names {ENUM_NAME}.{attr}, "
                    f"which is not a member of {ENUM_NAME}",
                )
            )

    # The Model layout: who may carry array parts, and its mark in the header.
    # (A protocol file without the layout declares neither name.)
    parted = _kinds(tree, PARTS_NAME)
    for attr, line in parted or ():
        if attr not in members:
            findings.append(
                Finding(
                    NAME, "PC004", rel_path, line, PARTS_NAME,
                    f"{PARTS_NAME} names {ENUM_NAME}.{attr}, "
                    f"which is not a member of {ENUM_NAME}",
                )
            )
        elif attr in {a for a, _ in traced or ()}:
            findings.append(
                Finding(
                    NAME, "PC004", rel_path, line, PARTS_NAME,
                    f"{ENUM_NAME}.{attr} is in {PARTS_NAME} and in "
                    f"{ALLOWLIST_NAME}: its third part would be a trailer "
                    "and a leaf at once",
                )
            )
    codecs, codec_line = _enum_members(tree, CODEC_ENUM)
    values = sorted(v for v, _ in codecs.values())
    if values and (len(set(values)) != len(values) or not 0 <= values[0] <= values[-1] <= 255):
        findings.append(
            Finding(
                NAME, "PC004", rel_path, codec_line, CODEC_ENUM,
                f"{CODEC_ENUM} values {values} are not unique within the "
                "header's one byte (a layout's mark would be misread)",
            )
        )
    return findings


def _enum_members(tree: ast.Module, name: str) -> tuple[dict[str, tuple[int, int]], int]:
    """Integer members of the class ``name`` -> (value, lineno), and the
    class's own line."""
    members: dict[str, tuple[int, int]] = {}
    line = 1
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == name:
            line = node.lineno
            for stmt in node.body:
                if (
                    isinstance(stmt, ast.Assign)
                    and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and isinstance(stmt.value, ast.Constant)
                    and isinstance(stmt.value.value, int)
                ):
                    members[stmt.targets[0].id] = (stmt.value.value, stmt.lineno)
    return members, line


def _kinds(tree: ast.Module, name: str) -> list[tuple[str, int]] | None:
    """``Protocol.X`` attributes named in the module-level assign ``name`` ->
    (X, lineno); None where the module has no such assign."""
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == name
        ):
            return [
                (sub.attr, sub.lineno)
                for sub in ast.walk(node.value)
                if isinstance(sub, ast.Attribute)
                and isinstance(sub.value, ast.Name)
                and sub.value.id == ENUM_NAME
            ]
    return None


def check_mailbox_file(path: str | Path, rel_path: str) -> list[Finding]:
    tree = parse_file(path)
    findings: list[Finding] = []
    consts = _const_int_assigns(tree, prefix=SLOT_PREFIX)
    slots = {k: v for k, v in consts.items() if k.startswith(SLOT_PREFIX)}
    total = consts.get(SLOT_TOTAL)
    if not slots:
        return [
            Finding(NAME, "PC010", rel_path, 1, SLOT_PREFIX + "*", "no slot constants found")
        ]
    values = [v for v, _ in slots.values()]
    if sorted(values) != list(range(len(values))):
        findings.append(
            Finding(
                NAME, "PC010", rel_path, min(l for _, l in slots.values()),
                SLOT_PREFIX + "*",
                f"slot values {sorted(values)} are not unique+contiguous from 0",
            )
        )
    if total is None:
        findings.append(
            Finding(NAME, "PC010", rel_path, 1, SLOT_TOTAL, f"{SLOT_TOTAL} missing")
        )
    elif total[0] != len(slots):
        findings.append(
            Finding(
                NAME, "PC010", rel_path, total[1], SLOT_TOTAL,
                f"{SLOT_TOTAL} == {total[0]} but {len(slots)} slots are declared",
            )
        )
    return findings


def scan_slot_usage(path: str | Path, rel_path: str) -> list[Finding]:
    """PC011: bare integer subscripts on the stat mailbox array."""
    tree = parse_file(path)
    findings: list[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Subscript):
            continue
        base = node.value
        named = (isinstance(base, ast.Name) and base.id in MAILBOX_ARRAY_NAMES) or (
            isinstance(base, ast.Attribute) and base.attr in MAILBOX_ARRAY_NAMES
        )
        if not named:
            continue
        idx = node.slice
        if isinstance(idx, ast.Constant) and isinstance(idx.value, int):
            findings.append(
                Finding(
                    NAME, "PC011", rel_path, node.lineno, "<module>",
                    f"bare index [{idx.value}] into the stat mailbox — use the "
                    "SLOT_* constant from tpu_rl.runtime.mailbox",
                )
            )
    return findings


def _slot_refs(tree: ast.Module, slot_names: set[str]) -> set[str]:
    """Slot constants referenced as load names (imports don't count —
    an unused import is not a reader/writer)."""
    refs: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and node.id in slot_names:
            refs.add(node.id)
    return refs


def run(root: Path = REPO_ROOT) -> list[Finding]:
    findings = check_protocol_file(root / PROTOCOL_FILE, PROTOCOL_FILE)
    mailbox_path = root / MAILBOX_FILE
    findings.extend(check_mailbox_file(mailbox_path, MAILBOX_FILE))

    slots = {
        k
        for k in _const_int_assigns(parse_file(mailbox_path), prefix=SLOT_PREFIX)
        if k.startswith(SLOT_PREFIX)
    }
    ref_modules: dict[str, set[str]] = {s: set() for s in slots}
    for f in sorted((root / SLOT_USER_DIR).rglob("*.py")):
        rel_path = rel(f, root)
        if rel_path == MAILBOX_FILE:
            continue
        tree = parse_file(f)
        findings.extend(scan_slot_usage(f, rel_path))
        for s in _slot_refs(tree, slots):
            ref_modules[s].add(rel_path)
    for s in sorted(slots):
        mods = ref_modules[s]
        if len(mods) < MIN_SLOT_MODULES:
            findings.append(
                Finding(
                    NAME, "PC012", MAILBOX_FILE, 1, s,
                    f"{s} is referenced in {sorted(mods) or 'no modules'} — a "
                    f"mailbox slot needs both its writer and its reader "
                    f"(>= {MIN_SLOT_MODULES} modules) or it is dead/drifted",
                )
            )
    return findings
