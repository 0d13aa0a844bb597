"""Golden-fixture tests for the static-analysis plane (tools/analysis).

Each checker runs against one clean fixture (zero findings) and one seeded
fixture whose violations are asserted by exact (code, line) — a checker that
drifts off its seeded locations is broken, not merely noisy. The baseline
round-trip covers the waiver lifecycle: match, staleness, the 10-entry cap,
and the mandatory reason. The self-check runs the real CLI over the
committed tree and demands a clean exit.
"""

import subprocess
import sys
from pathlib import Path

import pytest

from tools.analysis.checks import drift, hotpath, jit_boundary, protocol_check, threads
from tools.analysis.engine import (
    Finding,
    MAX_WAIVERS,
    REPO_ROOT,
    apply_baseline,
    load_baseline,
)

FX = REPO_ROOT / "tools" / "analysis" / "fixtures"


def codes_lines(findings):
    return sorted((f.code, f.line) for f in findings)


# ------------------------------------------------------------------ hotpath
def test_hotpath_clean_fixture():
    got = hotpath.scan_file(
        FX / "hotpath_clean.py", {"Ring.hot_send": hotpath.STRICT}, "fx"
    )
    assert got == []


def test_hotpath_bad_fixture_strict():
    got = hotpath.scan_file(
        FX / "hotpath_bad.py", {"Ring.hot_send": hotpath.STRICT}, "fx"
    )
    assert codes_lines(got) == [
        ("HP001", 7),   # f-string
        ("HP002", 8),   # .format
        ("HP003", 9),   # %-format
        ("HP004", 10),  # comprehension
        ("HP005", 11),  # non-empty dict literal
        ("HP006", 12),  # print
        ("HP007", 13),  # json.dumps
    ]
    assert all(f.symbol == "Ring.hot_send" for f in got)


def test_hotpath_fmt_tier_allows_containers():
    got = hotpath.scan_file(
        FX / "hotpath_bad.py", {"Ring.hot_send": hotpath.FMT}, "fx"
    )
    # The fmt tier still bans formatting/logging but tolerates the
    # comprehension (HP004) and dict literal (HP005).
    assert codes_lines(got) == [
        ("HP001", 7), ("HP002", 8), ("HP003", 9), ("HP006", 12), ("HP007", 13)
    ]


def test_hotpath_missing_manifest_entry_is_flagged():
    got = hotpath.scan_file(
        FX / "hotpath_clean.py", {"Ring.gone": hotpath.STRICT}, "fx"
    )
    assert [f.code for f in got] == ["HP000"]


# ---------------------------------------------------------------------- jit
def test_jit_clean_fixture():
    assert jit_boundary.scan_file(FX / "jit_clean.py", "fx") == []


def test_jit_bad_fixture():
    got = jit_boundary.scan_file(FX / "jit_bad.py", "fx")
    assert codes_lines(got) == [
        ("JB001", 9),   # print
        ("JB002", 10),  # time.time()
        ("JB003", 11),  # .item()
        ("JB004", 12),  # np.asarray
        ("JB005", 13),  # float()
    ]
    assert all(f.symbol == "_body" for f in got)


# ----------------------------------------------------------------- protocol
def test_protocol_clean_fixture():
    got = protocol_check.check_protocol_file(
        FX / "proto_clean.py", "fx", {"_HEADER": "HEADER_BYTES"}
    )
    assert got == []


def test_protocol_bad_fixture():
    got = protocol_check.check_protocol_file(
        FX / "proto_bad.py", "fx", {"_HEADER": "HEADER_BYTES"}
    )
    assert codes_lines(got) == [
        ("PC001", 4),   # calcsize 12 != declared 10
        ("PC002", 14),  # TRACE_KINDS names Protocol.Ghost
        ("PC003", 8),   # enum values [0, 1, 3] have a gap
        ("PC004", 17),  # Codec values [0, 1, 1]: the layout's mark is LZ4's
        ("PC004", 23),  # PARTS_KINDS names a TRACE_KINDS member and a ghost
        ("PC004", 23),
    ]


def test_mailbox_fixtures():
    assert protocol_check.check_mailbox_file(FX / "mailbox_clean.py", "fx") == []
    got = protocol_check.check_mailbox_file(FX / "mailbox_bad.py", "fx")
    assert codes_lines(got) == [("PC010", 2), ("PC010", 4)]


def test_bare_slot_index_fixture():
    got = protocol_check.scan_slot_usage(FX / "slots_bad.py", "fx")
    assert codes_lines(got) == [("PC011", 5), ("PC011", 6)]


def test_real_protocol_and_mailbox_are_clean():
    # The acceptance bite: change _TRAILER's format or delete HEADER_BYTES in
    # the real tree and this (and `make analyze`) must fail.
    assert (
        protocol_check.check_protocol_file(
            REPO_ROOT / "tpu_rl/runtime/protocol.py", "tpu_rl/runtime/protocol.py"
        )
        == []
    )
    assert (
        protocol_check.check_mailbox_file(
            REPO_ROOT / "tpu_rl/runtime/mailbox.py", "tpu_rl/runtime/mailbox.py"
        )
        == []
    )


# -------------------------------------------------------------------- drift
def test_drift_clean_fixture():
    code = drift.extract_code_metrics([FX / "drift_code_clean.py"], FX)
    doc = drift.extract_doc_metrics(FX / "drift_doc_clean.md")
    assert {n for n, _, _, _ in code} == {"relay-frames", "queue-depth"}
    assert drift.compare_metrics(code, doc, "fx.md") == []


def test_drift_bad_fixture():
    code = drift.extract_code_metrics([FX / "drift_code_bad.py"], FX)
    doc = drift.extract_doc_metrics(FX / "drift_doc_bad.md")
    got = drift.compare_metrics(code, doc, "fx.md")
    assert codes_lines(got) == [
        ("DR001", 7),  # orphan-metric in code, not in doc
        ("DR002", 6),  # ghost-metric documented, not in code
        ("DR003", 6),  # relay-frames registered as both counter and gauge
    ]


def test_config_fixture():
    got = drift.check_config(FX / "config_bad.py", "fx", exempt={})
    assert codes_lines(got) == [("DR010", 6)]
    assert got[0].symbol == "Config.batch"
    # A stale exemption (field no longer exists) is itself a finding.
    got = drift.check_config(FX / "config_bad.py", "fx", exempt={"zzz": "gone"})
    assert ("DR010", 1) in codes_lines(got)


def test_cli_fixture():
    got = drift.check_cli(FX / "cli_bad.py", "fx", {"lr"})
    by_code = {f.code: f for f in got}
    assert set(by_code) == {"DR011", "DR012", "DR013"}
    assert by_code["DR011"].symbol == "args.batch"
    assert by_code["DR012"].symbol == "--dead-flag"
    assert by_code["DR013"].symbol == "ghost_field"


# ------------------------------------------------------------------ threads
def test_threads_clean_fixture():
    got = threads.scan_file(FX / "threads_clean.py", {"W._run": frozenset()}, "fx")
    assert got == []


def test_threads_bad_fixture():
    got = threads.scan_file(FX / "threads_bad.py", {"W._run": frozenset()}, "fx")
    assert codes_lines(got) == [("TH001", 6), ("TH001", 9)]
    # The allowlist clears exactly those findings.
    got = threads.scan_file(
        FX / "threads_bad.py", {"W._run": frozenset({"count"})}, "fx"
    )
    assert got == []


def test_threads_missing_entry_is_flagged():
    got = threads.scan_file(FX / "threads_clean.py", {"W.gone": frozenset()}, "fx")
    assert [f.code for f in got] == ["TH000"]


# ----------------------------------------------------------------- baseline
def _waiver_toml(n, reason='reason = "justified"'):
    entry = (
        '[[waiver]]\ncheck = "hotpath"\ncode = "HP001"\n'
        f'path = "tpu_rl/x.py"\n{reason}\n'
    )
    return entry * n


def test_baseline_round_trip(tmp_path):
    p = tmp_path / "baseline.toml"
    p.write_text(_waiver_toml(1))
    waivers = load_baseline(p)
    assert len(waivers) == 1 and waivers[0].symbol == "*"
    hit = Finding("hotpath", "HP001", "tpu_rl/x.py", 10, "A.f", "m")
    miss = Finding("hotpath", "HP002", "tpu_rl/x.py", 11, "A.f", "m")
    kept, waived, stale = apply_baseline([hit, miss], waivers)
    assert kept == [miss] and waived == [hit] and stale == []
    # A waiver that matches nothing is reported stale.
    kept, waived, stale = apply_baseline([miss], waivers)
    assert kept == [miss] and waived == [] and stale == waivers


def test_baseline_requires_reason(tmp_path):
    p = tmp_path / "baseline.toml"
    p.write_text(_waiver_toml(1, reason='reason = ""'))
    with pytest.raises(ValueError, match="no reason"):
        load_baseline(p)


def test_baseline_caps_waivers(tmp_path):
    p = tmp_path / "baseline.toml"
    p.write_text(_waiver_toml(MAX_WAIVERS + 1))
    with pytest.raises(ValueError, match="cap"):
        load_baseline(p)


def test_committed_baseline_loads_within_cap():
    assert len(load_baseline()) <= MAX_WAIVERS


# --------------------------------------------------------------- self-check
def test_repo_is_clean_under_the_full_suite():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analysis"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
