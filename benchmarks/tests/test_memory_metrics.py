"""The seven ``mem.*`` readers (``benchmarks/memory.py``,
``benchmarks/metrics/mem.*.py``) on a hand-made record, and on two records a
TPU v5e left (``data/backend_learner_mem_*.json``: the learner's
``backend-learner.json`` of one run of a one-chip catalog cell and of one of
``tf-longctx.dp4``, each with the ``memory_peak_bytes`` its result line
printed)."""

import copy
import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import harness, memory

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = [
    "mem.before_program_gib", "mem.state_gib", "mem.program_scratch_gib", "mem.feed_gib",
    "mem.snapshot_gib", "mem.window_peak_gib", "mem.named_share",
]
G = memory.GIB
T0 = 1_790_000_000.0


def read(name, run):
    got = harness.load_module(os.path.join(harness.HERE, "metrics", f"{name}.py")).read(run)
    return got if got is None or isinstance(got, tuple) else (got, {})


def as_run(doc, peak_hbm_bytes):
    """What a reader takes of a ``harness.Run``."""
    return SimpleNamespace(paths=doc, device={"memory_peak_bytes": peak_hbm_bytes})


@pytest.fixture
def record():
    """A learner that inherited 12 GiB of live peak and 2 of scratch, placed
    a 6 GiB state, reserved 4 GiB for its update, and saved once."""
    stamps = [
        ["run", T0, None, G // 2, 12 * G, 2 * G],
        ["place", T0 + 5, None, 6 * G + G // 2, 12 * G, 2 * G],
        ["publish", T0 + 9, 0, 7 * G, 12 * G, 2 * G],
        ["log-sync", T0 + 20, 2, 7 * G + G // 4, 12 * G, 4 * G],
        ["ckpt-save", T0 + 30, 8, 13 * G + G // 4, 13 * G + G // 4, 4 * G],
        ["close", T0 + 40, 12, 7 * G, 13 * G + G // 4, 4 * G],
    ]

    def owner(each, alive, bound=None):
        return {"bytes_each": each, "alive_max": max(alive), "bound": bound, "alive": alive}

    return {"memory": {
        "devices": 1, "bytes_limit": 16 * G, "stamps": stamps,
        "stamps_taken": 40, "stamps_dropped": 0,
        "owners": {
            "train-state": owner(6 * G, [0, 1, 1, 1, 1, 1]),
            "batch": owner(G // 8, [0, 0, 0, 2, 2, 0], bound=5),
            "publish-snapshot": owner(G // 2, [0, 0, 1, 1, 1, 0]),
            "inference-params": owner(None, [0] * 6),
            "ckpt-snapshot": owner(6 * G, [0, 0, 0, 0, 1, 0]),
            "diag": owner(1024, [0, 0, 0, 1, 0, 1]),
        },
        "window": {
            "first_sync_unix_s": T0 + 20,
            "stamp": stamps[4],
            "alive": {"train-state": 1, "batch": 2, "publish-snapshot": 1,
                      "inference-params": 0, "ckpt-snapshot": 1, "diag": 0},
            "in_use_peak_rose": True, "reserved_peak_rose": False,
            "live_peak_bytes": 13 * G + G // 4, "scratch_bytes": 4 * G,
            "raised_by_learner": True,
        },
    }}


def test_the_seven_readers_on_a_hand_made_record(record):
    run = as_run(record, 17 * G + G // 4)  # the benchmark adds 13.25 + 4
    v, x = read("mem.before_program_gib", run)
    assert v == 14.0 and x["share_of_peak_hbm"] == pytest.approx(100 * 14 / 17.25)
    assert (x["peak_in_use_gib"], x["peak_reserved_gib"], x["live_at_entry_gib"]) == (12.0, 2.0, 0.5)
    assert read("mem.state_gib", run) == (6.0, {"alive_max": 1})
    assert read("mem.program_scratch_gib", run) == (
        4.0, {"raised_by_learner": True, "reserved_peak_rose": False}
    )
    assert read("mem.feed_gib", run) == (
        0.25, {"batch_gib": 0.125, "alive_at_peak": 2, "alive_max": 2, "bound": 5}
    )
    v, x = read("mem.snapshot_gib", run)
    assert v == 6.5 and x["ckpt_snapshot_gib"] == 6.0 and x["publish_snapshot_gib"] == 0.5
    assert x["inference_params_gib"] == 0.0 and "a_save_would_hold_gib" not in x  # it saved
    v, x = read("mem.window_peak_gib", run)
    assert v == 17.25  # 13.25 live + 4 of scratch: over this chip's 16
    assert x["exact"] is True and x["peak_site"] == "ckpt-save" and x["peak_update"] == 8
    assert x["headroom_gib"] == -1.25 and x["fits_a_save"] is False and x["bytes_limit_gib"] == 16.0
    v, x = read("mem.named_share", run)
    named = 6 + 0.25 + 0.5 + 6  # state, two batches, a snapshot, the save's copy
    assert v == pytest.approx(100 * named / 13.25)
    assert x["unnamed_gib"] == pytest.approx(13.25 - named) and x["live_gib"] == 13.25
    assert x["owners_gib"] == {
        "train-state": 6.0, "batch": 0.25, "publish-snapshot": 0.5, "ckpt-snapshot": 6.0
    }


def test_a_run_without_a_save_says_what_one_would_hold(record):
    mem = record["memory"]
    # no save was due in the loop; the one at shutdown has a ckpt-d2h alone
    mem["stamps"][4][:3] = ["ckpt-d2h", T0 + 41, 12]
    mem["stamps"].append(mem["stamps"].pop(4))
    for owner in mem["owners"].values():
        owner["alive"].append(owner["alive"].pop(4))
    mem["owners"]["ckpt-snapshot"].update(alive_max=1, alive=[0] * 6)
    mem["window"].update(
        stamp=mem["stamps"][3], in_use_peak_rose=False, live_peak_bytes=7 * G + G // 4,
        alive={**mem["window"]["alive"], "ckpt-snapshot": 0, "diag": 1},
    )
    run = as_run(record, 16 * G)
    v, x = read("mem.snapshot_gib", run)
    assert v == 0.5 and x["a_save_would_hold_gib"] == 6.0
    v, x = read("mem.window_peak_gib", run)
    assert v == 11.25 and x["exact"] is False and x["peak_site"] == "log-sync"
    assert x["headroom_gib"] == 4.75 and x["fits_a_save"] is False  # 4.75 < 6
    mem["bytes_limit"] = 18 * G
    assert read("mem.window_peak_gib", run)[1]["fits_a_save"] is True
    mem["bytes_limit"] = None  # a runtime that states no limit: the peak alone
    v, x = read("mem.window_peak_gib", run)
    assert v == 11.25 and "fits_a_save" not in x and "headroom_gib" not in x


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_is_none(record, name):
    assert read(name, as_run({"paths": ["attn_flash_pallas"]}, 4 * G)) is None  # before PR 49
    assert read(name, as_run(None, 4 * G)) is None
    early = copy.deepcopy(record)
    early["memory"]["window"] = None  # ended before its first log-sync
    assert read(name, as_run(early, 4 * G)) is None
    cpu = copy.deepcopy(record)  # a backend without books: null columns
    for s in cpu["memory"]["stamps"]:
        s[3:] = [None, None, None]
    cpu["memory"]["window"].update(scratch_bytes=None, live_peak_bytes=None, raised_by_learner=None)
    assert read(name, as_run(cpu, 0)) is None


def test_an_undeclared_owner_reads_none_not_zero(record):
    record["memory"]["owners"]["batch"]["bytes_each"] = None
    run = as_run(record, 17 * G)
    assert read("mem.feed_gib", run) is None
    assert read("mem.named_share", run)[1]["owners_gib"].get("batch") is None


# ------------------------------------------------- records a TPU v5e left
def chip_run(name):
    with open(os.path.join(HERE, "data", name)) as f:
        doc = json.load(f)
    return doc, as_run(doc, doc["_result_line"]["memory_peak_bytes"])


def test_the_readers_on_a_four_chip_record():
    """``tf-longctx.dp4``, seed 2147483781 (my chip run, PR 49): the numbers
    below are the record's bytes by hand."""
    doc, run = chip_run("backend_learner_mem_dp4.json")
    assert doc["memory"]["devices"] == 4 and doc["mesh"] == {"data": 4}
    v, x = read("mem.before_program_gib", run)
    assert v == (260845056 + 3621396480) / G  # the set-up's two peaks at ``run``
    assert x["share_of_peak_hbm"] == pytest.approx(97.556, abs=1e-3)
    assert read("mem.state_gib", run)[0] == 101187660 / G
    v, x = read("mem.program_scratch_gib", run)
    assert v == 3621396480 / G and x["raised_by_learner"] is False  # the parity check ran it first
    v, x = read("mem.feed_gib", run)
    assert v == 5 * 20447232 / G and (x["alive_max"], x["bound"]) == (5, 5)
    v, x = read("mem.snapshot_gib", run)
    assert v == 2 * 50593828 / G and x["publish_snapshot_alive_max"] == 2
    assert x["ckpt_snapshot_gib"] == 0.0 and "a_save_would_hold_gib" not in x  # saved at update 100
    v, x = read("mem.window_peak_gib", run)
    assert v == (358090752 + 3621396480) / G and x["exact"] is True
    assert (x["peak_site"], x["peak_update"]) == ("publish", 19) and x["fits_a_save"] is True
    # in this cell the loop sets the reading: the benchmark's sum is the window's
    assert v == doc["_result_line"]["peak_hbm_gib"]
    v, x = read("mem.named_share", run)
    named = 101187660 + 5 * 20447232 + 2 * 50593828 + 408
    assert v == pytest.approx(100 * named / 355471360) and 85 < v < 86
    assert x["unnamed_gib"] == pytest.approx((355471360 - named) / G)


@pytest.mark.parametrize("name", ["backend_learner_mem_dp4.json", "backend_learner_mem_catalog.json"])
def test_the_window_fits_the_chip_and_the_benchmarks_sum(name):
    doc, run = chip_run(name)
    mem = doc["memory"]
    window, x = read("mem.window_peak_gib", run)
    assert window <= x["bytes_limit_gib"] == mem["bytes_limit"] / G
    assert window <= doc["_result_line"]["peak_hbm_gib"]  # two lifetime peaks added
    assert all(read(n, run) is not None for n in NAMES)
    sites = [s[0] for s in mem["stamps"]]
    assert sites[:5] == ["run", "train-state", "restore", "place", "inference-start"]
    assert "close" in sites and mem["stamps_dropped"] == 0
    batch = mem["owners"]["batch"]
    assert batch["alive_max"] <= batch["bound"] == 5
    assert mem["owners"]["publish-snapshot"]["alive_max"] <= 2
    # the update program's row holds the compiler's sizes, its count untouched
    rows = [r for r in doc["compiles"]["programs"].values() if "memory" in r]
    assert len(rows) == 1 and rows[0]["memory"]["alias_bytes"] >= mem["owners"]["train-state"]["bytes_each"]
