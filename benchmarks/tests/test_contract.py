"""``BENCHMARK.json`` against the builder's contract, and the harness against
its own rule: everything that belongs to one cell is found by name."""

import os
import re

import pytest

from benchmarks import harness

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")


def test_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"] and BENCH["command"][-1] == "benchmarks/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert 2 <= len(BENCH["workloads"]) <= 24 and 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16 and 1 <= len(BENCH["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert all(len(x["why"]) <= 200 for k in ("configs", "workloads") for x in BENCH[k])


def test_cells():
    configs = {c["name"]: c for c in BENCH["configs"]}
    cells = BENCH["workloads"]
    assert {w["config"] for w in cells} == set(configs)  # each used by some cell
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    for w in cells:
        traffic = harness.load_json(
            os.path.join(harness.HERE, "traffic", f"{w['traffic']}.json"))
        assert os.path.isfile(
            os.path.join(harness.HERE, "runners", f"{traffic['runner']}.py"))
    for c in configs.values():
        doc = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert doc["reduced"] == c["reduced"]
        assert not any(re.search(r"hidden|_dim$|_rank$|head", k) for k in c["reduced"])
        assert os.path.isfile(os.path.join(
            harness.HERE, "reference", f"{doc['parity']['reference']}.py"))


def test_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert all(0.01 <= m["bound"] <= 0.1 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace") for m in e2e.values())
    cells = {w["name"] for w in BENCH["workloads"]}
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            assert set(m.get("workloads", cells)) <= cells
            assert m["better"] in ("higher", "lower")
            assert os.path.isfile(
                os.path.join(harness.HERE, "metrics", f"{m['name']}.py")), m["name"]
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", m["layer"]), m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:  # setup_s, another end-to-end metric, a per-layer metric
        covers = lambda m: cell in m.get("workloads", cells)  # noqa: E731
        assert sum(covers(m) for m in BENCH["end_to_end"]) >= 2
        assert any(covers(m) for m in BENCH["per_layer"])


def test_cells_that_wait_are_complete():
    """What ``candidates.json`` holds must be registrable as it stands."""
    waiting = harness.load_json(os.path.join(harness.HERE, "candidates.json"))
    configs = {c["name"] for c in BENCH["configs"] + waiting["configs"]}
    cells = {w["name"] for w in waiting["workloads"]}
    assert not cells & {w["name"] for w in BENCH["workloads"]}
    for c in waiting["configs"]:
        assert harness.load_json(os.path.join(harness.ROOT, c["file"]))["reduced"] == c["reduced"]
    for w in waiting["workloads"]:
        assert w["config"] in configs and len(w["why"]) <= 200 and w["chips"] in (1, 4)
        traffic = harness.load_json(
            os.path.join(harness.HERE, "traffic", f"{w['traffic']}.json"))
        assert os.path.isfile(
            os.path.join(harness.HERE, "runners", f"{traffic['runner']}.py"))
    for m in waiting["per_layer"]:
        assert set(m["workloads"]) <= cells and NAME.match(m["name"])
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", m["layer"]), m["layer"]
        assert os.path.isfile(os.path.join(harness.HERE, "metrics", f"{m['name']}.py"))


@pytest.mark.parametrize("source", ["run.py", "harness.py", "measure.py"])
def test_the_harness_names_no_cell_config_or_metric(source):
    with open(os.path.join(harness.HERE, source)) as f:
        text = f.read()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]] + [w["traffic"] for w in BENCH["workloads"]]
    names.remove("setup_s")  # the one name the builder's contract fixes
    assert [n for n in names if re.search(rf"[\"']{re.escape(n)}[\"']", text)] == []
