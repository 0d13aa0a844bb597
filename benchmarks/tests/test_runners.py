"""Each runner rehearsed end to end on the CPU at tiny sizes, through
``run.main`` and the real data files. The harness has no CPU mode, flag or
environment variable: the tests replace ``harness.check_device`` themselves.
What comes out is control flow and counts, never a device number."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness, run

TINY_TF = ["params.hidden_size=32", "params.n_heads=2", "params.n_layers=1",
           "params.seq_len=16", "params.batch_size=8", "params.obs_shape=[6]",
           "params.action_space=3", 'params.compute_dtype="float32"',
           "windows.pool=16", "windows.episode_len_mean=6"]
CASES = {
    "tf-longctx.learner": TINY_TF,
    "tf-longctx.dp4": [*TINY_TF, "params.mesh_data=4"],
    "lstm-ref.colocated": ["params.colocated_envs=64"],
    "lstm-ref.dist": ["fleet.workers=2", "params.worker_num_envs=8",
                      "trace.start_update=60", "trace.updates=20"],
}
BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
WAITING = harness.load_json(os.path.join(harness.HERE, "candidates.json"))
CELLS = [w["name"] for w in BENCH["workloads"] + WAITING["workloads"]]


def result_line(capsys, cell: str, trace: int, seconds: float) -> dict:
    argv = ["--workload", cell, "--seed", "3", "--seconds", str(seconds),
            "--trace", str(trace)]
    for item in CASES[cell]:
        argv += ["--set", item]
    assert run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def any_device(monkeypatch):
    monkeypatch.setattr(harness, "check_device", lambda *a: None)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal(cell, any_device, capsys):
    assert cell in CASES, "a new cell needs a tiny rehearsal here"
    line = result_line(capsys, cell, trace=0, seconds=4)
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])} - {"peak_hbm_gib"}
    assert want <= set(line["metrics"])  # the CPU backend reports no HBM peak
    assert all(v["value"] > 0 for v in line["metrics"].values())
    checks = line["checks"]
    assert not checks["device"] and not line["correct"]  # a CPU is never correct
    assert checks["parity"] and checks["losses_finite"] and checks["no_compile_in_window"]
    assert line["window"]["clock_skew"] < 0.02


def test_a_traced_rehearsal_reports_only_what_it_can_read(any_device, capsys):
    """Program counters and spans are read; with no TPU plane in the capture
    every trace reader finds nothing and its metric is left out."""
    line = result_line(capsys, "lstm-ref.dist", trace=1, seconds=10)
    assert {"worker.env_steps_per_s", "relay.windows_per_s", "feed.wait_share",
            "feed.h2d_bytes_per_update", "feed.policy_lag_updates"} <= set(line["metrics"])
    assert line["metrics"]["feed.h2d_bytes_per_update"]["value"] == 128 * 5 * 138 * 4
    assert "device.idle_share" not in line["metrics"] and "breakdown" not in line
    assert line["checks"]["worker_acted_on_broadcast_policy"]


@pytest.mark.parametrize("cell", ["tf-longctx.learner", "lstm-ref.colocated"])
def test_without_a_tpu_there_is_no_result(cell):
    """One cell of each runner: nonzero exit, nothing on standard output."""
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3 and proc.stdout == ""
    assert "TPU" in proc.stderr
