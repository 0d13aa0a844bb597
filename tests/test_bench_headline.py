"""bench.py's summary line: it names the device it ran on, carries no
numbers from another run, and a failed row or cross-check makes the process
exit nonzero after printing what it has. (The committed ``bench_*.cpu.json``
schema checks below go with bench.py when ROADMAP S0 replaces it.)"""

import json
import os

import bench


def _stub_planes(monkeypatch):
    # The live-plane agreement sections spin real jitted learners (~30s on
    # a CI core each run_all call) and are not these tests' subject.
    monkeypatch.setattr(bench, "perf_crosscheck", lambda: {"stub": True})
    monkeypatch.setattr(bench, "goodput_crosscheck", lambda: {"stub": True})


def test_run_all_failed_row_fails_the_run(tmp_path, monkeypatch):
    """A row that raises is recorded, the rest of the matrix still runs and
    prints — and the row is named in ``failed``, which ``__main__`` turns
    into a nonzero exit (it used to be swallowed into {"error": ...})."""

    def bench_one(name, *a, **kw):
        if name == "PPO@ref":
            raise RuntimeError("Mosaic refused the kernel")
        return {"name": name, "tps": 1234.0, "step_ms": 1.0, "mfu": None,
                "steps_per_call": 1}

    monkeypatch.setattr(bench, "bench_one", bench_one)
    _stub_planes(monkeypatch)
    out = bench.run_all(out_path=str(tmp_path / "m.json"))
    assert out["failed"] == ["PPO@ref"]
    assert out["value"] == 1234.0  # the headline row itself still measured
    with open(tmp_path / "m.json") as f:
        rec = json.load(f)
    assert rec["failed"] == ["PPO@ref"]
    row = next(r for r in rec["rows"] if r["name"] == "PPO@ref")
    assert "Mosaic refused" in row["error"]
    assert len(rec["rows"]) > 1  # the matrix was not aborted


def test_run_all_failed_crosscheck_fails_the_run(tmp_path, monkeypatch):
    monkeypatch.setattr(
        bench, "bench_one",
        lambda name, *a, **kw: {"name": name, "tps": 1.0, "step_ms": 1.0},
    )
    monkeypatch.setattr(bench, "perf_crosscheck", lambda: 1 / 0)
    monkeypatch.setattr(bench, "goodput_crosscheck", lambda: {"stub": True})
    out = bench.run_all(out_path=str(tmp_path / "m.json"))
    assert out["failed"] == ["perf_plane"]


def test_run_all_headline_names_its_device_and_nothing_stale(
    tmp_path, monkeypatch
):
    """The summary line carries the device the numbers came from; on the CPU
    it says so, and it never embeds an older on-chip matrix."""
    monkeypatch.setattr(
        bench, "bench_one",
        lambda name, *a, **kw: {"name": name, "tps": 1234.0, "step_ms": 1.0,
                                "mfu": None, "steps_per_call": 1},
    )
    _stub_planes(monkeypatch)
    out = bench.run_all(out_path=str(tmp_path / "m.json"))
    assert out["failed"] == []
    assert out["device_kind"].lower().startswith("cpu")
    assert "CPU backend" in out["note"]
    assert "stale_onchip" not in out and "last_onchip" not in out


def test_committed_multihost_scaling_record():
    """The committed pod-Anakin weak-scaling record (ISSUE 18,
    ``run_colocated_multihost``) must parse with the full honesty schema —
    per-row device/process counts, per-device tps, host_cores and the
    oversubscribed flag — and the >=1.8x direction bar must hold wherever
    the capture box actually had parallel hardware (a 1-core CI host
    timeshares its virtual hosts, so its ratio documents overhead, not
    scaling)."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(bench.__file__)),
        "bench_colocated_multihost.cpu.json",
    )
    with open(path) as f:
        rec = json.load(f)
    for key in (
        "metric", "device_kind", "scaling_2x_vs_1x", "tps_1host",
        "tps_2host", "tps_per_device_1host", "tps_per_device_2host",
        "envs_per_device", "host_cores", "oversubscribed", "recorded_at",
        "rows",
    ):
        assert key in rec, f"missing key: {key}"
    rows = rec["rows"]
    assert [r["num_processes"] for r in rows] == [1, 2]
    assert rows[1]["devices"] == 2 * rows[0]["devices"]
    for r in rows:
        assert r["tps_per_device"] > 0
        assert r["colocated_tps"] > 0
        assert r["n_envs"] == rec["envs_per_device"] * r["devices"]
    assert rec["scaling_2x_vs_1x"] > 0
    assert rec["host_cores"] >= 1
    if not rec["oversubscribed"]:
        assert rec["scaling_2x_vs_1x"] >= 1.8, rec


def test_committed_diag_overhead_record():
    """The committed learning-dynamics diag A/B record (ISSUE 19,
    ``run_diag_compare``) must parse with the full schema — per-algo
    on/off step times and overhead, the 2% contract value, and the
    contract_binding flag — and wherever the capture was taken on an
    accelerator (binding regime), the <=2% bar must actually hold. CPU
    captures record the numbers but a 1-core CI box's timer noise exceeds
    the bar, so there the check is sanity-level only (no host sync snuck
    into the step: overheads stay far from 2x)."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(bench.__file__)),
        "bench_diag.cpu.json",
    )
    with open(path) as f:
        rec = json.load(f)
    for key in (
        "metric", "device_kind", "chain", "repeats", "max_overhead_pct",
        "contract_pct", "contract_binding", "recorded_at", "rows",
    ):
        assert key in rec, f"missing key: {key}"
    assert rec["contract_pct"] == 2.0
    algos = [r["algo"] for r in rec["rows"]]
    # clip/KL, V-trace clip-rate+ESS, and twin-critic/alpha channel shapes
    assert {"IMPALA", "PPO", "SAC"} <= set(algos)
    for r in rec["rows"]:
        assert r["step_ms_diag_on"] > 0 and r["step_ms_diag_off"] > 0
        assert r["tps_diag_on"] > 0 and r["tps_diag_off"] > 0
        assert r["overhead_pct"] is not None
        # sanity bound on every capture regime: a regression that forces a
        # host readback per update shows up as >2x, not single percents
        assert r["overhead_pct"] < 50.0, r
    if rec["contract_binding"]:
        assert rec["max_overhead_pct"] <= rec["contract_pct"], rec


def test_committed_history_overhead_record():
    """The committed run-history A/B record (ISSUE 20,
    ``run_history_compare``) must parse with the full schema and hold
    both contracts on every capture regime: the store's record call
    consumes <=2% of the exporter cadence budget (a host-side wall
    budget — binding even on CPU captures, unlike the chip benches), and
    the plane-off hot path allocates zero bytes (the one-``is None``
    -check cost model the telemetry plane itself ships with)."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(bench.__file__)),
        "bench_history.cpu.json",
    )
    with open(path) as f:
        rec = json.load(f)
    for key in (
        "metric", "device_kind", "workers", "ticks", "repeats",
        "interval_s", "record_ms", "overhead_pct_of_cadence",
        "contract_pct", "contract_binding", "off_path_alloc_bytes",
        "recorded_at", "rows",
    ):
        assert key in rec, f"missing key: {key}"
    assert rec["contract_pct"] == 2.0
    assert rec["interval_s"] > 0
    assert rec["workers"] >= 1 and rec["ticks"] >= 1
    assert len(rec["rows"]) == rec["repeats"]
    for r in rec["rows"]:
        assert r["tick_ms_on"] > 0 and r["tick_ms_off"] > 0
        assert r["record_ms"] >= 0
    assert rec["off_path_alloc_bytes"] == 0, rec
    assert rec["contract_binding"] is True
    assert rec["overhead_pct_of_cadence"] <= rec["contract_pct"], rec
