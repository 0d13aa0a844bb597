"""One cell, one process: load, warm up, measure, print one JSON line, exit.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name: the cell and its metrics
in ``BENCHMARK.json``, the configuration in the file its entry names, the
traffic mix in ``benchmarks/traffic/<traffic>.json``, the way it is driven in
``benchmarks/runners/<runner>.py`` and each metric in
``benchmarks/metrics/<metric>.py``. Nothing here names any of them.

There is no CPU mode: without a TPU, or with fewer chips than the cell asks
for, the process exits nonzero and prints no result line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
# Children (the CLI's roles, the parity child) import the checkout's package.
os.environ["PYTHONPATH"] = os.pathsep.join(
    p for p in (ROOT, os.environ.get("PYTHONPATH")) if p
)
# The program keeps its compile cache at <checkout>/.jax_cache unless
# JAX_COMPILATION_CACHE_DIR is set, and then leaves JAX's thresholds alone:
# make every program cacheable either way (the @ref programs compile in well
# under a second each, 79 of them).
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

from benchmarks import harness  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument(
        "--set", action="append", default=[], metavar="PATH=JSON",
        help="sweeps only: override one key of the traffic file, e.g. "
        "fleet.workers=6 or params.batch_size=16",
    )
    ap.add_argument("--artifacts", help="keep logs, records and the trace here")
    args = ap.parse_args(argv)

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    # Cells that are written and measured but not registered (PERF.md says
    # why) run by name too; the driver knows only BENCHMARK.json's.
    waiting = harness.load_json(os.path.join(HERE, "candidates.json"))
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {x["name"] for x in bench[key]}
        bench[key] = bench[key] + [x for x in waiting.get(key, []) if x["name"] not in have]
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        ap.error(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
    cell = cells[args.workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    traffic = harness.load_json(
        os.path.join(HERE, "traffic", f"{cell['traffic']}.json")
    )
    for item in args.set:
        path, _, value = item.partition("=")
        harness.set_path(traffic, path, json.loads(value))
    spec = harness.Spec(
        cell=cell,
        config=harness.load_json(os.path.join(ROOT, entry["file"])),
        traffic=traffic,
        seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace),
        t_start=T_START,
        artifacts=args.artifacts,
    )
    runner = harness.load_module(
        os.path.join(HERE, "runners", f"{traffic['runner']}.py")
    )
    try:
        run = runner.run(spec)
    except harness.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    except harness.RunFailed as e:
        print(f"benchmark: run failed: {e}", file=sys.stderr)
        return 4

    metrics = {}
    for m in bench["per_layer" if spec.trace else "end_to_end"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        reader = harness.load_module(
            os.path.join(HERE, "metrics", f"{m['name']}.py")
        )
        try:
            value = reader.read(run)
        except Exception:  # noqa: BLE001 — one reader must not lose the run
            traceback.print_exc()
            value = None
        if value is None:
            continue  # nothing to read: the metric is left out of the line
        extra = {}
        if isinstance(value, tuple):  # (value, {"bound": "memory"}, ...)
            value, extra = value
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"], **extra}

    ok, checks = run.correct()
    line = {
        "correct": ok,
        "attempted": run.window.updates,
        "failed": run.failed_updates,
        "metrics": metrics,
        "device": run.device,
    }
    if spec.trace and run.trace is not None:
        line["device"] = {
            **run.device,
            "busy_s": run.trace.busy_s,
            "window_s": run.trace.window_s,
        }
        line["breakdown"] = run.trace.breakdown()
    # Ignored by the driver; kept for whoever reads a run by hand.
    line["checks"] = checks
    line["parity"] = run.parity
    line["window"] = {
        "seconds": run.window.seconds,
        "updates": run.window.updates,
        "clock_skew": run.window.clock_skew,
        # every learn.jsonl line of the window: seconds since its start, index
        "lines": [
            [round(r.mono - run.window.start.mono, 6), r.idx]
            for r in (run.window.start, *run.window.rows)
        ],
        **run.notes.get("window", {}),
    }
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
