"""Run one cell several times, as the driver does, and print the spreads.

    python3 benchmarks/measure.py --workload <cell> [--sets 2] [--runs 6]
        [--traced 1] [--artifacts DIR] [--out chiprun_out/<cell>.jsonl]
        [--set PATH=JSON ...]

Every run is a new ``benchmarks/run.py`` process with another ``--seed``; the
runs of one call share the compile cache, so only the first compiles (its
``setup_s`` is shown apart). For each end-to-end metric and each set: median
and spread (distance between the quartiles over the median); the bound rule of
the builder's contract reads the wider of the sets' spreads. Every result line
is appended to ``--out``. This process never imports JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def one(workload: str, seed: int, seconds: float, trace: int, extra: list[str]) -> dict | None:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace), *extra]
    proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE, text=True)
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        print(f"run seed={seed} trace={trace}: exit {proc.returncode}, no result", flush=True)
        return None
    return json.loads(lines[-1])


def spread(values: list[float]) -> float:
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=0, help="traced runs after the sets")
    ap.add_argument("--artifacts", help="keep the traced runs' logs and captures here")
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--out")
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    extra = [x for item in args.set for x in ("--set", item)]
    out = args.out or os.path.join(ROOT, "chiprun_out", f"{args.workload}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)

    seed, sets = args.seed0, []
    with open(out, "a") as log:
        for s in range(args.sets):
            lines = []
            for _ in range(args.runs):
                line = one(args.workload, seed, seconds, 0, extra)
                seed += 1
                if line is None:
                    return 1  # a cell that fails once fails again: save the chip time
                log.write(json.dumps({"set": s, "seed": seed - 1, **line}) + "\n")
                log.flush()
                lines.append(line)
                print(f"set {s} seed {seed - 1} correct={line['correct']} " + " ".join(
                    f"{k}={v['value']:.6g}" for k, v in line["metrics"].items()), flush=True)
            sets.append(lines)
        for _ in range(args.traced):
            keep = ["--artifacts", args.artifacts] if args.artifacts else []
            line = one(args.workload, seed, seconds, 1, extra + keep)
            seed += 1
            if line is not None:
                log.write(json.dumps({"set": "traced", "seed": seed - 1, **line}) + "\n")
                print("traced: " + json.dumps(line), flush=True)

    names = sorted({k for lines in sets for ln in lines for k in ln["metrics"]})
    for name in names:
        for s, lines in enumerate(sets):
            vals = [ln["metrics"][name]["value"] for ln in lines if name in ln["metrics"]]
            if s == 0 and name == "setup_s" and len(vals) > 1:
                print(f"{name} first (compiling) run: {vals[0]:.6g}")
                vals = vals[1:]
            if vals:
                print(f"{name} set {s}: n={len(vals)} median={statistics.median(vals):.6g} "
                      f"spread={spread(vals):.4%} min={min(vals):.6g} max={max(vals):.6g}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
