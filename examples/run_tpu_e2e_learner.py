"""On-chip END-TO-END learner FPS: the production LearnerService fed through
the REAL shared-memory path (OnPolicyStore put -> consume -> assemble ->
chained dispatch), not a synthetic pre-placed device batch.

This is the honest counterpart to bench.py's @ref rows (which time the
compiled step on a device-resident batch): here every update's batch crosses
host shm -> device, exactly like a deployment. If the host feed cannot keep
the chip busy, that gap IS the result — both rates are reported.

The harness itself lives in ``bench.e2e_learner_row`` (shared with the
``TPU_RL_BENCH_E2E`` A/B mode); this wrapper adds the CLI. ``--feed``
selects the data plane: ``prefetch`` (pipelined feeder thread,
``Config.learner_prefetch`` depth), ``sync`` (the serial baseline,
``learner_prefetch=0``), or ``both`` (run each and report the speedup —
the overlap A/B on real hardware).

The reference's corresponding instrument is the learner-throughput timer
around its sample+update loop (``/root/reference/utils/utils.py:167-189``).

Run on the TPU host (learner owns the chip; feeders are host threads):
  PYTHONPATH=/root/repo python examples/run_tpu_e2e_learner.py \
      [--updates 2048] [--chain 16] [--feeders 4] [--feed both] \
      [--prefetch-depth 2] [--out bench_e2e_learner.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--updates", type=int, default=2048)
    p.add_argument("--chain", type=int, default=16)
    p.add_argument("--feeders", type=int, default=4)
    p.add_argument("--publish-interval", type=int, default=256)
    p.add_argument(
        "--feed", choices=("prefetch", "sync", "both"), default="prefetch",
        help="data plane: pipelined feed, serial baseline, or A/B both",
    )
    p.add_argument("--prefetch-depth", type=int, default=2)
    p.add_argument("--out", default="bench_e2e_learner.json")
    args = p.parse_args()

    from bench import e2e_learner_row, run_e2e_compare

    if args.feed == "both":
        result = run_e2e_compare(
            updates=args.updates, chain=args.chain, feeders=args.feeders,
            out_path=args.out,
        )
        print(json.dumps(result), flush=True)
        print(f"wrote {args.out}", flush=True)
        return

    prefetch = args.prefetch_depth if args.feed == "prefetch" else 0
    row = e2e_learner_row(
        updates=args.updates, chain=args.chain, feeders=args.feeders,
        publish_interval=args.publish_interval, prefetch=prefetch,
    )
    row["note"] = (
        "e2e through the real shm feed (put->consume->assemble->chained "
        "dispatch); feed_blocked_ratio ~1 means the chip outran the host "
        "feed's spare capacity, ~0 means the feed was the bottleneck"
    )
    print(json.dumps(row), flush=True)
    with open(args.out, "w") as f:
        json.dump(row, f, indent=1)
    print(f"wrote {args.out}", flush=True)


if __name__ == "__main__":
    main()
