"""Under a group-limited router, the share of tokens whose kept groups hold an
expert this rank holds, averaged over the expert layers and the window: only
those tokens can send a row here. The program counts it in-jit (``diag`` scalar
``moe-group-hit-share``, ``obs/learn.route_scalars`` over
``ops/moe.route_stats``) and every ``learn.jsonl`` line carries the mean over
the updates since the last. With 4 of 8 groups kept and one group's experts
held a fair router reads 50%. A program whose router has no group stage ships
no such counter and reads nothing."""


def read(run):
    vals = [r.row["moe-group-hit-share"] for r in run.window.rows
            if "moe-group-hit-share" in r.row]
    return 100.0 * sum(vals) / len(vals) if vals else None
