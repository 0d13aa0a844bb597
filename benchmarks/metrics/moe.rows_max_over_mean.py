"""Per update and expert layer, the rows of the fullest held expert over the
mean held expert's, averaged over the window: the program counts them in-jit
(``diag`` scalar ``moe-rows-max-over-mean``, ``obs/learn.route_scalars``) and
every ``learn.jsonl`` line carries the mean over the updates since the last.
1 is an even load; the grouped matmul's row tiles are filled worse the higher
it reads."""


def read(run):
    vals = [r.row["moe-rows-max-over-mean"] for r in run.window.rows
            if "moe-rows-max-over-mean" in r.row]
    return sum(vals) / len(vals) if vals else None
