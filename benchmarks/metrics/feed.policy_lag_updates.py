"""How many updates old the policy was that produced the rows the learner
trained on: the row-weighted mean of the lower edges of ``learn.jsonl``'s
power-of-two staleness buckets over the window. Exact while the lag is at most
1, a lower bound beyond; rows whose version is unknown count as fresh (the
program's own fallback), so a missing version sidecar reads as 0."""

EDGES = {"0": 0, "1": 1, "2-3": 2, "4-7": 4, "8-15": 8, "16-31": 16,
         "32-63": 32, "64+": 64}


def read(run):
    rows = lag = 0.0
    for seen in run.window.rows:
        for bucket, doc in seen.row.get("buckets", {}).items():
            rows += doc["rows"]
            lag += doc["rows"] * EDGES[bucket]
    return lag / rows if rows else None
