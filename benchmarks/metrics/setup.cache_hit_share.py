"""Share of the learner's backend compilations before the window's start that
the persistent compile cache answered: hits / (hits + misses) of
``jax.monitoring``'s cache events, each counted at the program it belongs to.
Says of a ``setup_s`` reading whether it was a warm one. A cold run does not
read 0: the benchmark's checks compile in this process before the learner
does, and what they wrote minutes ago the learner's few small programs hit —
so beside the count the same share by backend seconds (``hit_s`` /
``miss_s`` over the phases of at least 10 ms), which the update program's
compilation decides. None when the process has no persistent cache (no
verdicts)."""

from benchmarks import startup


def read(run):
    s = startup.of_run(run)
    if s is None:
        return None
    hits, misses = s.verdicts()
    if hits + misses <= 0:
        return None
    secs = {"hit": 0.0, "miss": 0.0}
    for p in s.phases:
        if p.cache in secs and p.start < s.window_start:
            secs[p.cache] += p.secs
    return 100.0 * hits / (hits + misses), {
        "hits": hits, "misses": misses,
        "hit_s": round(secs["hit"], 3), "miss_s": round(secs["miss"], 3),
    }
