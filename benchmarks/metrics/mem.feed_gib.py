"""GiB of placed batches alive at the window's fullest stamp: the owner
``batch`` (one placed batch, from its leaves' sizes) times the count alive —
in placement or placed and untaken on the feed's side, plus dispatched into
an update that has not finished. Beside it one batch, the count there, the
largest count of the run and the bound the program's design gives it
(``learner_prefetch`` + ``RUN_AHEAD``)."""

from benchmarks import memory


def read(run):
    m = memory.of_run(run)
    if m is None or not m.each("batch"):
        return None
    batch = m.owners["batch"]
    return m.at_peak("batch") / memory.GIB, {
        "batch_gib": m.each("batch") / memory.GIB,
        "alive_at_peak": m.window["alive"]["batch"],
        "alive_max": batch["alive_max"],
        "bound": batch["bound"],
    }
