"""GiB of tree-sized copies alive at the window's fullest stamp: the
broadcast's snapshots (the slot's and the publisher's), the in-process
inference service's parameters with a swap in flight, and a save's snapshot
of the train state until the writer has it on the host. Each beside it, with
the largest count of the run; and where no save fell into the loop (the one
at shutdown is outside the window),
``a_save_would_hold_gib``: the train state once more, what a checkpointing
job has to find room for."""

from benchmarks import memory


def read(run):
    m = memory.of_run(run)
    if m is None:
        return None
    extra = {}
    for name in memory.SNAPSHOT_OWNERS:
        key = name.replace("-", "_")
        extra[f"{key}_gib"] = m.at_peak(name) / memory.GIB
        extra[f"{key}_alive_max"] = m.owners[name]["alive_max"]
    if not m.saved_in_loop:
        extra["a_save_would_hold_gib"] = m.each("train-state") / memory.GIB
    return sum(m.at_peak(n) for n in memory.SNAPSHOT_OWNERS) / memory.GIB, extra
