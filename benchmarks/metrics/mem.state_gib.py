"""GiB of the placed train state on one chip (parameters, optimizer state,
counters): the owner ``train-state`` of the learner's memory book, from the
leaves' own sizes after ``place``. Beside it how many were alive at most
(two during a rollback's restore)."""

from benchmarks import memory


def read(run):
    m = memory.of_run(run)
    if m is None or not m.each("train-state"):
        return None
    return m.each("train-state") / memory.GIB, {
        "alive_max": m.owners["train-state"]["alive_max"]
    }
