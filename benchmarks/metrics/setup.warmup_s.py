"""Seconds from the entry of the learner's loop to the window's start: the
first dispatches with their compilations, and the warm-up updates the traffic
file asks for (``warmup_pairs`` lines of ``learn.jsonl``)."""

from benchmarks import startup


def read(run):
    s = startup.of_run(run)
    if s is None:
        return None
    return s.window_start - s.loop_entry
