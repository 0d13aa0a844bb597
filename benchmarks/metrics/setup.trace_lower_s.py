"""Seconds of tracing and lowering between the entry of ``LearnerService.run``
and the window's start: Python's share of "compile time", paid in a warm
compile cache too. Per thread the union of the lane ``xla``'s ``trace`` and
``lower`` spans of at least 10 ms (a jitted callee's trace lies inside its
caller's), summed over threads; beside it the five programs that cost most,
from the per-program aggregate."""

from benchmarks import startup

KINDS = ("trace", "lower")


def read(run):
    s = startup.of_run(run)
    if s is None:
        return None
    return s.phase_s(KINDS), {"top": s.top(KINDS)}
