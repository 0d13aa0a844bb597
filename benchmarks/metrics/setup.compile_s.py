"""Seconds in XLA's backend between the entry of ``LearnerService.run`` and
the window's start — compilation, or the retrieval of an executable from the
persistent cache: per thread the union of the lane ``xla``'s ``backend`` spans
of at least 10 ms, summed over threads; beside it the five programs that cost
most, from the per-program aggregate."""

from benchmarks import startup

KINDS = ("backend",)


def read(run):
    s = startup.of_run(run)
    if s is None:
        return None
    return s.phase_s(KINDS), {"top": s.top(KINDS), **s.notes}
