"""Share of the main thread's time between the entry of ``LearnerService.run``
and the end of its first ``log-sync`` that lies under a span of the lane
``startup`` or ``main``: the start-up's ``loop.idle_attributed_share``. Beside
it whether the ring had wrapped when the record was written (then spans are
missing and the share reads low), and how many entries the lane ``xla`` added
to the ring by then."""

from benchmarks import startup


def read(run):
    s = startup.of_run(run)
    if s is None:
        return None
    return 100.0 * s.named_share(), {
        "ring_wrapped": s.ring_wrapped,
        "ring_entries": len(s.spans),
        "xla_entries": len(s.lane(startup.XLA)),
    }
