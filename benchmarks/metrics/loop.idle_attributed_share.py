"""Share of the first chip's idle time, inside the traced window, during which
a named span of the learner's main lane (``tpu_rl/main/*``) was open: how much
of "the chip waits for the host" the program can put a name to. Beside the
value: the ten longest idle gaps with the name of the main-lane span that
covers most of each (a wait for the feed is suffixed with what the feeder lane
was doing), and ``clock_ok``, the causal check of the clock that host spans and
device events share (``benchmarks/hostplane.py``); where it fails nothing is
named and the share reads 0."""

from benchmarks import hostplane


def read(run):
    host = hostplane.of_run(run)
    if host is None:
        return None
    share = host.attributed_share(run.trace)
    if share is None:
        return None
    return 100.0 * share, {
        "clock_ok": host.clock_ok(run.trace),
        "idle_gaps": host.idle_gaps(run.trace),
    }
