"""Share of the traced window the learner's main lane spent waiting for data:
``feed-wait`` (every pop of the feed, whether it found a batch or gave up after
50 ms) plus ``idle-poll`` (what the loop does after an empty pop). The wait
timer behind ``feed.wait_share`` counts successful pops only."""

from benchmarks import hostplane


def read(run):
    host = hostplane.of_run(run)
    if host is None:
        return None
    lo, hi = run.trace.devices[0].window
    return 100.0 * host.lane_ns(run.trace, hostplane.MAIN, names=hostplane.FEED_WAITS) / (hi - lo)
