"""Time per update the learner's main lane spent in its own statements: every
``tpu_rl/main/*`` span of the traced window except the waits for the feed
(``feed-wait``, ``idle-poll``) and the spans that block on the device
(``log-sync``, ``diag-drain``, ``profiler-window``)."""

from benchmarks import hostplane


def read(run):
    host = hostplane.of_run(run)
    if host is None:
        return None
    return host.per_update_ms(
        run.trace, hostplane.MAIN, but=hostplane.FEED_WAITS + hostplane.DEVICE_SYNCS
    )
