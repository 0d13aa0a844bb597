"""Time per update the learner's main lane spent blocked on the device: the
``log-sync`` (wait for the update, read its scalars back), ``diag-drain`` (the
diagnostics' read-back) and ``profiler-window`` spans of the traced window."""

from benchmarks import hostplane


def read(run):
    host = hostplane.of_run(run)
    if host is None:
        return None
    return host.per_update_ms(run.trace, hostplane.MAIN, names=hostplane.DEVICE_SYNCS)
