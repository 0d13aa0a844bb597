"""Busy time per update of the publisher thread: ``publish-d2h`` (the blocking
``device_get``, which also waits for the update that produced the weights) plus
``publish-send`` (encode and ZMQ send, under the GIL)."""

from benchmarks import hostplane


def read(run):
    host = hostplane.of_run(run)
    if host is None:
        return None
    return host.per_update_ms(run.trace, hostplane.PUBLISHER)
