"""Time per update the learner's main lane spent on the weight broadcast: the
``tpu_rl/main/publish`` span (device-side snapshot copies and the start of
their transfer to the host; the whole send where the publisher thread is off)."""

from benchmarks import hostplane


def read(run):
    host = hostplane.of_run(run)
    if host is None:
        return None
    return host.per_update_ms(run.trace, hostplane.MAIN, names=("publish",))
