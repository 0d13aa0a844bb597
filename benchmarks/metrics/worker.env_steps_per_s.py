"""Environment steps per second of the whole worker fleet: the sum over the
workers of the change of ``worker-env-steps`` between the first and the last
``telemetry.json`` of the window, each worker on its own clock."""

from benchmarks import harness


def read(run):
    return harness.counter_rate(run.telemetry, "worker", "worker-env-steps")
