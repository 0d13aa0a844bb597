"""Trajectory windows per second the storage process assembled and the
shared-memory store accepted (``storage-windows``)."""

from benchmarks import harness


def read(run):
    return harness.counter_rate(run.telemetry, "storage", "storage-windows")
