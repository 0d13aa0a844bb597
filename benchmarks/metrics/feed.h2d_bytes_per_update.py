"""Bytes one update moves from the host to the device: the program's own
``BatchLayout`` (every field of every step, float32) times the batch. Exact,
and data-independent."""


def read(run):
    return run.bytes_per_update or None  # no feed, nothing to read
