"""Peak bytes in use on the fullest chip, read by the chip's owner from
``device.memory_stats()`` after the window."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 2**30 if peak else None
