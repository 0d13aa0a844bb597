"""GiB one chip must hold *at once* for this cell's loop: the live peak from
the first ``log-sync`` on plus the scratch reserved for the running update.
The live peak is the runtime's own lifetime peak where it rose inside the
window (``exact``), else the fullest stamp — a lower bound, because the
set-up's higher peak hides the loop's. Beside it the chip's ``bytes_limit``,
the headroom under it, the site and update of the fullest stamp, and
``fits_a_save``: whether the headroom holds the train state once more."""

from benchmarks import memory


def read(run):
    m = memory.of_run(run)
    if m is None:
        return None
    extra = {
        "live_peak_gib": m.window["live_peak_bytes"] / memory.GIB,
        "exact": m.window["in_use_peak_rose"],
        "peak_site": m.top[memory.SITE],
        "peak_update": m.top[memory.UPDATE],
    }
    if m.bytes_limit:
        headroom = m.bytes_limit - m.window_peak_bytes
        extra.update(
            bytes_limit_gib=m.bytes_limit / memory.GIB,
            headroom_gib=headroom / memory.GIB,
            fits_a_save=headroom >= m.each("train-state"),
        )
    return m.window_peak_bytes / memory.GIB, extra
