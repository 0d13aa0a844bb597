"""GiB the process had *ever* held when ``LearnerService.run`` got the chip,
before the learner allocated a byte: the runtime's two lifetime peaks at the
stamp ``run`` (``backend-learner.json``: ``memory``), added as
``peak_hbm_gib`` adds them. It is the benchmark's own part of that metric —
the parity checks' programs and ``warm_snapshots``' two train states — and
where it is the larger part, ``peak_hbm_gib`` reads the set-up, not the loop.
Beside it its share of the run's ``peak_hbm_gib``, the two books apart and
what was still live at that instant."""

from benchmarks import memory


def read(run):
    m = memory.of_run(run)
    at = m and m.stamp("run")
    if not at:
        return None
    before = at[memory.PEAK_IN_USE] + at[memory.PEAK_RESERVED]
    extra = {
        "peak_in_use_gib": at[memory.PEAK_IN_USE] / memory.GIB,
        "peak_reserved_gib": at[memory.PEAK_RESERVED] / memory.GIB,
        "live_at_entry_gib": at[memory.IN_USE] / memory.GIB,
    }
    if m.peak_hbm_bytes:
        extra["share_of_peak_hbm"] = 100.0 * before / m.peak_hbm_bytes
    return before / memory.GIB, extra
