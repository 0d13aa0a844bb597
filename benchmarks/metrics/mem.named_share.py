"""Share of the live bytes at the window's fullest stamp that the program's
own owners account for (train state, batches, snapshots, inference
parameters, diag sums: each ``bytes_each`` times the count alive there): the
memory's ``loop.idle_attributed_share``. Beside it the unnamed remainder in
GiB — buffers of the process no owner counts — and each owner's part."""

from benchmarks import memory


def read(run):
    m = memory.of_run(run)
    if m is None or not m.top[memory.IN_USE]:
        return None
    live = m.top[memory.IN_USE]
    return 100.0 * m.named_bytes / live, {
        "unnamed_gib": (live - m.named_bytes) / memory.GIB,
        "live_gib": live / memory.GIB,
        "owners_gib": {
            name: round(m.at_peak(name) / memory.GIB, 6) for name in m.owners if m.at_peak(name)
        },
    }
