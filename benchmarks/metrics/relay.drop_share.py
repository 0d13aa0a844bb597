"""Environment steps the workers produced that never reached the store:
1 - (windows the store accepted x seq_len) / (env steps of the fleet), both as
rates over the window. It counts every loss between an env step and the
store, whoever drops it: the manager's queue, a full socket, the assembler's
idle-trajectory window, a full store. The program's own drop and reject
counters (``manager-dropped-frames``, ``*-rejected-frames``,
``storage-stale-epoch-frames``) stayed at 0 on the chip host while 99% of the
steps of four workers were lost (PERF.md, PR 22), so they are not used."""

from benchmarks import harness


def read(run):
    steps = harness.counter_rate(run.telemetry, "worker", "worker-env-steps")
    windows = harness.counter_rate(run.telemetry, "storage", "storage-windows")
    if not steps or windows is None:
        return None
    return 100.0 * (1.0 - windows * run.spec.params["seq_len"] / steps)
