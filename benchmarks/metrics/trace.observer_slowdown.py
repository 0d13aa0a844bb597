"""What the capture costs the program it observes: the device-observed period
inside the traced window over the same run's period outside the capture, minus
1. Outside is every interval between two ``learn.jsonl`` lines of the measured
window that the capture does not touch: the updates the profiler window covers
(the traffic file's ``trace`` block) and the interval its flush falls in are
left out, because one ``stop_trace`` takes seconds and a mean over them would
say more about the flush than about the loop."""


def read(run):
    window = run.spec.traffic.get("trace", {})
    if run.trace is None or "start_update" not in window:
        return None
    first = int(window["start_update"])
    last = first + int(window["updates"])
    rows = [run.window.start, *run.window.rows]
    seconds = updates = 0.0
    for a, b in zip(rows, rows[1:]):
        if b.idx < first or a.idx > last:  # the capture was not open in (a, b]
            seconds += b.mono - a.mono
            updates += b.idx - a.idx
    if not updates:
        return None
    inside = run.trace.window_s / run.trace.n_steps
    return 100.0 * (inside / (seconds / updates) - 1.0)
