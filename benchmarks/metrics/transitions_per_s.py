"""Transitions consumed by completed learner updates per second of window.

Updates are counted from ``learn.jsonl`` update indices (written after a
blocking device read-back) between two lines the benchmark stamped itself."""


def read(run):
    return run.window.updates * run.transitions_per_update / run.window.seconds
