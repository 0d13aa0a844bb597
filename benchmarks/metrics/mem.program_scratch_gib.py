"""GiB of scratch the runtime had reserved for running programs when the
learner's first logged update had finished (``window.scratch_bytes``: the
lifetime peak of the runtime's second book at the first ``log-sync``): the
update program's activations and temporaries — if the learner raised it
(``raised_by_learner``); where a program of the set-up had already reserved
more it is that program's, an upper bound. ``reserved_peak_rose`` says
whether anything later in the run reserved more."""

from benchmarks import memory


def read(run):
    m = memory.of_run(run)
    if m is None:
        return None
    return m.window["scratch_bytes"] / memory.GIB, {
        "raised_by_learner": m.window["raised_by_learner"],
        "reserved_peak_rose": m.window["reserved_peak_rose"],
    }
