"""Time per update in which a collective operation ran on a chip and nothing
else did (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all; their -start / -done halves included), averaged over the chips."""


def read(run):
    if run.trace is None:
        return None
    return 1e3 * run.trace.exposed_collective_s / run.trace.n_steps
