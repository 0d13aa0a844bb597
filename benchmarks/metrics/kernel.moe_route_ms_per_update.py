"""Device time per update under ``moe_route`` (router matmul, sigmoid, top-k,
weights), ``moe_dispatch`` (sort, gather) and ``moe_combine`` (gather back,
weighting), from the trace: what sparse routing costs without touching an
expert's weights."""

SCOPE = r"moe_route|moe_dispatch|moe_combine"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
