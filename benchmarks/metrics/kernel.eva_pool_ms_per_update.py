"""Device time per update under ``eva_pool`` (finding the window's complete
chunks, gathering their members, the pooling's logits and two softmaxes, the
weighted sums, and the members' gradients scattered back), from the trace:
what turning 16 keys into one summary costs."""

SCOPE = r"eva_pool"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
