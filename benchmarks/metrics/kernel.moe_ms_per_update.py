"""Device time under the expert blocks' scope (``moe``: router, dispatch, the
grouped products, combine and the shared expert) per update, from the trace:
forward, the rematerialised second forward, and backward."""

SCOPE = r"/moe/"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
