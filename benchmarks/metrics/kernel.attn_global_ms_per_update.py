"""Device time under ``attn_global`` (the global NoPE layers' attention
mixers: projections, the causal kernel, forward, the rematerialised second
forward and backward) per update, from the trace."""

SCOPE = r"attn_global"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
