"""Device time under ``attn_window`` (the sliding-window RoPE layers'
attention mixers: projections, rotation, the masked kernel, forward, the
rematerialised second forward and backward) per update, from the trace. Three
such layers to one global one: if each costs what the global layer costs, the
kernel is not skipping the tiles behind the band."""

SCOPE = r"attn_window"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
