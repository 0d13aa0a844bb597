"""Device time under the Mamba-2 scan's scopes (``ssd_scan``: the chunked
recurrence; ``ssd_conv``: the depthwise convolution before it) per update, from
the trace: forward, the rematerialised second forward, and backward."""

SCOPE = r"ssd_scan|ssd_conv"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
