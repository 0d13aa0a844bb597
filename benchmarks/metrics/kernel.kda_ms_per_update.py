"""Device time under the Kimi-Delta-Attention scopes that are not projections
(``kda_scan``: the chunked per-channel delta rule, its cumulative sums and
decay factors among it; ``kda_conv``: the depthwise convolution before it;
``kda_gate``: the bounded gate) per update, from the trace: forward, the
rematerialised forwards (the layer's and, for the scan, its spans'), and
backward."""

SCOPE = r"kda_scan|kda_conv|kda_gate"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
