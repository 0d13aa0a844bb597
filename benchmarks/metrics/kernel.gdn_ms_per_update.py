"""Device time under the Gated-DeltaNet scopes (``gdn_scan``: the chunked
delta rule; ``gdn_conv``: the depthwise convolution before it) per update,
from the trace: forward, the rematerialised forwards (the layer's and, for the
scan, its spans'), and backward."""

SCOPE = r"gdn_scan|gdn_conv"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
