"""Device time per update under ``shortconv_gate`` (``b * x~``, the
``conv_L_cache`` seam-stopped taps — ``shortconv_conv`` inside it — and ``c *
h``, with their float32 casts and the taps' weight-gradient sums), from the
trace: what the gated short convolution costs outside its two projections."""

SCOPE = r"shortconv_gate"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
