"""Device time under the short-convolution mixers' scope (``shortconv``:
``in_proj`` and its split, the gates' product, the taps, ``c * h`` and
``out_proj``) per update, from the trace: forward, the rematerialised second
forward, and backward. It contains what
``kernel.shortconv_gate_ms_per_update`` reads."""

SCOPE = r"/shortconv/"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
