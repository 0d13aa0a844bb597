"""Device time under the LSTM unroll's scope (``lstm_pallas``: the fused
kernel, or ``lstm_scan``: the XLA scan), forward and backward, per update."""

SCOPE = r"lstm_pallas|lstm_scan"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
