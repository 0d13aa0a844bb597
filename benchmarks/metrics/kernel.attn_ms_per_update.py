"""Device time under the attention scope (``attn_flash_pallas`` or
``attn_full``: the kernel, forward and backward, and the layout work around
it) per update, from the trace."""

SCOPE = r"attn_flash_pallas|attn_full"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
