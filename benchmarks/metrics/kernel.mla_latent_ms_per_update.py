"""Device time per update under ``mla_down`` (``q_a_proj``, ``kv_a_proj`` and
the two latent norms) and ``mla_up`` (``q_b_proj``, ``kv_b_proj``, the split
into unrotated and rotated parts and the assembly of every head's query and
key, the shared key's broadcast among them), from the trace: what the low-rank
path costs outside the attention kernel."""

SCOPE = r"mla_down|mla_up"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
