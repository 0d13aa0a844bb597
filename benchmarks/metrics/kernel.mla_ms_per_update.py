"""Device time under the latent-attention scope (``mla``: the layer's input
norm, the low-rank projections and their norms, the rotation, the assembly of
every head's query and key with the shared key's broadcast, the splash
kernels and the output projection) per update, from the trace: forward, the
rematerialised second forward, and backward. It contains what
``kernel.attn_ms_per_update`` reads (``attn_flash_pallas``)."""

SCOPE = r"/mla/"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
