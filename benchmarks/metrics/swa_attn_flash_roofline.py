"""The masked attention kernel's share of its roofline in the smallthinker
family: the least time one chip could spend on an update's attention — the
larger of operations / peak FLOP/s and bytes / peak HBM bytes/s, from
``flops_smallthinker.attention_train`` at the query-key pairs the program
**counted** (``diag`` scalars ``attn-pairs-global`` + ``attn-pairs-window``:
what the window and the seams leave) — over the device time the trace shows
under ``attn_flash_pallas`` (global and window layers together; it also holds
the rematerialised second forward and the layout work around the kernel). A
tile the kernel visits and the seams empty earns nothing here. The line also
says which of the two bounds it and the pairs counted."""

from benchmarks import flops, flops_smallthinker

SCOPE = r"attn_flash_pallas"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    pairs = [flops_smallthinker.counted(run.window.rows, f"attn-pairs-{kind}")
             for kind in ("global", "window")]
    if seconds is None or None in pairs:
        return None
    params = run.spec.params
    rows = run.transitions_per_update // params["seq_len"] // params.get("mesh_data", 1)
    ops, nbytes = flops_smallthinker.attention_train(params, rows, sum(pairs))
    peak = flops.peaks(run.device["kind"])
    t_ops, t_bytes = ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (
        100.0 * max(t_ops, t_bytes) / (seconds / run.trace.n_steps),
        {"bound": "compute" if t_ops >= t_bytes else "memory", "pairs": sum(pairs)},
    )
