"""The masked attention kernel's share of its roofline in the ling_flash
family (latent attention, 192-wide queries and keys, 128-wide values): the
least time one chip could spend on an update's attention — the larger of
operations / peak FLOP/s and bytes / peak HBM bytes/s, from
``flops_ling_flash.attention_train`` at the query-key pairs the program
**counted** (``diag`` scalar ``attn-pairs-global``) and the model's 640
operations a pair and head — over the device time the trace shows under
``attn_flash_pallas`` (it also holds the rematerialised second forward, the
padding of the heads to the size the kernels take and the layout work around
them: padding shows as lost share, not as work). The line also says which of
the two bounds it and the pairs counted."""

from benchmarks import flops, flops_ling_flash

SCOPE = r"attn_flash_pallas"


def read(run):
    if run.trace is None or "kda_lower_bound" not in run.spec.params.get("arch", {}):
        return None
    seconds = run.trace.scope_s(SCOPE)
    pairs = flops_ling_flash.counted(run.window.rows, "attn-pairs-global")
    if seconds is None or pairs is None:
        return None
    params = run.spec.params
    rows = run.transitions_per_update // params["seq_len"] // params.get("mesh_data", 1)
    ops, nbytes = flops_ling_flash.attention_train(params, rows, pairs)
    peak = flops.peaks(run.device["kind"])
    t_ops, t_bytes = ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (
        100.0 * max(t_ops, t_bytes) / (seconds / run.trace.n_steps),
        {"bound": "compute" if t_ops >= t_bytes else "memory", "pairs": pairs},
    )
