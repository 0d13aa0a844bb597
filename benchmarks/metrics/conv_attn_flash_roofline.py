"""The masked attention kernel's share of its roofline in the lfm2_moe family
(32 : 8 heads of 64, the grouped key/value heads unrepeated): the least time
one chip could spend on an update's attention — the larger of operations /
peak FLOP/s and bytes / peak HBM bytes/s, from
``flops_lfm2_moe.attention_train`` at the query-key pairs the program
**counted** (``diag`` scalar ``attn-pairs-global``, summed over the attention
layers: what the seams leave of the causal triangle) — over the device time
the trace shows under ``attn_flash_pallas`` (it also holds the rematerialised
second forward, the backward's own kernel and the layout work around them). A
tile the kernel visits and the seams empty earns nothing here. The line also
says which of the two bounds it and the pairs counted."""

from benchmarks import flops, flops_lfm2_moe

SCOPE = r"attn_flash_pallas"


def read(run):
    if run.trace is None or "conv_L_cache" not in run.spec.params.get("arch", {}):
        return None
    seconds = run.trace.scope_s(SCOPE)
    pairs = flops_lfm2_moe.counted(run.window.rows, "attn-pairs-global")
    if seconds is None or pairs is None:
        return None
    params = run.spec.params
    rows = run.transitions_per_update // params["seq_len"] // params.get("mesh_data", 1)
    ops, nbytes = flops_lfm2_moe.attention_train(params, rows, pairs)
    peak = flops.peaks(run.device["kind"])
    t_ops, t_bytes = ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (
        100.0 * max(t_ops, t_bytes) / (seconds / run.trace.n_steps),
        {"bound": "compute" if t_ops >= t_bytes else "memory", "pairs": pairs},
    )
