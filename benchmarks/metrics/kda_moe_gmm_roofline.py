"""``moe_glu_gmm_roofline`` for the ling_flash family's SwiGLU experts (three
grouped products a row, top-8 of 512 under the group limit, 8 held — one group
of eight's eighth): the least time one chip could spend on an update's grouped
expert products — the larger of operations / peak FLOP/s and bytes / peak HBM
bytes/s, from ``flops_ling_flash.gmm_train`` and the routed rows the program
**counted** in the window (``diag`` scalar ``moe-rows``, the mean per update)
— over the device time the trace shows under ``moe_experts`` (which also
holds the rematerialised second forward, the gate's product and the weights'
casts). The line also says which of the two bounds it and how many rows an
update routed."""

from benchmarks import flops, flops_ling_flash

SCOPE = r"moe_experts"


def read(run):
    if run.trace is None or "kda_lower_bound" not in run.spec.params.get("arch", {}):
        return None
    seconds = run.trace.scope_s(SCOPE)
    routed = flops_ling_flash.counted(run.window.rows, "moe-rows")
    if seconds is None or routed is None:
        return None
    ops, nbytes = flops_ling_flash.gmm_train(run.spec.params, routed)
    peak = flops.peaks(run.device["kind"])
    t_ops, t_bytes = ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (
        100.0 * max(t_ops, t_bytes) / (seconds / run.trace.n_steps),
        {"bound": "compute" if t_ops >= t_bytes else "memory", "routed_rows": routed},
    )
