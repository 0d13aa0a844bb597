"""The per-channel delta-rule scan's share of its roofline: the least time one
chip could spend on an update's scans — the larger of operations / peak FLOP/s
and bytes / peak HBM bytes/s, from ``flops_ling_flash.kda_train`` (the chunked
algorithm at chunks of 64 in sub-blocks of 16, nothing recomputed, no
convolution; the decay alone is 16 KB a token and layer) — over the device
time the trace shows under ``kda_scan``, whatever implements it (it does hold
the rematerialised second and third forward). The line also says which of the
two bounds it."""

from benchmarks import flops, flops_ling_flash

SCOPE = r"kda_scan"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    if seconds is None:
        return None
    params = run.spec.params
    rows = run.transitions_per_update // params["seq_len"] // params.get("mesh_data", 1)
    ops, nbytes = flops_ling_flash.kda_train(params, rows)
    peak = flops.peaks(run.device["kind"])
    t_ops, t_bytes = ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (
        100.0 * max(t_ops, t_bytes) / (seconds / run.trace.n_steps),
        {"bound": "compute" if t_ops >= t_bytes else "memory"},
    )
