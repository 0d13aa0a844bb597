"""``attn_flash_roofline`` for the nemotron_h family: the least time one chip
could spend on an update's attention at this model's widths (32 query heads
on 2 key/value heads, head size 128; ``flops_nemotron_h.attention_train``)
over the device time the trace shows under the attention scope. The line also
says which of the two bounds it."""

from benchmarks import flops, flops_nemotron_h

SCOPE = r"attn_flash_pallas"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    if seconds is None:
        return None
    params = run.spec.params
    rows = run.transitions_per_update // params["seq_len"] // params.get("mesh_data", 1)
    ops, nbytes = flops_nemotron_h.attention_train(params, rows)
    peak = flops.peaks(run.device["kind"])
    t_ops, t_bytes = ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (
        100.0 * max(t_ops, t_bytes) / (seconds / run.trace.n_steps),
        {"bound": "compute" if t_ops >= t_bytes else "memory"},
    )
