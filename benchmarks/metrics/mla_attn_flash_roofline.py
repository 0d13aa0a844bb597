"""The masked attention kernel's share of its roofline in the glm4_moe_lite
family (latent attention trained in its expanded form: 20 : 20 heads of 256,
the shared rotated key broadcast to every head): the least time one chip could
spend on an update's attention — the larger of operations / peak FLOP/s and
bytes / peak HBM bytes/s, from ``flops_glm4_moe_lite.attention_train`` at the
query-key pairs the program **counted** (``diag`` scalar
``attn-pairs-global``, summed over the layers: what the seams leave of the
causal triangle) — over the device time the trace shows under
``attn_flash_pallas`` (it also holds the rematerialised second forward and the
layout work around the kernel). A tile the kernel visits and the seams empty
earns nothing here. The line also says which of the two bounds it and the
pairs counted."""

from benchmarks import flops, flops_glm4_moe_lite

SCOPE = r"attn_flash_pallas"


def read(run):
    if run.trace is None or "kv_lora_rank" not in run.spec.params.get("arch", {}):
        return None
    seconds = run.trace.scope_s(SCOPE)
    pairs = flops_glm4_moe_lite.counted(run.window.rows, "attn-pairs-global")
    if seconds is None or pairs is None:
        return None
    params = run.spec.params
    rows = run.transitions_per_update // params["seq_len"] // params.get("mesh_data", 1)
    ops, nbytes = flops_glm4_moe_lite.attention_train(params, rows, pairs)
    peak = flops.peaks(run.device["kind"])
    t_ops, t_bytes = ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (
        100.0 * max(t_ops, t_bytes) / (seconds / run.trace.n_steps),
        {"bound": "compute" if t_ops >= t_bytes else "memory", "pairs": pairs},
    )
