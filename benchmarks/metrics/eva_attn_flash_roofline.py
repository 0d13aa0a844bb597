"""The two-resolution read's share of its roofline in the evabyte family (32
heads of 128): the least time one chip could spend on an update's attention —
the larger of operations / peak FLOP/s and bytes / peak HBM bytes/s, from
``flops_evabyte.attention_train`` at the pairs the program **counted**
(``diag`` scalars ``attn-pairs-block`` + ``attn-pairs-summary``, each summed
over the layers: a summary counts where it is read, once) — over the device
time the trace shows under ``attn_flash_pallas`` (the kernels over the blocks'
exact keys and, inside ``eva_summary``, over the summaries: forward, the
rematerialised second forward, the backward's own kernel, the layout work
around them) **or** under ``eva_summary`` (the summaries' read and the merge of
the two parts by their logsumexps: where a program reads the summaries outside
the kernels, as the ``jnp`` form does, their pairs are counted all the same, so
their time is too). A tile the kernel visits and a
boundary empties earns nothing here, nor does a candidate the mask drops. The
line also says which of the two bounds it and the pairs counted."""

from benchmarks import flops, flops_evabyte

SCOPE = r"attn_flash_pallas|eva_summary"


def read(run):
    if run.trace is None or "chunk_size" not in run.spec.params.get("arch", {}):
        return None
    seconds = run.trace.scope_s(SCOPE)
    pairs = flops_evabyte.counted_pairs(run.window.rows)
    if seconds is None or pairs is None:
        return None
    params = run.spec.params
    rows = run.transitions_per_update // params["seq_len"] // params.get("mesh_data", 1)
    ops, nbytes = flops_evabyte.attention_train(params, rows, sum(pairs))
    peak = flops.peaks(run.device["kind"])
    t_ops, t_bytes = ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (
        100.0 * max(t_ops, t_bytes) / (seconds / run.trace.n_steps),
        {"bound": "compute" if t_ops >= t_bytes else "memory", "pairs": sum(pairs)},
    )
