"""The least time one chip could spend on an update's grouped expert products
— the larger of operations / peak FLOP/s and bytes / peak HBM bytes/s, from
``flops_nemotron_h.gmm_train`` and the routed rows the program **counted** in
the window (``diag`` scalar ``moe-rows``, the mean per update) — over the
device time the trace shows under ``moe_experts`` (which also holds the
rematerialised second forward and the weights' casts). The line also says
which of the two bounds it and how many rows an update routed."""

from benchmarks import flops, flops_nemotron_h

SCOPE = r"moe_experts"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    rows = [r.row["moe-rows"] for r in run.window.rows if "moe-rows" in r.row]
    if seconds is None or not rows:
        return None
    routed = sum(rows) / len(rows)
    ops, nbytes = flops_nemotron_h.gmm_train(run.spec.params, routed)
    peak = flops.peaks(run.device["kind"])
    t_ops, t_bytes = ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (
        100.0 * max(t_ops, t_bytes) / (seconds / run.trace.n_steps),
        {"bound": "compute" if t_ops >= t_bytes else "memory", "routed_rows": routed},
    )
