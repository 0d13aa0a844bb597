"""The share of its roofline that the chunk pooling reaches: the least time
one chip could spend moving what one fused pass over the pooling must move in
an update — bytes / peak HBM bytes/s, from ``flops_evabyte.pool_train``
(forward, the rematerialised second forward, backward; no matrix product, so
the bound is the memory's whatever the shapes) — over the device time the
trace shows under ``eva_pool``. The line also names the bound."""

from benchmarks import flops, flops_evabyte

SCOPE = r"eva_pool"


def read(run):
    if run.trace is None or "chunk_size" not in run.spec.params.get("arch", {}):
        return None
    seconds = run.trace.scope_s(SCOPE)
    if seconds is None:
        return None
    params = run.spec.params
    rows = run.transitions_per_update // params["seq_len"] // params.get("mesh_data", 1)
    nbytes = flops_evabyte.pool_train(params, rows)
    t_bytes = nbytes / flops.peaks(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * t_bytes / (seconds / run.trace.n_steps), {"bound": "memory"}
