"""The share of its roofline that the gated short convolution reaches between
its two projections: the least time one chip could spend moving what one fused
pass over ``b * x~``, the taps and ``c * h`` must move in an update — bytes /
peak HBM bytes/s, from ``flops_lfm2_moe.gate_train`` (forward, the
rematerialised second forward, backward; no matrix product, so the bound is
the memory's whatever the shapes) — over the device time the trace shows under
``shortconv_gate``. The line also names the bound."""

from benchmarks import flops, flops_lfm2_moe

SCOPE = r"shortconv_gate"


def read(run):
    if run.trace is None or "conv_L_cache" not in run.spec.params.get("arch", {}):
        return None
    seconds = run.trace.scope_s(SCOPE)
    if seconds is None:
        return None
    params = run.spec.params
    rows = run.transitions_per_update // params["seq_len"] // params.get("mesh_data", 1)
    nbytes = flops_lfm2_moe.gate_train(params, rows)
    t_bytes = nbytes / flops.peaks(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * t_bytes / (seconds / run.trace.n_steps), {"bound": "memory"}
