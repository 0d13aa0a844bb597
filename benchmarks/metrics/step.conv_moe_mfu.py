"""Model FLOP/s utilisation of the lfm2_moe family: the operations an
update's forward and backward passes need (``benchmarks/flops_lfm2_moe``:
shapes, attention at the query-key pairs and the held experts at the rows the
program counted; the convolution's gates and taps are bytes and count
nothing; nothing recomputed) times the updates per second the device
completed in the traced window, over the chip's bf16 peak. The line also says
the bound: a share of the compute peak."""

from benchmarks import flops, flops_lfm2_moe


def read(run):
    params = run.spec.params
    if run.trace is None or "conv_L_cache" not in params.get("arch", {}):
        return None
    pairs = flops_lfm2_moe.counted(run.window.rows, "attn-pairs-global")
    routed = flops_lfm2_moe.counted(run.window.rows, "moe-rows")
    if pairs is None or routed is None:
        return None
    rows = run.transitions_per_update // params["seq_len"]
    ops = flops_lfm2_moe.update(params, rows, pairs, routed)
    peak = flops.peaks(run.device["kind"])["bf16_flops_per_s"]
    per_s = run.trace.n_steps / run.trace.window_s
    return 100.0 * ops * per_s / (params.get("mesh_data", 1) * peak), {"bound": "compute"}
