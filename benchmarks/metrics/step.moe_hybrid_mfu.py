"""Model FLOP/s utilisation of the nemotron_h family: the operations an
update's forward and backward passes need (``benchmarks/flops_nemotron_h``:
shapes, and the held experts at the rows the program counted; nothing
recomputed) times the updates per second the device completed in the traced
window, over the chip's bf16 peak."""

from benchmarks import flops, flops_nemotron_h


def read(run):
    params = run.spec.params
    counted = [r.row["moe-rows"] for r in run.window.rows if "moe-rows" in r.row]
    if run.trace is None or not counted or "hybrid_override_pattern" not in params.get("arch", {}):
        return None
    rows = run.transitions_per_update // params["seq_len"]
    ops = flops_nemotron_h.update(params, rows, sum(counted) / len(counted))
    peak = flops.peaks(run.device["kind"])["bf16_flops_per_s"]
    per_s = run.trace.n_steps / run.trace.window_s
    return 100.0 * ops * per_s / (params.get("mesh_data", 1) * peak)
