"""Model FLOP/s utilisation: the operations an update's forward and backward
passes need (``benchmarks/flops.py``: shapes only, no recompute) times the
updates per second the device completed in the traced window, over chips times
the chip's bf16 peak (``benchmarks/peaks.json``)."""

from benchmarks import flops


def read(run):
    if run.trace is None:
        return None
    params = run.spec.params
    rows = run.transitions_per_update // params["seq_len"]
    ops = flops.update(params, rows, run.spec.traffic.get("acts_in_program", False))
    peak = flops.peaks(run.device["kind"])["bf16_flops_per_s"]
    per_s = run.trace.n_steps / run.trace.window_s
    return 100.0 * ops * per_s / (params.get("mesh_data", 1) * peak)
