"""Model FLOP/s utilisation of the evabyte family: the operations an update's
forward and backward passes need (``benchmarks/flops_evabyte``: shapes, and
attention at the pairs of both kinds the program counted; the chunk pooling is
bytes and counts nothing; nothing recomputed) times the updates per second the
device completed in the traced window, over the chip's bf16 peak. The line
also says the bound: a share of the compute peak."""

from benchmarks import flops, flops_evabyte


def read(run):
    params = run.spec.params
    if run.trace is None or "chunk_size" not in params.get("arch", {}):
        return None
    pairs = flops_evabyte.counted_pairs(run.window.rows)
    if pairs is None:
        return None
    rows = run.transitions_per_update // params["seq_len"]
    ops = flops_evabyte.update(params, rows, sum(pairs))
    peak = flops.peaks(run.device["kind"])["bf16_flops_per_s"]
    per_s = run.trace.n_steps / run.trace.window_s
    return 100.0 * ops * per_s / (params.get("mesh_data", 1) * peak), {"bound": "compute"}
