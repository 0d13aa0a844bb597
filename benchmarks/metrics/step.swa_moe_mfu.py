"""Model FLOP/s utilisation of the smallthinker family: the operations an
update's forward and backward passes need (``benchmarks/flops_smallthinker``:
shapes, attention at the query-key pairs and the held experts at the rows the
program counted; nothing recomputed) times the updates per second the device
completed in the traced window, over the chip's bf16 peak."""

from benchmarks import flops, flops_smallthinker


def read(run):
    params = run.spec.params
    pairs = [flops_smallthinker.counted(run.window.rows, f"attn-pairs-{kind}")
             for kind in ("global", "window")]
    routed = flops_smallthinker.counted(run.window.rows, "moe-rows")
    if run.trace is None or None in pairs or routed is None:
        return None
    rows = run.transitions_per_update // params["seq_len"]
    ops = flops_smallthinker.update(params, rows, sum(pairs), routed)
    peak = flops.peaks(run.device["kind"])["bf16_flops_per_s"]
    per_s = run.trace.n_steps / run.trace.window_s
    return 100.0 * ops * per_s / (params.get("mesh_data", 1) * peak)
