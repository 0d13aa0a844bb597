"""Model FLOP/s utilisation of the granite_hybrid family: the operations an
update's forward and backward passes need (``benchmarks/flops_granite_hybrid``:
shapes only, the rematerialised second forward not counted) times the updates
per second the device completed in the traced window, over chips times the
chip's bf16 peak. ``step.mfu`` prices every model that is not the transformer
as an LSTM, so this family has a reader of its own."""

from benchmarks import flops, flops_granite_hybrid


def read(run):
    params = run.spec.params
    if run.trace is None or "arch" not in params:
        return None
    rows = run.transitions_per_update // params["seq_len"]
    ops = flops_granite_hybrid.update(params, rows)
    peak = flops.peaks(run.device["kind"])["bf16_flops_per_s"]
    per_s = run.trace.n_steps / run.trace.window_s
    return 100.0 * ops * per_s / (params.get("mesh_data", 1) * peak)
