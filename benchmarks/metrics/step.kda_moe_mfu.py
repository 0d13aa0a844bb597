"""Model FLOP/s utilisation of the ling_flash family: the operations an
update's forward and backward passes need (``benchmarks/flops_ling_flash``:
shapes, the scan as the chunked algorithm in its ten sub-block products,
latent attention at the query-key pairs and the held experts at the rows the
program counted; nothing recomputed, nothing for padding) times the updates
per second the device completed in the traced window, over the chip's bf16
peak. The line also says what bounds the step: compute (this is a share of
the bf16 peak)."""

from benchmarks import flops, flops_ling_flash


def read(run):
    params = run.spec.params
    if run.trace is None or "kda_lower_bound" not in params.get("arch", {}):
        return None
    pairs = flops_ling_flash.counted(run.window.rows, "attn-pairs-global")
    routed = flops_ling_flash.counted(run.window.rows, "moe-rows")
    if pairs is None or routed is None:
        return None
    rows = run.transitions_per_update // params["seq_len"]
    ops = flops_ling_flash.update(params, rows, pairs, routed)
    peak = flops.peaks(run.device["kind"])["bf16_flops_per_s"]
    per_s = run.trace.n_steps / run.trace.window_s
    return (
        100.0 * ops * per_s / (params.get("mesh_data", 1) * peak),
        {"bound": "compute", "pairs": pairs, "routed_rows": routed},
    )
