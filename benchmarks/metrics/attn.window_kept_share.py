"""Of the query-key pairs a global layer's mask keeps (causal and same
episode), the share a sliding-window layer's keeps, over the window's updates:
the program counts both in-jit from ``is_fir`` (``diag`` scalars
``attn-pairs-window`` and ``attn-pairs-global``, each summed over the layers
of its kind; every ``learn.jsonl`` line carries the mean over the updates
since the last), and this is the ratio of the two sums per layer: weighted by
pairs, as the kernel's work is (a mean of the updates' own ratios reads 1-3
points higher; the program ships no such gauge). 44% with no seam at 16,384
steps and a window of 4,096; the closer to 100%, the less the window does on
this traffic. A pool of 16 seeded windows reads 55-70% (5th-95th percentile
over seeds, by simulation of ``traffic.firsts``), 62% in the mean."""

from benchmarks import flops_smallthinker


def read(run):
    layout = run.spec.params.get("arch", {}).get("sliding_window_layout", [])
    windowed = sum(layout)
    kept = [flops_smallthinker.counted(run.window.rows, f"attn-pairs-{kind}")
            for kind in ("window", "global")]
    if None in kept or not 0 < windowed < len(layout):
        return None
    return 100.0 * (kept[0] / windowed) / (kept[1] / (len(layout) - windowed))
