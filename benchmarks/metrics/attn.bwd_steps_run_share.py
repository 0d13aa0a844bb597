"""Of the grid steps the attention backward takes, the share that compute a
tile, over the window's updates. The program counts both in-jit from the
segment ids the kernels get (``diag`` scalars ``attn-tiles-run-{global,window}``
— the band's tiles no seam emptied — and ``attn-bwd-steps-{global,window}`` —
the steps of the backward's grid a head, ``parallel/sequence.attention_tiles``
— each summed over the rows and the layers of its kind; every ``learn.jsonl``
line carries the mean over the updates since the last), and this is the sum
of the run tiles over the sum of the steps, over the kinds of layer the family
has (a family of global layers alone ships no ``-window`` counter). The
library's fused backward steps over the whole (T / tile)^2 rectangle whatever
the mask keeps: 136 of 256 steps a head in a causal band at 16 x 16 tiles, 70
of 256 in a 4,096-key window, fewer still with seams (29% in smallthinker's
cell, 33% in glm's, by their tile counters at PR 39). A backward whose grid is
the band reads the cell's tiles-run share. A program that ships no such
counter (the parent's) reads nothing."""

from benchmarks import flops_smallthinker


def read(run):
    kinds = [
        [flops_smallthinker.counted(run.window.rows, f"attn-{what}-{kind}")
         for what in ("tiles-run", "bwd-steps")]
        for kind in ("global", "window")
    ]
    kinds = [(tiles, steps) for tiles, steps in kinds if tiles is not None and steps is not None]
    steps = sum(s for _, s in kinds)
    if not steps:
        return None
    return 100.0 * sum(t for t, _ in kinds) / steps
