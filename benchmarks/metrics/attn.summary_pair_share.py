"""Of the pairs an EVA layer's queries read over the window's updates, the
share that are summaries: the program counts both kinds in-jit from ``is_fir``
(``diag`` scalars ``attn-pairs-summary`` — ``sum_t (W / C) b(t)`` — and
``attn-pairs-block`` — ``sum_t (p(t) mod W) + 1`` —, each summed over the
layers; every ``learn.jsonl`` line carries the mean over the updates since the
last), and this is the one sum over the two. 30.4% with no seam at 16,384
steps, blocks of 2,048 and chunks of 16 (448 of 1,472.5 a query); every seam
restarts the grid, so the closer to 0, the less of what a query reads is
compressed on this traffic. A program that ships no such counter reads
nothing."""

from benchmarks import flops_evabyte


def read(run):
    pairs = flops_evabyte.counted_pairs(run.window.rows)
    if pairs is None or not sum(pairs):
        return None
    return 100.0 * pairs[1] / sum(pairs)
