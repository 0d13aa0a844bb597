"""Of the tiles in the static band of the splash kernels' grid (causal, and
inside the window for a window layer), the share the kernels computed over
the window's updates: a tile in which no query and key share an episode is
stepped over (``parallel/sequence.seam_empty_tiles``). The program counts both
in-jit from the segment ids the kernels get (``diag`` scalars
``attn-tiles-run-{global,window}`` and ``attn-tiles-band-{global,window}``,
each summed over the layers of its kind; every ``learn.jsonl`` line carries
the mean over the updates since the last), and this is the sum of the run
tiles over the sum of the band's: weighted by tiles, as the kernels' work is.
100% with no seam, or where the grid is too small for the kernels to read the
seams; the lower, the more of the causal triangle the seams empty. Episodes of
mean 8,192 in windows of 16,384 steps on tiles of 1,024 read 79.7% in the
mean over seeds (by simulation of ``traffic.firsts``; a pool of 16 windows
moves it a few points). A program that ships no such counter reads nothing."""

from benchmarks import flops_smallthinker


def read(run):
    run_tiles, band = (
        [flops_smallthinker.counted(run.window.rows, f"attn-tiles-{what}-{kind}")
         for kind in ("global", "window")]
        for what in ("run", "band")
    )
    if None in run_tiles + band or not sum(band):
        return None
    return 100.0 * sum(run_tiles) / sum(band)
