"""``attn.tiles_run_share`` for a family whose only attention layers are
global: of the tiles in the causal band of the splash kernels' grid, the share
the kernels computed over the window's updates — a tile in which no query and
key share an episode is stepped over (``parallel/sequence.seam_empty_tiles``).
The program counts both in-jit from the segment ids the kernels get (``diag``
scalars ``attn-tiles-run-global`` and ``attn-tiles-band-global``, each summed
over the full-attention layers; every ``learn.jsonl`` line carries the mean
over the updates since the last), and this is the sum of the run tiles over
the sum of the band's. 100% with no seam; episodes of mean 2,048 in windows of
8,192 steps on tiles of 1,024 (an 8 x 8 grid, 36 band tiles a row) empty most
tiles off the diagonal. A program that ships no such counter reads nothing."""

from benchmarks import flops_qwen3_next


def read(run):
    run_tiles, band = (
        flops_qwen3_next.counted(run.window.rows, f"attn-tiles-{what}-global")
        for what in ("run", "band")
    )
    if run_tiles is None or not band:
        return None
    return 100.0 * run_tiles / band
