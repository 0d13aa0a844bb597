"""Process start to the first measured second: imports, reaching the chip,
weights, the parity check, compilation (or its cache), warm-up updates."""


def read(run):
    return run.window.start.mono - run.spec.t_start
