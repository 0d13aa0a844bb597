"""Seconds from the benchmark process's start to the entry of
``LearnerService.run``: the benchmark's own part of ``setup_s`` — imports,
reaching the chip, the parity checks, ``warm_snapshots`` — and, in a cold
compile cache, nearly all of the process's compilation: the checks build and
run the model before the learner does, so the learner finds its programs in
the process's own jit cache. From the program's ``run_entry`` stamp
(``backend-learner.json``: ``startup``); beside it the runner's own split
(``setup_phases_s``: seconds since process start at which each phase ended),
which no compile clock covers."""

from benchmarks import startup


def read(run):
    s = startup.of_run(run)
    if s is None:
        return None
    phases = run.notes.get("window", {}).get("setup_phases_s")
    return s.run_entry - s.t_start, ({"phases_end_s": phases} if phases else {})
