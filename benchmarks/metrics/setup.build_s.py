"""Seconds the learner spent building what its loop runs on: the spans
``family``, ``train-state`` (the eager ``init_params`` and the optimizer's
``init``), ``step-build``, ``restore`` and ``place`` of its lane ``startup``.
Beside it the whole lane (``run_entry`` to ``loop_entry``) and every site:
``setup_s`` = ``setup.before_program_s`` + the lane + ``setup.warmup_s``."""

from benchmarks import startup


def read(run):
    s = startup.of_run(run)
    if s is None:
        return None
    sites = {span[1]: round(span[3], 3) for span in s.lane(startup.STARTUP)}
    return s.site_s(startup.BUILD_SITES), {
        "startup_lane_s": s.loop_entry - s.run_entry, "sites": sites,
    }
