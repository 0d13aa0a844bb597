"""Share of wall time the learner's loop spent blocked on its feed: the mean
``learner-queue-wait-time`` of the program's own timer (its last 100
dispatches; a host clock around a blocking queue pop, which is what it
claims to be) times the dispatches per second of the window. Polls that found
the feed empty for 50 ms are not in that timer, so a starving learner reads
low here and shows in ``device.idle_share`` instead."""


def read(run):
    wait = run.timers.get("learner-queue-wait-time")
    if wait is None:
        return None
    chain = max(1, run.spec.params.get("learner_chain", 1))
    return 100.0 * wait * run.updates_per_s / chain
