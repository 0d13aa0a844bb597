"""Time per update the learner's main lane spent in ``ckpt-save`` (the
device-side snapshot and the hand-over to the writer thread); 0 where no save
fell inside the traced window."""

from benchmarks import hostplane


def read(run):
    host = hostplane.of_run(run)
    if host is None:
        return None
    return host.per_update_ms(run.trace, hostplane.MAIN, names=("ckpt-save",))
