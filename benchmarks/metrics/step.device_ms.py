"""Device time of one execution of the update program (the module with the
most device time in the trace), median over its executions and chips."""


def read(run):
    return run.trace.step_device_ms if run.trace is not None else None
