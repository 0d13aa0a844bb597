"""Device time of the optimizer's pass per update, from the trace: the ops
under ``opt_update`` (``algos/ppo.py``: the global-norm clip and the RMSprop
step) plus the program's conditional. With ``update_guard`` the clip's scaling,
RMSprop and the parameter update run inside the guard's ``lax.cond``, the
update program's only conditional, whose event carries no name stack; XLA also
fuses the diagnostics' norms of the new parameters and of the update into the
same pass over the weights, and names the fusion for them."""


def read(run):
    if run.trace is None:
        return None

    def optimizer(op) -> bool:
        return "opt_update" in op.scope or op.category == "conditional"

    ns = [d.covered_ns(optimizer) for d in run.trace.devices]
    if not any(ns):
        return None
    return 1e3 * (sum(ns) / len(ns) / 1e9) / run.trace.n_steps
