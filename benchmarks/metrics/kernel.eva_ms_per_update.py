"""Device time under the EVA mixers' scope (``eva``: the three projections,
the rotation, the chunk pooling, the kernels over the blocks' exact keys, the
read of the summaries and the merge of the two parts, ``o_proj``) per update,
from the trace: forward, the rematerialised second forward, and backward. It
contains what ``kernel.eva_pool_ms_per_update`` and, in this cell,
``kernel.attn_ms_per_update`` read."""

SCOPE = r"/eva/"


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scope_s(SCOPE)
    return None if seconds is None else 1e3 * seconds / run.trace.n_steps
