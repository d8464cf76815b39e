"""non_agg_ms: device milliseconds per train step of every operation
outside the aggregation kernels (`group_aggregate*`, `group_edge_grad*`)
in the traced window: projections, scores, softmax, skip, loss and the
optimiser."""
from chipbench.lib.trace import sum_by_name

KERNELS = ("group_aggregate", "group_edge_grad")


def read(r):
    win = getattr(r, "window", None)
    if win is None or not r.trace_steps:
        return None
    ops = win["ops"][0]
    ns = sum(iv.end - iv.start for iv in ops) - sum_by_name(ops, KERNELS)
    if ns <= 0:
        return None
    return ns * 1e-6 / r.trace_steps
