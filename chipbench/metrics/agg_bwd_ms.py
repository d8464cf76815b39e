"""agg_bwd_ms: device milliseconds per train step of the backward
aggregation launches: the kernel over the transposed schedule
(`group_aggregate_bwd`) and the edge-value gradient (`group_edge_grad`),
in the traced window."""
from chipbench.lib.trace import sum_by_name

KERNELS = ("group_aggregate_bwd", "group_edge_grad")


def read(r):
    win = getattr(r, "window", None)
    if win is None or not r.trace_steps:
        return None
    ns = sum_by_name(win["ops"][0], KERNELS)
    if ns <= 0:
        return None
    return ns * 1e-6 / r.trace_steps
