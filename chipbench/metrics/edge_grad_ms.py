"""edge_grad_ms: device milliseconds per train step of the edge-value
cotangent kernel (`group_edge_grad`) in the traced window: attention's
per-edge, per-head gradient."""
from chipbench.lib.trace import sum_by_name

KERNELS = ("group_edge_grad",)


def read(r):
    win = getattr(r, "window", None)
    if win is None or not r.trace_steps:
        return None
    ns = sum_by_name(win["ops"][0], KERNELS)
    if ns <= 0:
        return None
    return ns * 1e-6 / r.trace_steps
