"""gen_late_ms.serve: the 95th percentile of how late the load generator
submitted each request after it was due."""
from chipbench.lib.serve import percentile


def read(r):
    s = getattr(r, "serve", None)
    if not s or not len(s["gen_late_s"]):
        return None
    return percentile(s["gen_late_s"], 95) * 1e3
