"""agg_roofline: the aggregation kernels' share of their roofline in a
train step: the least time of the step's aggregation passes, each the
larger of its compulsory FLOPs over the peak FLOP/s and its compulsory
bytes over the peak bandwidth (counted on real edges and nodes), over the
kernels' device time per step."""
from chipbench.lib import counts
from chipbench.lib.peaks import peak_for
from chipbench.lib.trace import sum_by_name

KERNELS = ("group_aggregate", "group_edge_grad")


def read(r):
    win = getattr(r, "window", None)
    if win is None or not r.trace_steps:
        return None
    ns = sum_by_name(win["ops"][0], KERNELS)
    if ns <= 0:
        return None
    peak = peak_for(r.device["kind"])
    g = r.cell.config["graph"]
    passes = counts.train_agg_passes(r.cell.config["model"], r.nodes,
                                     r.edges, g["feat_dim"],
                                     g["num_classes"])
    least = sum(counts.agg_least_s(p, peak.flops, peak.hbm_bw)
                for p in passes)
    return 100.0 * least / (ns * 1e-9 / r.trace_steps)
