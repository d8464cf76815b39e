"""edge_grad_roofline: the edge-value cotangent kernel's share of its
roofline in a train step: the least time of the step's edge-cotangent
passes (the architecture's `train_edge_grad_passes`, each the larger of
its compulsory FLOPs over the peak FLOP/s and its compulsory bytes over
the peak bandwidth, counted on real edges and nodes) over the kernel's
device time per step (`group_edge_grad`).  An architecture without edge
values to differentiate has nothing to read."""
from chipbench.lib import arch, counts
from chipbench.lib.peaks import peak_for
from chipbench.lib.trace import sum_by_name

KERNELS = ("group_edge_grad",)


def read(r):
    win = getattr(r, "window", None)
    if win is None or not r.trace_steps:
        return None
    model = r.cell.config["model"]
    passes_of = getattr(arch.load(model["arch"]), "train_edge_grad_passes",
                        None)
    ns = sum_by_name(win["ops"][0], KERNELS)
    if passes_of is None or ns <= 0:
        return None
    peak = peak_for(r.device["kind"])
    g = r.cell.config["graph"]
    least = sum(counts.agg_least_s(p, peak.flops, peak.hbm_bw)
                for p in passes_of(model, r.nodes, r.edges, g["feat_dim"],
                                   g["num_classes"]))
    return 100.0 * least / (ns * 1e-9 / r.trace_steps)
