"""train_mfu: the whole train step's share of the chip's peak: model
FLOPs per step (dense matmuls and aggregation multiply-adds on real
edges, forward and backward) times steps per second of the traced
window, over the published bf16 peak of the chips used."""
from chipbench.lib import counts
from chipbench.lib.peaks import peak_for


def read(r):
    steps, window_s = r.host.get("steps"), r.host.get("window_s")
    if not steps or not window_s:
        return None
    peak = peak_for(r.device["kind"])
    g = r.cell.config["graph"]
    flops = counts.train_step_flops(r.cell.config["model"], r.nodes,
                                    r.edges, g["feat_dim"], g["num_classes"])
    return 100.0 * flops * steps / window_s / (r.cell.chips * peak.flops)
