"""plan_analyze_s: seconds of the program's `plan/analyze` span in
`build_gnn`: GCN's edge values, the graph's properties, the renumbering
decision and any renumbering."""
from chipbench.lib import program


def read(r):
    return program.hist_sum(program.registry(r), "span_seconds",
                            span="plan/analyze")
