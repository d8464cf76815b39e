"""plan_partition_s: seconds of the program's `plan/partition` span in
`build_gnn`: the forward partition, the transposed graph and the backward
partition."""
from chipbench.lib import program


def read(r):
    return program.hist_sum(program.registry(r), "span_seconds",
                            span="plan/partition")
