"""plan_executor_s: seconds of the program's `plan/executor` span in
`build_gnn`: the device schedule's upload, synced, and parameter init."""
from chipbench.lib import program


def read(r):
    return program.hist_sum(program.registry(r), "span_seconds",
                            span="plan/executor")
