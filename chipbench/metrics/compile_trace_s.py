"""compile_trace_s: seconds JAX spent tracing the train step to a jaxpr
and lowering it to an MLIR module (the program's `jit_trace_seconds` and
`jit_lower_seconds` of `gnn_train_step`)."""
from chipbench.lib import program


def read(r):
    reg = program.registry(r)
    parts = [program.hist_sum(reg, f"jit_{stage}_seconds",
                              fun=program.TRAIN_STEP)
             for stage in ("trace", "lower")]
    if all(p is None for p in parts):
        return None
    return sum(p for p in parts if p is not None)
