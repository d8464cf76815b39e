"""compile_backend_s: seconds of the train step's backend compile (XLA and
Mosaic), or of its load from the persistent compilation cache on a hit
(the program's `jit_backend_seconds` of `gnn_train_step`).

It leaves out what JAX does after that stage and before `compile()`
returns, which no JAX event covers: reading the executable's parameter
and output layouts back (`_get_layouts_from_executable`).  For the
blogcatalog step on a TPU v5e that read-back takes 3.5-4.0 s, and
`compile_trace_s` plus this metric fall short of `compile_s` by about
that much."""
from chipbench.lib import program


def read(r):
    return program.hist_sum(program.registry(r), "jit_backend_seconds",
                            fun=program.TRAIN_STEP)
