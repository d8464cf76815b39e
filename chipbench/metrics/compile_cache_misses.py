"""compile_cache_misses: persistent compilation cache lookups of the train
step that missed, so compiled anew (the program's
`compile_cache_misses_total` of `gnn_train_step`; 0 on a hit)."""
from chipbench.lib import program


def read(r):
    return program.counter(program.registry(r), "compile_cache_misses_total",
                           fun=program.TRAIN_STEP)
