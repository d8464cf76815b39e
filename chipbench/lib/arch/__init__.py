"""The architectures the benchmark knows, one module each, found by the
configuration's ``model.arch``: ``lib/arch/<arch>.py``.

An architecture module gives

* ``param_shapes(model, in_dim, num_classes)``: ``{name: shape}``;
* ``graph_arrays(indptr, indices)``: its aggregation graph, a
  ``reference.Graph`` built from the original CSR;
* ``logits(params, feat, g, model, num)``: the plain reference forward
  under a ``reference.Numerics``;
* ``program_config(model, graph, dtype)``: the keyword arguments the train
  and serve loops hand to the program's ``GNNConfig``.  It passes every
  model key the reference reads, so a program that lacks one fails at
  ``GNNConfig(...)`` in set-up and never runs something else;
* ``train_agg_passes(model, nodes, edges, in_dim, num_classes)`` and
  ``dense_train_flops(model, nodes, in_dim, num_classes)``: the work of one
  full-graph training step, as ``lib/counts.py`` counts it.

The configuration's ``graph.labels`` names the task, whatever the
architecture: ``"single"`` (the default: one class a node, softmax
cross-entropy) or ``"multi"`` (``(N, C)`` 0/1 targets, sigmoid binary
cross-entropy).
"""
from __future__ import annotations

import importlib
from types import ModuleType

__all__ = ["load", "TASKS", "task", "common_config"]

# what each task adds to the program's configuration
TASKS = {"single": {}, "multi": {"multi_label": True}}


def load(name: str) -> ModuleType:
    """The module ``lib/arch/<name>.py``; LookupError naming it where there
    is none."""
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise LookupError(f"no architecture module chipbench/lib/arch/"
                          f"{name}.py for arch {name!r}") from None


def task(graph: dict) -> str:
    """The configuration's task; ValueError for one the benchmark lacks."""
    name = graph.get("labels", "single")
    if name not in TASKS:
        raise ValueError(f"unknown labels {name!r}; known: {sorted(TASKS)}")
    return name


def common_config(graph: dict, dtype: str) -> dict:
    """The ``GNNConfig`` arguments every architecture passes: widths of the
    input and output, the feature dtype, and the task's."""
    return {"in_dim": graph["feat_dim"], "num_classes": graph["num_classes"],
            "feat_dtype": dtype, **TASKS[task(graph)]}
