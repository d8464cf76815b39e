"""What the program records about itself, read after the run.

The program keeps one registry per process (`repro.obs.process_tracer`):
its planner spans (``span_seconds{span=plan/...}``), the stages of each
JAX compile by function (``jit_{trace,lower,backend}_seconds{fun}``) and
the compile cache's counters.  Readers look it up here.  A program that
has no such registry gives None, and so does a metric it never recorded:
the reader then reports nothing.

Readings may carry a ``registry`` of their own (the tests' synthetic
readings); a run's readings do not, and the process's is read.
"""
from __future__ import annotations

from typing import Optional

__all__ = ["TRAIN_STEP", "registry", "hist_sum", "counter"]

# the name `make_gnn_train_step` gives the jitted train step
TRAIN_STEP = "gnn_train_step"


def registry(r):
    reg = getattr(r, "registry", None)
    if reg is not None:
        return reg
    try:
        from repro.obs import process_tracer
    except ImportError:
        return None
    return process_tracer().registry


def hist_sum(reg, name: str, **labels) -> Optional[float]:
    """Summed observations of one histogram; None if it has none."""
    h = None if reg is None else reg.get(name, labels)
    if h is None or not h.count:
        return None
    return h.sum


def counter(reg, name: str, **labels) -> Optional[float]:
    c = None if reg is None else reg.get(name, labels)
    return None if c is None else c.value
