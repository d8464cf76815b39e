"""JAX's compile stages as program spans and counters.

JAX reports each stage of building an executable through `jax.monitoring`:
tracing the Python function to a jaxpr, lowering the jaxpr to an MLIR
module, and the backend compile (XLA and Mosaic, or a load from the
persistent compilation cache).  `install_compile_listener` registers one
listener per process that turns them into metrics of the process tracer's
registry, labelled by the function's name (``jit(f)`` -> ``f``):

* ``jit_trace_seconds{fun}``, ``jit_lower_seconds{fun}``,
  ``jit_backend_seconds{fun}``: one observation per stage, and a tracer
  record ``jit/<stage>`` with the stage's own start and end;
* ``jit_compiles_total{fun}``: executables built (compiled or loaded);
* ``compile_cache_hits_total{fun}``, ``compile_cache_misses_total{fun}``:
  persistent-cache lookups.  JAX's cache events carry no function name, so
  each is put down to the backend compile open on that thread (JAX records
  a scalar naming the function when that stage starts).

Tracing nests: a jitted function called inside another is traced inside
the outer trace and gets its own record, so the stages of one label never
double count, but labels do not add up to a whole.

A step that recompiles shows as a second ``jit_compiles_total`` of its
label.  The listener costs nothing on a call that builds nothing: JAX
emits these events only while it traces, lowers or compiles.
"""
from __future__ import annotations

import threading
from typing import Optional

from repro.obs.trace import SpanTracer, process_tracer

__all__ = ["CompileListener", "install_compile_listener", "fun_label"]

STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE = {
    "/jax/compilation_cache/cache_hits": "compile_cache_hits_total",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses_total",
}
UNKNOWN = "unknown"


def fun_label(name) -> str:
    """The function's own name from JAX's stage names: the trace stage
    names ``f``, lowering and compile name ``jit(f)`` (``pmap(f)``...), so
    one outer transform is taken off."""
    name = str(name) if name is not None else UNKNOWN
    head, sep, rest = name.partition("(")
    if sep and rest.endswith(")") and head.isidentifier():
        return rest[:-1]
    return name


class CompileListener:
    """The `jax.monitoring` callbacks, bound to one tracer and its
    registry."""

    def __init__(self, tracer: SpanTracer):
        self.tracer = tracer
        self.registry = tracer.registry
        self._local = threading.local()

    def _open(self) -> list:
        st = getattr(self._local, "backend", None)
        if st is None:
            st = self._local.backend = []
        return st

    def on_scalar(self, event: str, value, **kw) -> None:
        if event != _BACKEND:
            return
        fun = fun_label(kw.get("fun_name"))
        self._open().append(fun)
        for name in _CACHE.values():     # a compile reads 0 hits, not none
            self.registry.counter(name, labels={"fun": fun},
                                  desc="persistent compilation cache "
                                       "lookups (repro.obs.compile)")

    def on_span(self, event: str, start: float, end: float, **kw) -> None:
        stage = STAGES.get(event)
        if stage is None:
            return
        fun = fun_label(kw.get("fun_name"))
        self.registry.histogram(
            f"jit_{stage}_seconds", labels={"fun": fun},
            desc=f"JAX {stage} stage per function (repro.obs.compile)",
        ).observe(end - start)
        self.tracer.add_record(f"jit/{stage}", start, end, fun=fun)
        if event == _BACKEND:
            open_ = self._open()
            if open_:
                open_.pop()
            self.registry.counter(
                "jit_compiles_total", labels={"fun": fun},
                desc="executables built, compiled or loaded from the "
                     "persistent cache (repro.obs.compile)").inc()

    def on_event(self, event: str, **kw) -> None:
        name = _CACHE.get(event)
        if name is None:
            return
        open_ = self._open()
        self.registry.counter(
            name, labels={"fun": open_[-1] if open_ else UNKNOWN}).inc()


_INSTALLED: Optional[CompileListener] = None
_INSTALL_LOCK = threading.Lock()


def install_compile_listener() -> CompileListener:
    """Register the listener on `jax.monitoring` once per process, over
    `process_tracer()`; later calls return the same listener."""
    global _INSTALLED
    with _INSTALL_LOCK:
        if _INSTALLED is None:
            from jax import monitoring

            lst = CompileListener(process_tracer())
            monitoring.register_scalar_listener(lst.on_scalar)
            monitoring.register_event_time_span_listener(lst.on_span)
            monitoring.register_event_listener(lst.on_event)
            _INSTALLED = lst
        return _INSTALLED
