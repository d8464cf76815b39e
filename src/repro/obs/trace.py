"""Span tracing: nested wall-clock timing that lands in the metrics registry.

    trace = SpanTracer(registry)
    with trace.span("plan_build"):
        plan = plan_for(g, ...)

Every closed span records its duration into the histogram
``span_seconds{span="<path>"}`` in the tracer's registry and appends a
bounded ring-buffer record (for the JSON exporter's ``spans`` section).
Spans opened inside an active span on the same thread get a "/"-joined
path (``serve/plan_build``), so the naming convention in
docs/observability.md falls out of call structure instead of discipline.

**Async-dispatch caveat** (the reason this exists as a class and not three
lines of `perf_counter`): jax dispatch returns before device compute
finishes, so a naive span around a jitted call times the *enqueue*, not
the work.  Pass the computation's output through ``span.sync(out)`` — at
span close the tracer calls ``jax.block_until_ready`` on it (lazily
imported; a no-op when jax is absent), so the recorded duration covers the
device work.  ``SpanTracer(block_until_ready=True)`` makes that the
default for every span that registered a sync value; ``span(...,
block=False)`` opts a single span out.

**On the profiler's clock**: each span also enters
``jax.profiler.TraceAnnotation("repro/<path>")`` (when jax is importable),
so while a profiler session is active the span lands on the trace's
``/host:CPU`` plane beside the device ops, on the same clock, and a device
gap can be put down to what the host was doing in it.

**The process tracer**: `process_tracer()` is one tracer over one
registry per process.  Code that is handed no tracer (the planner, the
compile listener of `repro.obs.compile`) records there; components that
are handed one (the launch drivers, `Trainer`, `ServingEngine`) keep
using theirs.
"""
from __future__ import annotations

import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Any, Optional

from repro.obs.metrics import MetricsRegistry

__all__ = ["Span", "SpanTracer", "process_tracer"]

ANNOTATION_PREFIX = "repro/"


def _annotation(path: str):
    """The span's profiler annotation.  A no-op until something else has
    imported jax: no profiler session can be open before then, and the
    registry stays dependency-free."""
    jax = sys.modules.get("jax")
    if jax is None:
        return nullcontext()
    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + path)


class Span:
    """One open span.  ``sync(x)`` registers device values to block on at
    close (and returns ``x``, so it wraps call sites inline); ``note()``
    attaches key=value attributes to the exported record."""

    __slots__ = ("path", "t_start", "duration_s", "attrs", "_sync")

    def __init__(self, path: str, t_start: float, attrs: dict):
        self.path = path
        self.t_start = t_start
        self.duration_s: Optional[float] = None
        self.attrs = attrs
        self._sync: Any = None

    def sync(self, value):
        self._sync = value
        return value

    def note(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self


class SpanTracer:
    """Factory for `Span` contexts bound to one `MetricsRegistry`.

    Arguments
    ---------
    registry : the sink; span durations become
        ``span_seconds{span=path}`` histograms there.
    block_until_ready : default for the per-span ``block`` flag — when
        True, spans that registered a ``sync`` value block on it before
        taking the end timestamp (honest jax timings).
    max_spans : ring-buffer bound on retained span records (the JSON
        exporter's trace section); older records are dropped, histograms
        keep counting.
    """

    def __init__(self, registry: MetricsRegistry, *,
                 block_until_ready: bool = False, max_spans: int = 256):
        self.registry = registry
        self.block_until_ready = block_until_ready
        self._records: deque = deque(maxlen=max_spans)
        self._local = threading.local()
        self._t0 = time.perf_counter()
        # the same epoch on the wall clock, for records timed elsewhere
        # (`add_record`: JAX's compile events carry time.time() stamps)
        self._t0_wall = time.time()
        # compact per-tracer thread ids: the Chrome-trace exporter wants
        # small stable track numbers, not 64-bit thread idents
        self._tids: dict = {}
        self._tid_lock = threading.Lock()

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._tid_lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = self._tids[ident] = len(self._tids)
            return tid

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    @contextmanager
    def span(self, name: str, *, block: Optional[bool] = None, **attrs):
        stack = self._stack()
        path = "/".join([s.path for s in stack[-1:]] + [name])
        with _annotation(path):
            sp = Span(path, time.perf_counter(), attrs)
            stack.append(sp)
            try:
                yield sp
            finally:
                stack.pop()
                if (self.block_until_ready if block is None else block) \
                        and sp._sync is not None:
                    try:
                        import jax
                        jax.block_until_ready(sp._sync)
                    except ImportError:    # registry stays dependency-free
                        pass
                sp.duration_s = time.perf_counter() - sp.t_start
                self.registry.histogram(
                    "span_seconds", labels={"span": path},
                    desc="wall-clock span durations (repro.obs.trace)",
                ).observe(sp.duration_s)
                self._append(path, sp.t_start - self._t0, sp.duration_s,
                             sp.attrs)

    def add_record(self, path: str, start_s: float, end_s: float,
                   **attrs) -> None:
        """Append a ring-buffer record for work timed elsewhere, from its
        wall-clock (``time.time()``) start and end; no histogram."""
        self._append(path, start_s - self._t0_wall, end_s - start_s, attrs)

    def _append(self, path: str, t_rel_s: float, duration_s: float,
                attrs: dict) -> None:
        # tid + thread name ride in every record: the Chrome-trace
        # exporter needs a per-thread track, the JSON exporter's
        # ``spans`` section gets attributable multi-thread traces
        self._records.append({
            "span": path,
            "t_rel_s": round(t_rel_s, 6),
            "duration_s": round(duration_s, 6),
            "tid": self._tid(),
            "thread": threading.current_thread().name,
            **({"attrs": dict(attrs)} if attrs else {}),
        })

    def records(self) -> list:
        """Retained span records, oldest first (bounded by max_spans)."""
        return list(self._records)


_PROCESS: Optional[SpanTracer] = None
_PROCESS_LOCK = threading.Lock()


def process_tracer() -> SpanTracer:
    """The process-wide tracer over the process-wide registry, made on
    first use.  Its ring buffer is larger than a component's: set-up
    (planning, one record per JAX compile stage) fills it in bursts."""
    global _PROCESS
    with _PROCESS_LOCK:
        if _PROCESS is None:
            _PROCESS = SpanTracer(MetricsRegistry(), max_spans=4096)
        return _PROCESS
