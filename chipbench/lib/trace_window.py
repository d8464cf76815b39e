"""The traced window: the profiler on around the window and the harness's
own host spans, named ``chipbench/<name>``, around each call into the
program.  Off (``--trace 0``) it does nothing."""
from __future__ import annotations

import contextlib
import shutil
import tempfile

from . import trace as trace_lib

__all__ = ["TraceWindow"]


class TraceWindow:
    def __init__(self, enabled: bool):
        self.enabled = bool(enabled)
        self.data = None
        self._dir = None

    def __enter__(self):
        if self.enabled:
            import jax

            self._dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        if not self.enabled:
            return
        import jax

        try:
            jax.profiler.stop_trace()
            if exc[0] is None:
                self.data = trace_lib.load(trace_lib.latest_xplane(self._dir))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"chipbench/{name}")
