"""Run provenance: who/what/when produced a metrics or benchmark artifact.

`run_context()` stamps exported metrics documents and every
``BENCH_<section>.json`` (benchmarks/run.py) so the perf trajectory is
attributable across PRs: git SHA, ISO timestamp, jax version, default
backend and device kind, python/platform.  Collected once per process
(subprocess git call + device query), then cached.
"""
from __future__ import annotations

import os
import platform
import subprocess
from datetime import datetime, timezone
from typing import Optional

__all__ = ["run_context"]

_context: Optional[dict] = None


def _git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=5)
        if out.returncode == 0:
            return out.stdout.strip()
    except (OSError, subprocess.TimeoutExpired):
        pass
    return "unknown"


def run_context() -> dict:
    """Provenance dict (cached).  A failed device query raises: an
    artifact must never be stamped with a device it did not run on."""
    global _context
    if _context is None:
        import jax

        _context = {
            "git_sha": _git_sha(),
            "timestamp": datetime.now(timezone.utc).isoformat(
                timespec="seconds"),
            "python": platform.python_version(),
            "platform": platform.platform(),
            "jax": jax.__version__,
            "jax_backend": jax.default_backend(),
            "device": jax.devices()[0].device_kind,
            "num_devices": jax.device_count(),
        }
    return dict(_context)
