"""Wall-clock timing harness: `measure` gives calibrated, outlier-robust
samples of a callable (the `benchmarks/` rows and their baselines).

Kernel time per schedule is not measured here: host wall time around
isolated calls is the wrong source for it.  The device trace is
(`group_aggregate_fwd` / `group_aggregate_bwd` launches, see
docs/observability.md).

Honesty rules (the same ones docs/observability.md states for spans):

  * every timed call is closed with ``jax.block_until_ready`` on its
    output, so samples cover device compute, not dispatch;
  * warmup is CALIBRATED by default: iterations run until two consecutive
    times agree within ``stable_rel`` (or ``max_warmup`` is hit), which
    absorbs jit compilation and first-touch paging without hardcoding a
    warmup count that is wrong on every backend;
  * the reported center is an outlier-robust trimmed mean plus p50/p90/min
    — never a lone sample.

Module-top imports are stdlib-only (the `repro.obs` package stays
dependency-free); jax/numpy are imported lazily inside the functions that
need them.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Optional, Sequence

__all__ = ["Measurement", "measure"]


def _quantile(sorted_xs: Sequence[float], q: float) -> float:
    """Linear-interpolation quantile of pre-sorted samples (numpy's default
    method, so `p50` of the harness == `np.median` of the same samples)."""
    n = len(sorted_xs)
    if n == 0:
        return float("nan")
    if n == 1:
        return float(sorted_xs[0])
    pos = q * (n - 1)
    i = int(math.floor(pos))
    if i + 1 >= n:
        return float(sorted_xs[-1])
    frac = pos - i
    return float(sorted_xs[i] + frac * (sorted_xs[i + 1] - sorted_xs[i]))


def _block(out):
    """block_until_ready when jax is importable; no-op otherwise (keeps the
    harness usable on plain-python callables and in jax-free tests)."""
    try:
        import jax
    except ImportError:
        return out
    return jax.block_until_ready(out)


@dataclasses.dataclass(frozen=True)
class Measurement:
    """Post-warmup wall-clock samples (seconds) of one callable."""

    samples: tuple
    warmup: int          # warmup iterations actually run (calibration incl.)

    @property
    def count(self) -> int:
        return len(self.samples)

    @property
    def mean(self) -> float:
        return (sum(self.samples) / len(self.samples)
                if self.samples else float("nan"))

    @property
    def trimmed_mean(self) -> float:
        """Mean with the top and bottom 20% of samples dropped (at least
        one from each side once there are >= 5 samples) — the harness's
        outlier-robust center."""
        xs = sorted(self.samples)
        k = int(len(xs) * 0.2)
        core = xs[k:len(xs) - k] if len(xs) - 2 * k >= 1 else xs
        return sum(core) / len(core) if core else float("nan")

    @property
    def p50(self) -> float:
        return _quantile(sorted(self.samples), 0.50)

    @property
    def p90(self) -> float:
        return _quantile(sorted(self.samples), 0.90)

    @property
    def min(self) -> float:
        return min(self.samples) if self.samples else float("nan")

    @property
    def max(self) -> float:
        return max(self.samples) if self.samples else float("nan")

    @property
    def spread_rel(self) -> float:
        """(p90 - p50) / p50 — the run's own noise estimate, which the
        baseline comparator turns into a per-row tolerance."""
        p50 = self.p50
        return (self.p90 - p50) / p50 if p50 > 0 else float("nan")

    def to_row(self) -> dict:
        """Microsecond-scaled fields merged into benchmark rows
        (`benchmarks.common.emit(..., stats=m)`), which is how recorded
        p50/p90 spread reaches the persisted baselines."""
        return {
            "p50_us": self.p50 * 1e6,
            "p90_us": self.p90 * 1e6,
            "min_us": self.min * 1e6,
            "mean_us": self.trimmed_mean * 1e6,
            "iters": self.count,
        }


def measure(fn: Callable, *args, warmup: Optional[int] = None,
            iters: int = 5, max_warmup: int = 8,
            stable_rel: float = 0.25) -> Measurement:
    """Measure ``fn(*args)`` with block-until-ready-honest timing.

    ``warmup=None`` (default) calibrates: warmup iterations run until two
    consecutive times agree within ``stable_rel`` relative difference
    (minimum 2, maximum ``max_warmup``), which absorbs jit compilation no
    matter how long it takes.  Pass an int to pin the warmup count (the
    benchmarks do, for run-to-run comparability).  Then ``iters`` timed
    samples are taken; each sample covers one full call including device
    compute.
    """
    if iters < 1:
        raise ValueError(f"iters must be >= 1, got {iters}")
    ran = 0
    if warmup is None:
        prev = None
        while ran < max_warmup:
            t0 = time.perf_counter()
            _block(fn(*args))
            dt = time.perf_counter() - t0
            ran += 1
            if (ran >= 2 and prev is not None and prev > 0
                    and abs(dt - prev) <= stable_rel * max(dt, prev)):
                break
            prev = dt
    else:
        for _ in range(warmup):
            _block(fn(*args))
        ran = warmup
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _block(fn(*args))
        samples.append(time.perf_counter() - t0)
    return Measurement(samples=tuple(samples), warmup=ran)
