"""On-device measurement harness: honest timings + model-residual metrics.

The analytical `KernelModel` (paper §5/§7.1) predicts; this module
*measures*.  It is the substrate the measured-autotuning loop builds on
(ROADMAP): `measure` gives calibrated, outlier-robust wall-clock samples of
a jax callable, and `profile_plan` attributes time and achieved throughput
per schedule (forward vs backward, per shard) so the achieved-vs-predicted
residual becomes a first-class metric
(``kernel_model_residual{schedule=...}``) instead of a one-off benchmark
printout.

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

__all__ = ["Measurement", "measure", "profile_plan", "ProfileReport",
           "ScheduleProfile"]


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


@dataclasses.dataclass(frozen=True)
class ScheduleProfile:
    """Measured + modeled view of ONE schedule (forward, backward, or a
    shard's forward)."""

    schedule: str
    measured: Measurement
    model_latency_s: float
    model_bytes: float
    edges: int
    tiles: int

    @property
    def residual(self) -> float:
        """measured p50 / model-predicted latency.  1.0 = the analytical
        model is calibrated for this schedule; the tuner's measured stage
        uses the residual to know when predictions can be trusted."""
        return (self.measured.p50 / self.model_latency_s
                if self.model_latency_s > 0 else float("nan"))

    @property
    def achieved_bytes_per_s(self) -> float:
        """Modeled DMA traffic moved per measured second."""
        p50 = self.measured.p50
        return self.model_bytes / p50 if p50 > 0 else float("nan")

    @property
    def achieved_edges_per_s(self) -> float:
        p50 = self.measured.p50
        return self.edges / p50 if p50 > 0 else float("nan")

    def to_row(self) -> dict:
        return {
            "schedule": self.schedule,
            "model_latency_us": self.model_latency_s * 1e6,
            "model_bytes": self.model_bytes,
            "residual": self.residual,
            "achieved_bytes_per_s": self.achieved_bytes_per_s,
            "achieved_edges_per_s": self.achieved_edges_per_s,
            **self.measured.to_row(),
        }


@dataclasses.dataclass(frozen=True)
class ProfileReport:
    """All schedules of one plan, plus the combined total for attribution."""

    schedules: tuple
    total: Measurement
    dim: int
    backend: str

    def attribution(self) -> dict:
        """Per-schedule p50 seconds.  Shard rows measure the same work the
        fwd/bwd rows cover, partitioned differently, so they are EXCLUDED
        from the sum-to-total identity (`attribution_error`)."""
        return {s.schedule: s.measured.p50 for s in self.schedules
                if "shard" not in s.schedule}

    def attribution_error(self) -> float:
        """|sum(per-schedule p50) - total p50| / total p50.  Small by
        construction (the total runs the same kernels back to back), large
        only when measurement noise swamps the kernels — the signal to
        distrust this profile."""
        total = self.total.p50
        if not total or total <= 0:
            return float("nan")
        return abs(sum(self.attribution().values()) - total) / total

    def to_rows(self) -> list:
        return [s.to_row() for s in self.schedules]


def profile_plan(plan, feat=None, *, backend: Optional[str] = None,
                 dim: Optional[int] = None, iters: int = 5,
                 warmup: Optional[int] = None, registry=None,
                 label: str = "", shards: Optional[int] = None,
                 seed: int = 0) -> ProfileReport:
    """Measure a `Plan`'s schedules and attribute time per schedule.

    Runs the forward kernel (and, when the plan carries a backward
    partition, the transposed-schedule backward kernel) under `measure`,
    prices each schedule with the analytical `KernelModel` over its EXACT
    tile count, and reports per-schedule achieved throughput plus the
    measured/predicted residual.  A combined forward+backward run gives the
    total that per-schedule attribution must sum to
    (`ProfileReport.attribution_error`).

    Arguments
    ---------
    plan : repro.core.plan.Plan (advisor/`plan_for` output).
    feat : optional (N, D) features in the plan's node order; generated
        deterministically (``seed``) at ``dim`` columns when omitted.
    backend : kernel backend ("xla" | "pallas" | "pallas_interpret");
        None resolves by platform.
    registry : optional MetricsRegistry — when given, every schedule lands
        ``kernel_model_residual{schedule=...}`` /
        ``profile_achieved_bytes_per_s{schedule=...}`` gauges and a
        ``profile_schedule_seconds{schedule=...}`` histogram fed the raw
        samples.
    label : prefix for schedule names — callers profiling one plan per
        shape bucket pass ``label=f"b{bucket}/"`` so residuals stay
        distinguishable per bucket.
    shards : additionally profile each of ``plan.shards(shards)``'s
        sub-plan forward kernels as ``shard{p}/forward`` rows (single
        device, full gathered feature operand — the kernel-side cost of
        halo-exchange execution without the collective).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.extractor import extract_graph_props
    from repro.core.model import KernelModel
    from repro.kernels.ops import resolve_backend

    backend = resolve_backend(backend)
    g = plan.graph
    if feat is None:
        d = dim if dim is not None else 64
        rng = np.random.default_rng(seed)
        feat = rng.standard_normal((g.num_nodes, d)).astype(np.float32)
    feat_j = jnp.asarray(feat, dtype=jnp.dtype(plan.config.feat_dtype))
    d = int(feat_j.shape[1])

    props = plan.graph_props
    if props is None:
        props = extract_graph_props(g, detect_communities=False)
    km = KernelModel()

    def model_terms(partition):
        return km.terms(props, d, plan.config, tiles=partition.num_tiles)

    fwd_ex = plan.executor(backend)
    fwd_fn = jax.jit(lambda x: fwd_ex(x))
    m_fwd = measure(fwd_fn, feat_j, warmup=warmup, iters=iters)
    t_fwd = model_terms(plan.partition)
    schedules = [ScheduleProfile(
        schedule=f"{label}forward", measured=m_fwd,
        model_latency_s=t_fwd["latency"], model_bytes=t_fwd["bytes"],
        edges=g.num_edges, tiles=int(plan.partition.num_tiles))]

    bwd_fn = None
    if plan.partition_bwd is not None:
        from repro.core.aggregate import PlanExecutor
        bwd_ex = PlanExecutor.from_schedule(
            plan.sched_bwd(), dt=plan.config.dt, backend=backend,
            out_dtype=plan.config.feat_dtype)
        bwd_fn = jax.jit(lambda x: bwd_ex(x))
        ct = jnp.ones_like(feat_j)
        m_bwd = measure(bwd_fn, ct, warmup=warmup, iters=iters)
        t_bwd = model_terms(plan.partition_bwd)
        schedules.append(ScheduleProfile(
            schedule=f"{label}backward", measured=m_bwd,
            model_latency_s=t_bwd["latency"], model_bytes=t_bwd["bytes"],
            edges=g.num_edges, tiles=int(plan.partition_bwd.num_tiles)))

    # total: the SAME jitted callables back to back inside one timed call,
    # so its dispatch structure matches the per-schedule rows and the
    # attribution identity holds up to noise, not up to fusion luck
    if bwd_fn is not None:
        def total_call(x):
            return _block(bwd_fn(_block(fwd_fn(x))))
    else:
        def total_call(x):
            return _block(fwd_fn(x))
    m_total = measure(total_call, feat_j, warmup=warmup, iters=iters)

    if shards:
        sub_plans = plan.shards(shards)
        for p_idx, sub in enumerate(sub_plans.plans):
            sub_ex = sub.executor(backend)
            sub_fn = jax.jit(lambda x, _ex=sub_ex: _ex(x))
            m_sub = measure(sub_fn, feat_j, warmup=warmup, iters=iters)
            t_sub = model_terms(sub.partition)
            edges = int(sub_plans.edge_ranges[p_idx][1]
                        - sub_plans.edge_ranges[p_idx][0]) \
                if hasattr(sub_plans, "edge_ranges") else sub.graph.num_edges
            schedules.append(ScheduleProfile(
                schedule=f"{label}shard{p_idx}/forward", measured=m_sub,
                model_latency_s=t_sub["latency"], model_bytes=t_sub["bytes"],
                edges=edges, tiles=int(sub.partition.num_tiles)))

    report = ProfileReport(schedules=tuple(schedules), total=m_total,
                           dim=d, backend=backend)
    if registry is not None:
        for s in schedules:
            lbl = {"schedule": s.schedule}
            registry.gauge(
                "kernel_model_residual", labels=lbl,
                desc="measured p50 / KernelModel-predicted latency",
            ).set(s.residual)
            registry.gauge(
                "profile_achieved_bytes_per_s", labels=lbl,
                desc="modeled DMA bytes moved per measured second",
            ).set(s.achieved_bytes_per_s)
            registry.gauge(
                "profile_achieved_edges_per_s", labels=lbl,
                desc="edges aggregated per measured second",
            ).set(s.achieved_edges_per_s)
            h = registry.histogram(
                "profile_schedule_seconds", labels=lbl,
                desc="measured per-call wall time (repro.obs.profile)")
            for x in s.measured.samples:
                h.observe(x)
    return report
