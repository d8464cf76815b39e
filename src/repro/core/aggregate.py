"""High-level aggregation API used by GNN layers.

Bridges a `Plan` (advisor output) to executable JAX functions.
When the plan carries a backward partition (`plan_for(with_backward=True)`),
every call is differentiable on every backend: the Pallas kernel's custom
VJP re-aggregates the output cotangent over the transposed schedule (see
`repro.kernels.ops`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import Plan
from repro.kernels.ops import (DeviceSchedule, aggregate as _kernel_aggregate,
                               resolve_backend)

__all__ = ["PlanExecutor"]


class PlanExecutor:
    """Executable aggregation bound to one plan (device-resident schedule)."""

    def __init__(self, plan: Plan, *, backend: str | None = None):
        self.plan = plan
        self.sched = plan.sched()
        self.sched_bwd = plan.sched_bwd()
        self.backend = resolve_backend(backend)
        self.dt = plan.config.dt
        # outputs follow the plan's dtype policy (f32 accumulation inside;
        # see the dtype rules in repro.kernels.ops)
        self.out_dtype = jnp.dtype(plan.config.feat_dtype)
        # cache the inverse node permutation once — aggregate_original_order
        # used to argsort on every call.
        self._perm = None if plan.perm is None else jnp.asarray(plan.perm)
        self._inv_perm = (None if plan.perm is None else
                          jnp.asarray(np.argsort(plan.perm)))

    @classmethod
    def from_schedule(cls, sched: DeviceSchedule, *, dt: int,
                      backend: str | None = None,
                      sched_bwd: DeviceSchedule = None,
                      out_dtype="float32") -> "PlanExecutor":
        """Plan-less executor over a bare schedule.

        Shared jitted functions (the serving engine's forwards, the sampled
        trainer's per-bucket step executables) rebuild one per trace from
        traced arrays, so the compiled executable closes over nothing
        entry-specific.

        Arguments
        ---------
        sched : DeviceSchedule (or any duck-typed view exposing the same
            array members + static ints).  Arrays may be jax tracers.
        dt : int — dim-tile width handed to the kernel (clamped to the
            feature width at call time).
        backend : see `repro.kernels.ops` Backend dispatch rules; None
            resolves by platform.
        sched_bwd : optional TRANSPOSED-graph schedule (same duck typing);
            when given the executor is differentiable on every backend —
            the sampled mini-batch trainer passes one per layer block.
        out_dtype : dtype (name) of the executor's outputs — the plan's
            ``AggConfig.feat_dtype`` policy; accumulation is f32 always.

        Without ``sched_bwd`` the result is forward-only (exactly what
        serving needs).  Example:

        >>> ex = PlanExecutor.from_schedule(sched, dt=128)
        >>> out = ex(feat)                       # (N, D) float32
        """
        ex = cls.__new__(cls)
        ex.plan = None
        ex.sched = sched
        ex.sched_bwd = sched_bwd
        ex.backend = resolve_backend(backend)
        ex.dt = dt
        ex.out_dtype = jnp.dtype(out_dtype)
        ex._perm = ex._inv_perm = None
        return ex

    def __call__(self, feat: jax.Array) -> jax.Array:
        """feat: (N, D) in the plan's (renumbered) node order -> (N, D) in
        the plan's ``feat_dtype`` (f32 unless a bf16 policy is active)."""
        return _kernel_aggregate(feat, self.sched, dt=self.dt,
                                 backend=self.backend,
                                 sched_bwd=self.sched_bwd,
                                 out_dtype=self.out_dtype)

    def aggregate_edges(self, feat: jax.Array,
                        edge_values: jax.Array) -> jax.Array:
        """Aggregation with DYNAMIC per-edge weights, (E,) or (E, H) with
        a value per head (original CSR edge order of the plan's graph) —
        the GAT-type path: the schedule is reused, only the edge-value
        tensor is laid out anew per forward (a gather through the
        schedule's ``slot_edge``).  With a backward schedule, gradients
        flow to BOTH ``feat`` (via the transposed kernel) and
        ``edge_values`` (per-edge, per-head gather-dot)."""
        return _kernel_aggregate(feat, self.sched, dt=self.dt,
                                 backend=self.backend,
                                 edge_values=edge_values,
                                 sched_bwd=self.sched_bwd,
                                 out_dtype=self.out_dtype)

    def aggregate_original_order(self, feat_original: jax.Array) -> jax.Array:
        """Convenience: accepts/returns arrays in the ORIGINAL node order."""
        plan = self.plan
        if plan.perm is None:
            return self(feat_original)
        out = self(feat_original[self._inv_perm])
        return out[self._perm]
