"""Multi-device halo-exchange graph execution over a sharded `Plan`.

Dataflow (per aggregation): every device owns one contiguous node-range
shard of the graph (`repro.core.shard`), activations live sharded over the
``"shard"`` mesh axis, and each layer

    all-gather activations  ->  local group-aggregate over the shard's
    sub-schedule  ->  slice back to the owned rows

The all-gather IS the halo exchange (every shard's halo is a subset of the
gathered matrix); its linearization transpose is a psum-scatter, so the
backward pass returns feature cotangents to their owner shards while the
aggregation itself differentiates through the custom VJP's TRANSPOSED
per-shard schedules (`kernels.ops`) — forward and backward both run the
group-aggregate kernel, per device.

Everything follows the Plan IR's jit-argument convention: per-shard
schedule tensors are stacked into ``(P, ...)`` operands fed through
`shard_map` with ``PartitionSpec("shard")``, and the per-device body
rebuilds its executor via `Plan.executor_from_args` — one compiled
executable regardless of shard count, nothing entry-specific in closures.

Validated on CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
(see tests/test_shard.py, benchmarks/bench_shard.py).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core.plan import Plan
from repro.core.shard import PlanShards
from repro.kernels.ops import (_SCHED_ARRAY_FIELDS, N_TILE_FIELDS,
                               resolve_backend)
from repro.obs import MetricsRegistry

__all__ = ["SHARD_AXIS", "ShardedExecutor", "local_step_value_and_grad",
           "make_sharded_logits_fn", "make_sharded_train_step", "shard_mesh",
           "squeeze_shard_args", "stack_shard_args"]

SHARD_AXIS = "shard"

# the tile-tensor members of the jit-argument layout (the (E,)-sized edge
# members are stacked separately — see _stack_dir)
_TILE_FIELDS = _SCHED_ARRAY_FIELDS[:N_TILE_FIELDS]


def shard_mesh(num_shards: int) -> Mesh:
    """1-D mesh over the first ``num_shards`` local devices."""
    devs = jax.devices()
    if len(devs) < num_shards:
        raise ValueError(
            f"need {num_shards} devices for {num_shards} shards, have "
            f"{len(devs)} — on CPU run under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={num_shards}")
    return Mesh(np.asarray(devs[:num_shards]), (SHARD_AXIS,))


def _stack_dir(scheds, *, with_edges: bool) -> tuple:
    """Stack one direction's per-shard schedules into (P, ...) operands,
    laid out like `kernels.ops.sched_arrays`.  Tile tensors are already
    uniform (`shard_plan` pads); the (E_p,)-sized edge members are padded
    to the max edge count — padded ``edge_slot`` entries point one past
    the flat group range, so the edge cotangent's gather never reads
    them back — and every shard's padded slots name edge E_max, the 0
    the edge-value layout appends after the E_max padded values."""
    tiles = tuple(jnp.stack([getattr(s, f) for s in scheds])
                  for f in _TILE_FIELDS)
    if not with_edges:
        return tiles + (None, None, None, None)
    oob = scheds[0].nbrs.shape[0] * scheds[0].gpt     # out-of-range slot
    e_max = max(int(s.edge_slot.shape[0]) for s in scheds)

    def padded(name, fill):
        cols = []
        for s in scheds:
            a = getattr(s, name)
            if a is None:
                return None
            cols.append(jnp.pad(jnp.asarray(a), (0, e_max - a.shape[0]),
                                constant_values=fill))
        return jnp.stack(cols)

    slot_edge = jnp.stack([
        jnp.where(s.slot_edge < s.edge_slot.shape[0], s.slot_edge, e_max)
        for s in scheds])
    return tiles + (padded("edge_slot", oob), padded("edge_pos", 0),
                    padded("edge_perm", 0), slot_edge)


def stack_shard_args(shards: PlanShards, *, with_edges: bool = False):
    """(fwd, bwd_or_None) stacked schedule operands for a `PlanShards`."""
    fwd = _stack_dir([p.sched() for p in shards.plans], with_edges=with_edges)
    bwds = [p.sched_bwd() for p in shards.plans]
    bwd = (None if bwds[0] is None
           else _stack_dir(bwds, with_edges=with_edges))
    return fwd, bwd


def squeeze_shard_args(arrs):
    """Drop the per-device leading dim-1 `shard_map` hands each body."""
    return (None if arrs is None
            else tuple(None if a is None else a[0] for a in arrs))


_squeeze = squeeze_shard_args


def local_step_value_and_grad(logits_of, params, labels_l, mask_l,
                              axis: str = SHARD_AXIS):
    """The shared per-device loss/grad body of every sharded train step.

    ``logits_of(params) -> (n_local, C)`` is this device's forward (the
    full-graph layer chain or the sampled block chain).  Computes the
    masked-mean cross-entropy of the GLOBAL batch (den is psum'd first, so
    each device's loss share sums to the global loss), backprops it
    per-device (`value_and_grad` must run INSIDE the shard body — the
    0.4.x `shard_map` transpose cannot differentiate replicated inputs
    from outside), and psums grads/metrics to replicated values.

    Returns ``(grads, loss, {"loss", "accuracy"})``.
    """
    den = jnp.maximum(jax.lax.psum(mask_l.sum(), axis), 1.0)

    def local_loss(p):
        lg = logits_of(p)
        logp = jax.nn.log_softmax(lg, axis=-1)
        per = -jnp.take_along_axis(logp, labels_l[:, None], axis=1)[:, 0]
        return (per * mask_l).sum() / den, lg

    (loss_p, lg), grads = jax.value_and_grad(local_loss, has_aux=True)(params)
    loss, accn = jax.lax.psum(
        (loss_p, ((lg.argmax(-1) == labels_l) * mask_l).sum() / den), axis)
    grads = jax.lax.psum(grads, axis)
    return grads, loss, {"loss": loss, "accuracy": accn}


def _record_shard_gauges(registry: MetricsRegistry, shards: PlanShards):
    """Partition-shape gauges shared by every sharded entry point: edge
    balance across shards and per-shard halo node counts."""
    st = shards.stats()
    registry.gauge(
        "shard_edge_balance",
        desc="max/mean edges per shard (1.0 = perfect)").set(
        st["edge_balance"])
    for p, h in enumerate(shards.halo):
        registry.gauge(
            "shard_halo_nodes", labels={"shard": p},
            desc="remote source nodes shard p reads (selective-"
                 "exchange lower bound)").set(len(h))


class ShardedExecutor:
    """Multi-device counterpart of `core.aggregate.PlanExecutor`.

    ``__call__(feat)`` / ``aggregate_edges(feat, edge_values)`` take and
    return arrays in the PARENT plan's node order and full node count —
    sharding, padding and the halo exchange are internal.  Differentiable
    w.r.t. features (and dynamic edge values) whenever the parent plan
    carried a backward pair or the backend is ``"xla"``.

    Example
    -------
    >>> plan = plan_for(g, arch="gcn", edge_vals=vals, with_backward=True)
    >>> ex = ShardedExecutor(plan.shards(4))   # backend by platform
    >>> out = ex(feat)                        # == PlanExecutor(plan)(feat)
    """

    def __init__(self, shards: PlanShards, *, backend: Optional[str] = None,
                 mesh: Optional[Mesh] = None,
                 registry: Optional[MetricsRegistry] = None):
        self.shards = shards
        self.spec = shards.spec
        self.backend = resolve_backend(backend)
        self.mesh = mesh if mesh is not None else shard_mesh(
            shards.spec.num_shards)
        self.statics = shards.plans[0].jit_statics()
        # the parent plan's dtype policy: features enter the halo exchange
        # at this dtype (bf16 halves the all-gather bytes)
        self.feat_dtype = jnp.dtype(shards.plans[0].config.feat_dtype)
        self._args = stack_shard_args(shards, with_edges=False)
        self._args_dyn = None      # built on first aggregate_edges
        self._edge_ids = None
        self._fwd = None
        self._dyn = None
        # per-shard exchange/balance gauges: halo node counts are known
        # now; halo BYTES need the feature dim, recorded on first call
        self.registry = registry if registry is not None else MetricsRegistry()
        _record_shard_gauges(self.registry, shards)
        self._halo_bytes_dim = None

    def _record_halo_bytes(self, dim: int) -> None:
        """Per-shard halo traffic of a selective exchange at this feature
        width — the lower bound the all-gather transport is compared
        against (docs/distributed.md)."""
        if self._halo_bytes_dim == dim:
            return
        self._halo_bytes_dim = dim
        nbytes = self.feat_dtype.itemsize * dim
        for p, h in enumerate(self.shards.halo):
            self.registry.gauge(
                "shard_halo_bytes", labels={"shard": p},
                desc="halo nodes x feature dim x dtype bytes").set(
                len(h) * nbytes)

    # -------------- static edge values --------------

    def __call__(self, feat: jax.Array) -> jax.Array:
        if self._fwd is None:
            self._fwd = self._build(dynamic=False)
        self._record_halo_bytes(int(feat.shape[1]))
        args_f, args_b = self._args
        return self._fwd(feat, args_f, args_b)

    # -------------- dynamic edge values --------------

    def aggregate_edges(self, feat: jax.Array,
                        edge_values: jax.Array) -> jax.Array:
        """Dynamic per-edge weights in the PARENT graph's CSR edge order
        (the GAT-type path).  Shard p's edges are a contiguous slice of
        that order, gathered inside the jitted wrapper so edge-value
        cotangents scatter straight back to the global tensor."""
        if self._dyn is None:
            self._dyn = self._build(dynamic=True)
            self._args_dyn = stack_shard_args(self.shards, with_edges=True)
            e_max = max(hi - lo for lo, hi in self.shards.edge_ranges)
            ids = np.zeros((self.spec.num_shards, e_max), np.int64)
            msk = np.zeros((self.spec.num_shards, e_max), np.float32)
            for p, (lo, hi) in enumerate(self.shards.edge_ranges):
                ids[p, : hi - lo] = np.arange(lo, hi)
                msk[p, : hi - lo] = 1.0
            self._edge_ids = (jnp.asarray(ids), jnp.asarray(msk))
        self._record_halo_bytes(int(feat.shape[1]))
        args_f, args_b = self._args_dyn
        ids, msk = self._edge_ids
        return self._dyn(feat, edge_values, ids, msk, args_f, args_b)

    # -------------- builders --------------

    def _build(self, *, dynamic: bool):
        spec, statics, backend = self.spec, self.statics, self.backend
        n, n_pad, n_local = spec.num_nodes, spec.padded_nodes, spec.n_local
        cdt = self.feat_dtype

        def local_fn(feat_l, ev_l, arrs_f, arrs_b):
            full = jax.lax.all_gather(feat_l, SHARD_AXIS, axis=0, tiled=True)
            ex = Plan.executor_from_args(
                statics, (_squeeze(arrs_f), _squeeze(arrs_b)),
                backend=backend)
            out = (ex(full) if ev_l is None
                   else ex.aggregate_edges(full, ev_l[0]))
            return out[:n_local]

        sm = shard_map(local_fn, mesh=self.mesh,
                       in_specs=(P(SHARD_AXIS), P(SHARD_AXIS),
                                 P(SHARD_AXIS), P(SHARD_AXIS)),
                       out_specs=P(SHARD_AXIS), check_vma=False)

        if not dynamic:
            @jax.jit
            def fwd(feat, args_f, args_b):
                feat = jnp.pad(feat.astype(cdt),
                               ((0, n_pad - feat.shape[0]), (0, 0)))
                return sm(feat, None, args_f, args_b)[:n]
            return fwd

        @jax.jit
        def dyn(feat, ev, ids, msk, args_f, args_b):
            feat = jnp.pad(feat.astype(cdt),
                           ((0, n_pad - feat.shape[0]), (0, 0)))
            ev_stack = ev.astype(jnp.float32)[ids] * msk      # (P, E_max)
            return sm(feat, ev_stack, args_f, args_b)[:n]
        return dyn


def _model_pieces(cfg, shards: PlanShards, mesh: Optional[Mesh]):
    from repro.models.gnn import gnn_sharded_logits
    mesh = mesh if mesh is not None else shard_mesh(shards.spec.num_shards)
    statics = shards.plans[0].jit_statics()
    args = stack_shard_args(shards, with_edges=False)

    def local_logits(params, feat_l, arrs_f, arrs_b):
        ex = Plan.executor_from_args(
            statics, (_squeeze(arrs_f), _squeeze(arrs_b)),
            backend=cfg.backend)
        return gnn_sharded_logits(cfg, params, feat_l, ex, axis=SHARD_AXIS)

    return mesh, args, local_logits


def make_sharded_logits_fn(cfg, shards: PlanShards, *,
                           mesh: Optional[Mesh] = None,
                           registry: Optional[MetricsRegistry] = None):
    """``logits_fn(params, feat) -> (num_nodes, num_classes)`` running the
    full-graph GCN/GIN forward sharded P ways (parent plan node order in
    and out — numerically the single-device `GNNModel.logits`)."""
    if registry is not None:
        _record_shard_gauges(registry, shards)
        nbytes = jnp.dtype(cfg.feat_dtype).itemsize * cfg.in_dim
        for p, h in enumerate(shards.halo):
            registry.gauge(
                "shard_halo_bytes", labels={"shard": p},
                desc="halo nodes x feature dim x dtype bytes").set(
                len(h) * nbytes)

    mesh, (args_f, args_b), local_logits = _model_pieces(cfg, shards, mesh)
    spec = shards.spec
    n, n_pad = spec.num_nodes, spec.padded_nodes

    sm = shard_map(local_logits, mesh=mesh,
                   in_specs=(P(), P(SHARD_AXIS), P(SHARD_AXIS),
                             P(SHARD_AXIS)),
                   out_specs=P(SHARD_AXIS), check_vma=False)

    @jax.jit
    def logits(params, feat, args_f, args_b):
        feat = jnp.pad(feat.astype(cfg.compute_dtype),
                       ((0, n_pad - feat.shape[0]), (0, 0)))
        return sm(params, feat, args_f, args_b)[:n]

    return lambda params, feat: logits(params, feat, args_f, args_b)


def make_sharded_train_step(cfg, shards: PlanShards, opt, *,
                            mesh: Optional[Mesh] = None, jit: bool = True,
                            registry: Optional[MetricsRegistry] = None):
    """`Trainer`-shaped ``step_fn(state, batch)`` for sharded full-graph
    training: per-device forward/backward over the shard sub-schedules,
    psum'd masked loss, gradients returned replicated by the `shard_map`
    transpose (the all-gathers' psum-scatters route feature cotangents;
    replicated-parameter cotangents psum across shards automatically).

    ``batch`` is the single-device contract: ``{"feat", "labels"[,
    "mask"]}`` in the parent plan's node order; the padded tail rows are
    masked out of the loss, so the loss matches the 1-device step."""
    from repro.optim.adamw import adamw_update

    if registry is not None:
        _record_shard_gauges(registry, shards)
        nbytes = jnp.dtype(cfg.feat_dtype).itemsize * cfg.in_dim
        for p, h in enumerate(shards.halo):
            registry.gauge(
                "shard_halo_bytes", labels={"shard": p},
                desc="halo nodes x feature dim x dtype bytes").set(
                len(h) * nbytes)

    mesh, (args_f, args_b), local_logits = _model_pieces(cfg, shards, mesh)
    spec = shards.spec
    n, n_pad = spec.num_nodes, spec.padded_nodes

    def local_step(params, feat_l, labels_l, mask_l, arrs_f, arrs_b):
        return local_step_value_and_grad(
            lambda p: local_logits(p, feat_l, arrs_f, arrs_b),
            params, labels_l, mask_l)

    step_sm = shard_map(local_step, mesh=mesh,
                        in_specs=(P(), P(SHARD_AXIS), P(SHARD_AXIS),
                                  P(SHARD_AXIS), P(SHARD_AXIS),
                                  P(SHARD_AXIS)),
                        out_specs=(P(), P(), P()), check_vma=False)

    def step(state, feat, labels, mask, args_f, args_b):
        params, opt_state = state
        feat = jnp.pad(feat.astype(cfg.compute_dtype),
                       ((0, n_pad - feat.shape[0]), (0, 0)))
        labels = jnp.pad(labels.astype(jnp.int32), (0, n_pad - labels.shape[0]))
        mask = jnp.pad(mask.astype(jnp.float32), (0, n_pad - mask.shape[0]))
        grads, loss, metrics = step_sm(params, feat, labels, mask,
                                       args_f, args_b)
        params, opt_state, om = adamw_update(opt, grads, opt_state, params)
        return (params, opt_state), {**metrics, **om}

    step_c = jax.jit(step) if jit else step

    def inputs(state, batch):
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(n, jnp.float32)
        return state, batch["feat"], batch["labels"], mask, args_f, args_b

    def step_fn(state, batch):
        return step_c(*inputs(state, batch))

    if jit:
        # ahead-of-time view of the jitted step (its HLO shows the kernels
        # and the collectives), like a `jax.jit` function's ``lower``
        step_fn.lower = lambda state, batch: step_c.lower(*inputs(state, batch))
    return step_fn
