"""Plan cache: amortize advisor runs across serving requests.

Two levels, from cheapest to most general:

  * **exact level** — blake2b over the (bucketed) subgraph's CSR bytes +
    edge values + arch key -> a ready `CacheEntry` (plan, device-resident
    schedule, and the engine-installed jitted forward).  Hot seeds and
    repeated batches skip ALL preprocessing.
  * **config level** — a coarse `graph_fingerprint` (pow2-bucketed
    node/edge counts + quantized log-degree histogram + arch key) ->
    `AggConfig`, so the §7 tuner runs once per workload *shape class*;
    a fingerprint hit still rebuilds the (cheap, vectorized) partition via
    `core.advisor.plan_for` but skips the evolutionary search.

Shape bucketing: subgraph node counts are padded to powers of two before
partitioning (`graphs.subgraph.pad_to_nodes`) and tile counts are padded to
powers of two here, so `group_aggregate_pallas` / the XLA executor see a
small recurring set of operand shapes and their jit caches actually hit.
Padded tiles carry all-zero edge values (the partitioner's own padding
convention), so they contribute nothing to any output row.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Optional

import numpy as np

from repro.core.advisor import plan_for
from repro.core.aggregate import PlanExecutor
from repro.core.model import AggConfig
from repro.core.partition import pad_partition_tiles
from repro.core.plan import Plan
from repro.graphs.csr import CSRGraph
from repro.kernels.ops import resolve_backend
from repro.obs import MetricsRegistry, SpanTracer

__all__ = [
    "CacheEntry",
    "PlanCache",
    "bucket_pow2",
    "graph_fingerprint",
    "graph_key",
    "pad_partition_tiles",
    "shape_class_fingerprint",
]


def bucket_pow2(x: int, lo: int = 1) -> int:
    """Smallest power of two >= max(x, lo)."""
    x = max(int(x), lo)
    return 1 << (x - 1).bit_length()


def shape_class_fingerprint(g: CSRGraph, arch_key: tuple = ()) -> tuple:
    """Coarse workload signature: graphs that share it get the same tuned
    config.  Pow2 size buckets + a 16-bin log2-degree histogram quantized to
    1/4ths of the working node count, so near-identical ego-batches collide.
    Isolated nodes are excluded — they carry no aggregation work and their
    count is mostly shape-bucketing pad.

    This is deliberately content-BLIND — it names an equivalence class of
    workload shapes, not a graph.  Use it as a `PlanCache(fingerprint_fn=)`
    only where every planned graph is ephemeral and exact-keyed anyway (the
    sampled loader's freshly drawn bipartite blocks, the serving engine's
    ego-graph batches — both re-key plans exactly, with the graph epoch in
    the exact key, so the shape-class memo can only ever transfer a tuned
    CONFIG, never a plan); long-lived mutable graphs planned directly must
    use the content-aware `graph_fingerprint` default."""
    degs = g.degrees
    degs = degs[degs > 0]
    hist = (np.bincount(np.minimum(np.log2(degs).astype(np.int64), 15),
                        minlength=16)
            if len(degs) else np.zeros(16, np.int64))
    frac = tuple(int(x) for x in
                 np.round(4.0 * hist / max(len(degs), 1)).astype(np.int64))
    return (bucket_pow2(g.num_nodes), bucket_pow2(max(g.num_edges, 1)),
            frac, tuple(arch_key))


def graph_fingerprint(g: CSRGraph, arch_key: tuple = ()) -> tuple:
    """Content-aware workload signature (the PlanCache default): the shape
    class of `shape_class_fingerprint` plus a structure digest — exact
    node/edge counts and strided samples of indptr/indices.  Two copies of
    the same structure still share it (so a same-shape lookup with
    different edge VALUES reuses the tuned config), but a mutated graph
    practically never collides with its pre-mutation self: indptr is
    cumulative, so even a single inserted or deleted edge shifts every
    later sampled row pointer.  That is what keeps the config memo from
    silently serving decisions made for a different graph after a
    `GraphDelta` lands."""
    h = hashlib.blake2b(digest_size=8)
    h.update(np.int64([g.num_nodes, g.num_edges]).tobytes())
    if g.num_nodes:
        h.update(np.ascontiguousarray(
            g.indptr[::max(1, g.num_nodes // 1024)]).tobytes())
    if g.num_edges:
        h.update(np.ascontiguousarray(
            g.indices[::max(1, g.num_edges // 1024)]).tobytes())
    return shape_class_fingerprint(g, arch_key) + (h.hexdigest(),)


def graph_key(g: CSRGraph, edge_vals: Optional[np.ndarray],
              arch_key: tuple = ()) -> tuple:
    """Exact identity of a (subgraph, edge values, arch) triple."""
    h = hashlib.blake2b(digest_size=16)
    h.update(np.ascontiguousarray(g.indptr).tobytes())
    h.update(np.ascontiguousarray(g.indices).tobytes())
    if edge_vals is not None:
        h.update(np.ascontiguousarray(edge_vals, dtype=np.float32).tobytes())
    return (h.hexdigest(), tuple(arch_key))


# pad_partition_tiles moved to `repro.core.partition` (the shard splitter
# needs it below the serving layer); re-exported here for back-compat.

_UNSET = object()   # "max_plans not given" sentinel (None means unbounded)


@dataclasses.dataclass
class CacheEntry:
    plan: Plan
    executor: PlanExecutor
    apply_fn: Optional[Callable] = None   # engine-installed jitted forward
    hits: int = 0
    extras: dict = dataclasses.field(default_factory=dict)
    # keyed-invalidation handles (docs/dynamic.md): the fingerprint the
    # entry was built under and the graph epoch the caller stamped
    # (`get_or_build(epoch=...)`) — `invalidate()` selects on these.
    fingerprint: Optional[tuple] = None
    epoch: int = 0


class PlanCache:
    """LRU plan cache + fingerprint->config memo (see module docstring).

    Memory bounds: ``max_plans`` LRU-bounds the ready-plan level (None =
    unbounded; ``max_entries`` is the legacy name for the same knob and
    keeps its old default of 64 when ``max_plans`` is not given), and
    ``max_configs`` LRU-bounds the fingerprint->config memo (None =
    unbounded — configs are tiny, but a long-tailed serving workload can
    accumulate fingerprints forever).  Evictions from both levels are
    surfaced in `stats()`.

    ``registry``: optional shared `repro.obs.MetricsRegistry` — hit/miss/
    eviction counters, the build-time histogram, tuner cost and per-source
    ``plan_cache_builds_total{source=tuner|memo|heuristic}`` provenance all
    land there (a private registry is kept when none is given).
    """

    def __init__(self, *, backend: Optional[str] = None,
                 tune_mode: str = "model",
                 tune_iters: int = 8, max_entries: int = 64,
                 max_plans: Optional[int] = _UNSET,
                 max_configs: Optional[int] = None,
                 bucket_shapes: bool = True, seed: int = 0,
                 with_backward: bool = False, config_fn=None,
                 feat_dtype: str = "float32",
                 fingerprint_fn: Callable = graph_fingerprint,
                 registry: Optional[MetricsRegistry] = None):
        self.backend = resolve_backend(backend)
        # fingerprint_fn: (CSRGraph, arch_key) -> hashable — the config
        # memo key.  Default is the content-aware
        # `graph_fingerprint`; the sampled loader opts into the coarser
        # `shape_class_fingerprint` (see its docstring for why that is
        # safe there and nowhere else).
        self.fingerprint_fn = fingerprint_fn
        self.tune_mode = tune_mode
        self.tune_iters = tune_iters
        # feat_dtype: the dtype policy every built plan carries — part of
        # the cache identity (a bf16 plan's statics/executable differ from
        # the f32 plan of the same subgraph) and of what the tuner prices.
        self.feat_dtype = feat_dtype
        # not-given falls back to the legacy max_entries knob; an EXPLICIT
        # max_plans=None means unbounded (the ServingConfig contract)
        self.max_plans = max_entries if max_plans is _UNSET else max_plans
        self.max_configs = max_configs
        self.bucket_shapes = bucket_shapes
        self.seed = seed
        # config_fn: optional (CSRGraph) -> AggConfig consulted on a
        # fingerprint MISS instead of running the tuner — callers who know
        # their workload shape class (the sampled loader's fanout-bounded
        # blocks, whose near-empty (row, window) buckets the full-graph
        # kernel model prices wrong) supply a heuristic; the memo and the
        # two-level hit accounting behave exactly as with the tuner.
        self.config_fn = config_fn
        # with_backward: every built plan also carries the transposed-graph
        # schedule (`plan_for(with_backward=True)`) so cached entries are
        # train-ready — the sampled mini-batch loader's mode.  Backward tile
        # counts are pow2-padded alongside the forward ones so the training
        # step's jit cache buckets both directions.
        self.with_backward = with_backward
        # one cache may now be SHARED across serving tenants (engines),
        # and the async tier's worker thread races test/driver threads on
        # it — every lookup/mutation runs under this reentrant lock.
        # Plan builds happen inside it too: serializing duplicate builds
        # of the same key is the behavior a cache wants anyway.
        self._lock = threading.RLock()
        self._plans: "OrderedDict[tuple, CacheEntry]" = OrderedDict()
        self._configs: "OrderedDict[tuple, AggConfig]" = OrderedDict()
        self.exact_hits = 0
        self.config_hits = 0
        self.misses = 0
        self.evictions = 0
        self.config_evictions = 0
        self.invalidations = 0
        # observability: the int attributes above stay the source of truth
        # for stats() (back-compat); the registry mirrors them as counters
        # and adds what ints can't carry — build-time distribution, tuner
        # cost, and config provenance (which path chose each built plan's
        # AggConfig: "tuner" search / fingerprint "memo" / caller-supplied
        # "heuristic" config_fn) — see docs/observability.md.
        self.registry = registry if registry is not None else MetricsRegistry()
        self._c_exact = self.registry.counter(
            "plan_cache_exact_hits_total", desc="ready-plan cache hits")
        self._c_config = self.registry.counter(
            "plan_cache_config_hits_total",
            desc="fingerprint->config memo hits (plan rebuilt, tuner skipped)")
        self._c_miss = self.registry.counter(
            "plan_cache_misses_total", desc="full cache misses")
        self._c_evict = self.registry.counter(
            "plan_cache_evictions_total", desc="plan-level LRU evictions")
        self._c_cfg_evict = self.registry.counter(
            "plan_cache_config_evictions_total",
            desc="config-memo LRU evictions")
        self._c_invalidate = self.registry.counter(
            "plan_cache_invalidations_total",
            desc="entries dropped by keyed invalidation (graph mutations)")
        self._h_build = self.registry.histogram(
            "plan_cache_build_seconds",
            desc="plan_for + tile padding + executor build on the miss path")
        self._c_tuner_runs = self.registry.counter(
            "tuner_runs_total", desc="evolutionary searches run")
        self._c_tuner_evals = self.registry.counter(
            "tuner_evaluations_total",
            desc="unique tuner score-fn evaluations (TunerResult.evaluations)")

    def get_or_build(self, g: CSRGraph, *, arch: str, in_dim: int,
                     hidden_dim: int, num_layers: int,
                     edge_vals: Optional[np.ndarray] = None,
                     epoch: Optional[int] = None,
                     tracer: Optional[SpanTracer] = None) -> CacheEntry:
        """The ready plan for ``g``, built on a miss.  A build's planner
        spans nest under the span open on ``tracer`` (the caller's; None
        = the process tracer)."""
        with self._lock:
            return self._get_or_build_locked(
                g, arch=arch, in_dim=in_dim, hidden_dim=hidden_dim,
                num_layers=num_layers, edge_vals=edge_vals, epoch=epoch,
                tracer=tracer)

    def _get_or_build_locked(self, g: CSRGraph, *, arch: str, in_dim: int,
                             hidden_dim: int, num_layers: int,
                             edge_vals: Optional[np.ndarray] = None,
                             epoch: Optional[int] = None,
                             tracer: Optional[SpanTracer] = None
                             ) -> CacheEntry:
        arch_key = (arch, in_dim, hidden_dim, num_layers,
                    self.feat_dtype) + (
            ("bwd",) if self.with_backward else ())
        # the graph epoch (mutable resident graphs — docs/dynamic.md) is
        # part of the EXACT key only: a plan may never be served across a
        # mutation boundary, but the shape-class config memo transfers.
        exact_key = arch_key if epoch is None else arch_key + ("epoch",
                                                               epoch)
        key = graph_key(g, edge_vals, exact_key)
        ent = self._plans.get(key)
        if ent is not None:
            self._plans.move_to_end(key)
            self.exact_hits += 1
            self._c_exact.inc()
            ent.hits += 1
            return ent

        fp = self.fingerprint_fn(g, arch_key)
        config = self._configs.get(fp)
        if config is not None:
            self._configs.move_to_end(fp)
            self.config_hits += 1
            self._c_config.inc()
            source = "memo"
        else:
            self.misses += 1
            self._c_miss.inc()
            source = "heuristic" if self.config_fn is not None else "tuner"
            if self.config_fn is not None:
                config = self.config_fn(g)
                if config.feat_dtype != self.feat_dtype:
                    config = dataclasses.replace(
                        config, feat_dtype=self.feat_dtype)
                self._set_config(fp, config)
        t_build = time.perf_counter()
        plan = plan_for(g, arch=arch, in_dim=in_dim, hidden_dim=hidden_dim,
                        num_layers=num_layers, edge_vals=edge_vals,
                        config=config, tune_mode=self.tune_mode,
                        tune_iters=self.tune_iters, seed=self.seed,
                        with_backward=self.with_backward,
                        feat_dtype=self.feat_dtype, tracer=tracer)
        if config is None:
            self._set_config(fp, plan.config)
        if plan.tuner is not None:
            self._c_tuner_runs.inc()
            self._c_tuner_evals.inc(plan.tuner.evaluations)
        if self.bucket_shapes:
            part = pad_partition_tiles(
                plan.partition, bucket_pow2(plan.partition.num_tiles))
            part_bwd = plan.partition_bwd
            if part_bwd is not None:
                part_bwd = pad_partition_tiles(
                    part_bwd, bucket_pow2(part_bwd.num_tiles))
            plan = dataclasses.replace(plan, partition=part,
                                       partition_bwd=part_bwd)
        ent = CacheEntry(plan=plan, executor=plan.executor(self.backend),
                         fingerprint=fp, epoch=0 if epoch is None else epoch)
        self._h_build.observe(time.perf_counter() - t_build)
        self.registry.counter(
            "plan_cache_builds_total", labels={"source": source},
            desc="plans built, by AggConfig provenance").inc()
        self._plans[key] = ent
        while self.max_plans is not None and len(self._plans) > self.max_plans:
            self._plans.popitem(last=False)
            self.evictions += 1
            self._c_evict.inc()
        return ent

    def invalidate(self, fingerprint: Optional[tuple] = None, *,
                   before_epoch: Optional[int] = None) -> int:
        """Keyed invalidation after a graph mutation (docs/dynamic.md).

        ``fingerprint``: drop the ready plans built under that fingerprint
        plus its config-memo entry.  ``before_epoch``: drop every ready
        plan stamped with an earlier graph epoch (the serving engine's swap
        protocol — entries for egos of the pre-mutation snapshot); the
        config memo is kept, a shape-class tuning decision survives content
        changes.  With neither selector the whole cache (both levels) is
        dropped.
        Returns the number of entries removed; each removal counts into
        ``plan_cache_invalidations_total``."""
        with self._lock:
            n = 0
            for key in list(self._plans):
                ent = self._plans[key]
                if fingerprint is not None and ent.fingerprint != fingerprint:
                    continue
                if before_epoch is not None and ent.epoch >= before_epoch:
                    continue
                del self._plans[key]
                n += 1
            if fingerprint is not None:
                if self._configs.pop(fingerprint, None) is not None:
                    n += 1
            elif before_epoch is None:
                n += len(self._configs)
                self._configs.clear()
            self.invalidations += n
            self._c_invalidate.inc(n)
            return n

    def _set_config(self, fp: tuple, config: AggConfig) -> None:
        with self._lock:
            self._configs[fp] = config
            self._configs.move_to_end(fp)
            while (self.max_configs is not None
                   and len(self._configs) > self.max_configs):
                self._configs.popitem(last=False)
                self.config_evictions += 1
                self._c_cfg_evict.inc()

    @property
    def num_plans(self) -> int:
        with self._lock:
            return len(self._plans)

    @property
    def num_configs(self) -> int:
        with self._lock:
            return len(self._configs)

    def stats(self) -> dict:
        with self._lock:
            return self._stats_locked()

    def _stats_locked(self) -> dict:
        total = self.exact_hits + self.config_hits + self.misses
        hits = self.exact_hits + self.config_hits
        return {
            "lookups": total,
            "exact_hits": self.exact_hits,
            "config_hits": self.config_hits,
            "misses": self.misses,
            "hit_rate": hits / total if total else 0.0,
            "plans": self.num_plans,
            "configs": self.num_configs,
            "evictions": self.evictions,
            "config_evictions": self.config_evictions,
            "invalidations": self.invalidations,
        }
