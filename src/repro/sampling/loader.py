"""Mini-batch loader + train step for neighbor-sampled GNN training.

`SampledLoader` turns a resident graph + features + labels into a
deterministic stream of device-ready `TrainBatch`es:

  1. seeds for step s are a slice of a per-epoch permutation (seeded by
     ``(seed, epoch)``), and the fanout sampler is seeded by ``(seed,
     step)`` — ``batch_for(step)`` is a pure function of the step index,
     which is the `runtime.Trainer` restart contract;
  2. every block is padded to pow2 *node* buckets (`pad_to_nodes` +
     `bucket_pow2`) and planned through a `PlanCache` (``with_backward``
     per backend), whose ``bucket_shapes`` mode pads *tile* counts to pow2
     — so the step executable sees a small recurring set of operand shapes;
  3. a background thread prefetches batches into a double buffer
     (``prefetch=2``): host-side sampling + planning for step s+1 overlaps
     device compute for step s.  Out-of-order requests (a Trainer restart)
     flush the buffer and resync — determinism makes that loss-free.

`SampledTrainStep` is the matching ``step_fn(state, batch)``: it keeps ONE
jitted executable per shape bucket and feeds each batch's schedule tensors
in as ARGUMENTS (`kernels.ops.SchedView`), so two batches with different
raw sizes but the same bucket reuse one compilation — the payoff of pow2
bucketing, now on the training path.  On Pallas backends the executable's
backward pass runs through the transposed-schedule kernel (the plans carry
``partition_bwd``).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Optional, Sequence

import numpy as np

from repro.graphs.csr import CSRGraph
from repro.graphs.subgraph import pad_to_nodes
from repro.models.gnn import GNNConfig, gnn_block_loss
from repro.obs import MetricsRegistry
from repro.sampling.neighbor import SampledBatch, sample_blocks
from repro.serving.plan_cache import (PlanCache, bucket_pow2,
                                      shape_class_fingerprint)

__all__ = ["LoaderConfig", "TrainBatch", "SampledLoader", "SampledTrainStep",
           "ShardedSampledTrainStep", "sampled_agg_config"]


def sampled_agg_config(g: CSRGraph):
    """Schedule knobs for fanout-sampled bipartite blocks.

    The §7 tuner's kernel model prices full graphs, where most
    (node_block, window) buckets are dense; sampled blocks are the opposite
    — a few fanout-bounded edges scattered over a wide frontier — and a
    full-graph-style config (small ``src_win``, large ``gpt``) explodes
    into ~99.7% padded slots (measured 4.5k× slower on a reddit block).
    Wide windows (~num_nodes/8, so every block sees a handful of windows)
    with small groups-per-tile keep bucket padding bounded: slot counts
    drop ~100× and the XLA step goes from seconds to milliseconds.
    """
    from repro.core.model import AggConfig
    src_win = min(max(bucket_pow2(max(g.num_nodes // 8, 1)), 256), 4096)
    return AggConfig(gs=8, gpt=8, dt=128, src_win=src_win, ont=8)


@dataclasses.dataclass(frozen=True)
class LoaderConfig:
    fanouts: tuple                  # per-layer fanout, forward order
    batch_nodes: int                # seeds per mini-batch
    seed: int = 0
    bucket_shapes: bool = True      # pow2 node/tile shape bucketing
    prefetch: int = 2               # double buffering depth
    drop_last: bool = True          # keep every batch the same seed count
    use_tuner: bool = False         # False: `sampled_agg_config` heuristic
    tune_mode: str = "model"
    tune_iters: int = 4
    max_plans: int = 32


@dataclasses.dataclass
class TrainBatch:
    """One device-ready sampled mini-batch."""

    feat: np.ndarray                # (P0, in_dim) padded input features
    labels: np.ndarray              # (P_last,) int32, padded with 0
    mask: np.ndarray                # (P_last,) float32, 1.0 on real seeds
    entries: list                   # per-layer plan-cache CacheEntry
    seeds: np.ndarray               # (B,) global seed ids
    num_seeds: int
    step: int
    key: tuple                      # jit-bucket signature (statics + shapes)
    raw_nodes: tuple                # per-block UNPADDED src counts
    raw_edges: tuple                # per-block UNPADDED edge counts


class SampledLoader:
    """Deterministic, prefetching mini-batch source (see module doc).

    Callable — ``loader(step)`` returns the batch for ``step`` (through the
    prefetch buffer), so it drops straight into `Trainer(batch_fn=loader)`.
    Use as a context manager or call `close()` to stop the worker thread.
    """

    def __init__(self, g: CSRGraph, feat: np.ndarray, labels: np.ndarray,
                 cfg: GNNConfig, loader: LoaderConfig, *,
                 train_nodes: Optional[np.ndarray] = None,
                 cache: Optional[PlanCache] = None,
                 with_backward: Optional[bool] = None,
                 start_thread: bool = True,
                 registry: Optional[MetricsRegistry] = None):
        if cfg.arch not in ("gcn", "gin"):
            # fail at construction, not minutes later inside the first
            # jitted step (gat needs per-block dynamic-edge plumbing the
            # sampled path does not carry)
            raise ValueError(
                f"sampled training supports gcn/gin, not {cfg.arch!r}: "
                "GAT trains full-graph only")
        if len(loader.fanouts) != cfg.num_layers:
            raise ValueError(
                f"fanouts {loader.fanouts} must name one fanout per layer "
                f"(num_layers={cfg.num_layers})")
        assert feat.shape == (g.num_nodes, cfg.in_dim), \
            (feat.shape, g.num_nodes, cfg.in_dim)
        self.g = g
        self.feat = np.ascontiguousarray(feat, dtype=np.float32)
        self.labels = np.ascontiguousarray(labels, dtype=np.int32)
        self.cfg = cfg
        self.lc = loader
        self.train_nodes = (np.arange(g.num_nodes, dtype=np.int64)
                            if train_nodes is None
                            else np.asarray(train_nodes, dtype=np.int64))
        if with_backward is None:
            with_backward = cfg.backend.startswith("pallas")
        # metrics: sample/plan time per batch, prefetch stall seen by the
        # consumer, and resync events — shared with the plan cache so one
        # registry tells the whole loader story (docs/observability.md).
        # The registry's per-metric locks make worker-thread observes and
        # train-thread reads safe (raced in tests/test_obs.py).
        self.registry = registry if registry is not None else MetricsRegistry()
        self._h_sample = self.registry.histogram(
            "loader_sample_seconds",
            desc="fanout sampling + padding + planning per batch")
        self._h_stall = self.registry.histogram(
            "loader_prefetch_stall_seconds",
            desc="consumer wait for a batch (0 when the prefetch buffer hit)")
        self._c_batches = self.registry.counter(
            "loader_batches_built_total", desc="sampled batches constructed")
        self._c_resync = self.registry.counter(
            "loader_resyncs_total",
            desc="prefetch-buffer flushes on out-of-order access (restarts)")
        self._c_swaps = self.registry.counter(
            "loader_graph_swaps_total",
            desc="resident-graph replacements applied at batch boundaries")
        self._g_epoch = self.registry.gauge(
            "loader_graph_epoch", desc="delta generation of the resident graph")
        # sampled blocks are ephemeral subgraphs keyed EXACTLY in the plan
        # cache; the coarse shape-class fingerprint keeps the config memo
        # hot across them (a content-aware fingerprint would make every
        # block a memo miss — see shape_class_fingerprint's docstring)
        self.cache = cache if cache is not None else PlanCache(
            backend=cfg.backend, tune_mode=loader.tune_mode,
            tune_iters=loader.tune_iters, max_entries=loader.max_plans,
            bucket_shapes=loader.bucket_shapes, seed=loader.seed,
            with_backward=with_backward,
            config_fn=None if loader.use_tuner else sampled_agg_config,
            fingerprint_fn=shape_class_fingerprint,
            feat_dtype=cfg.feat_dtype, registry=self.registry)
        self.edge_mode = "gcn" if cfg.arch == "gcn" else "scale"
        self._default_train_nodes = train_nodes is None
        self.graph_epoch = 0
        n = len(self.train_nodes)
        b = min(loader.batch_nodes, n)
        self.steps_per_epoch = max(
            n // b if loader.drop_last else -(-n // b), 1)
        self._epoch_perm_cache: tuple[int, np.ndarray] = (-1, None)
        # prefetch state
        self._cond = threading.Condition()
        self._buf: dict[int, TrainBatch] = {}
        self._head = 0                  # next step the worker picks up
        self._inflight: Optional[int] = None  # step the worker is computing
        self._last_req = 0              # most recently consumed/requested step
        self._pending_swap = None       # (g, feat, labels) applied at a
        #                                 batch boundary (update_graph)
        self._stop = False
        self._err: Optional[BaseException] = None
        self._thread = None
        if start_thread and loader.prefetch > 0:
            self._thread = threading.Thread(target=self._worker, daemon=True)
            self._thread.start()

    # ---------------- deterministic batch construction ----------------

    def _epoch_perm(self, epoch: int) -> np.ndarray:
        cached_epoch, perm = self._epoch_perm_cache
        if cached_epoch != epoch:
            rng = np.random.default_rng((self.lc.seed, 0x5eed, epoch))
            perm = rng.permutation(self.train_nodes)
            self._epoch_perm_cache = (epoch, perm)
        return perm

    def seeds_for(self, step: int) -> np.ndarray:
        epoch, pos = divmod(step, self.steps_per_epoch)
        b = min(self.lc.batch_nodes, len(self.train_nodes))
        return self._epoch_perm(epoch)[pos * b:(pos + 1) * b]

    def batch_for(self, step: int) -> TrainBatch:
        """Pure: sample + pad + plan the batch for ``step`` (no buffer)."""
        t0 = time.perf_counter()
        cfg, lc = self.cfg, self.lc
        sb = sample_blocks(self.g, self.seeds_for(step), lc.fanouts,
                           rng=np.random.default_rng((lc.seed, 1, step)),
                           edge_mode=self.edge_mode)
        entries, key_parts = [], []
        for blk in sb.blocks:
            sub = blk.graph
            if lc.bucket_shapes:
                sub = pad_to_nodes(sub, bucket_pow2(sub.num_nodes))
            ent = self.cache.get_or_build(
                sub, arch=cfg.arch, in_dim=cfg.in_dim,
                hidden_dim=cfg.hidden_dim, num_layers=cfg.num_layers,
                edge_vals=blk.edge_vals)
            entries.append(ent)
            acfg = ent.plan.config
            key_parts.append((
                acfg.gs, acfg.gpt, acfg.ont, acfg.src_win, acfg.dt,
                sub.num_nodes,
                ent.executor.sched.num_tiles,
                None if ent.executor.sched_bwd is None
                else ent.executor.sched_bwd.num_tiles))
        p0 = entries[0].executor.sched.num_nodes
        p_last = entries[-1].executor.sched.num_nodes
        # batch features ship at the policy dtype (bf16 halves the
        # host->device bytes; numpy handles ml_dtypes' bfloat16 natively)
        feat = np.zeros((p0, cfg.in_dim), cfg.compute_dtype)
        feat[:len(sb.input_nodes)] = self.feat[sb.input_nodes]
        labels = np.zeros(p_last, np.int32)
        labels[:len(sb.seeds)] = self.labels[sb.seeds]
        mask = np.zeros(p_last, np.float32)
        mask[:len(sb.seeds)] = 1.0
        batch = TrainBatch(
            feat=feat, labels=labels, mask=mask, entries=entries,
            seeds=sb.seeds, num_seeds=len(sb.seeds), step=step,
            key=(cfg.arch, cfg.backend, cfg.feat_dtype, p0,
                 tuple(key_parts)),
            raw_nodes=tuple(b.num_src for b in sb.blocks),
            raw_edges=tuple(b.graph.num_edges for b in sb.blocks))
        self._h_sample.observe(time.perf_counter() - t0)
        self._c_batches.inc()
        return batch

    # ---------------- graph mutation (docs/dynamic.md) ----------------

    def update_graph(self, delta, *, feat: Optional[np.ndarray] = None,
                     labels: Optional[np.ndarray] = None) -> None:
        """Swap the resident graph at the next safe batch boundary.

        ``delta`` is a `repro.graphs.delta.GraphDelta`; the new CSR is
        built here (caller's thread, no lock held) and handed to the
        prefetch worker, which applies it between ``batch_for`` calls — a
        batch is never sampled from a half-swapped (graph, feat, labels)
        triple.  A batch already being built finishes on the old graph
        (that is the safe boundary, not a torn read).  Features for new
        nodes come from ``delta.node_feat`` (zeros if absent); pass
        ``feat``/``labels`` to replace the full arrays instead.  Buffered
        batches are discarded and rebuilt from the consumer's current
        step, so ``loader(step)`` stays a pure function of the step index
        *per graph epoch* — the Trainer restart contract now holds within
        an epoch of the mutation stream.
        """
        res = self.g.apply_delta(delta)
        g2 = res.graph
        cfg = self.cfg
        if feat is not None:
            feat2 = np.ascontiguousarray(feat, dtype=np.float32)
        else:
            feat2 = self.feat
            if g2.num_nodes > feat2.shape[0]:
                new = np.zeros((g2.num_nodes - feat2.shape[0], cfg.in_dim),
                               np.float32)
                if delta.node_feat is not None:
                    nf = np.asarray(delta.node_feat, np.float32)
                    new[:len(nf)] = nf[:, :cfg.in_dim]
                feat2 = np.concatenate([feat2, new])
        assert feat2.shape == (g2.num_nodes, cfg.in_dim), \
            (feat2.shape, g2.num_nodes, cfg.in_dim)
        if labels is not None:
            labels2 = np.ascontiguousarray(labels, dtype=np.int32)
        else:
            labels2 = self.labels
            if g2.num_nodes > labels2.shape[0]:
                labels2 = np.concatenate(
                    [labels2,
                     np.zeros(g2.num_nodes - labels2.shape[0], np.int32)])
        with self._cond:
            self._pending_swap = (g2, feat2, labels2)
            if self._thread is None:
                self._apply_swap_locked()
            self._cond.notify_all()

    def _apply_swap_locked(self) -> None:
        """Install a pending swap (``self._cond`` held, worker quiescent)."""
        if self._pending_swap is None:
            return
        self.g, self.feat, self.labels = self._pending_swap
        self._pending_swap = None
        if self._default_train_nodes:
            self.train_nodes = np.arange(self.g.num_nodes, dtype=np.int64)
        else:
            # explicit seed sets survive the mutation minus deleted rows'
            # ids beyond the (possibly shrunk) node range
            self.train_nodes = self.train_nodes[
                self.train_nodes < self.g.num_nodes]
        self._epoch_perm_cache = (-1, None)
        n = len(self.train_nodes)
        b = min(self.lc.batch_nodes, n)
        self.steps_per_epoch = max(
            n // b if self.lc.drop_last else -(-n // b), 1)
        # buffered batches were sampled from the old snapshot: drop them
        # and restart prefetch at the consumer's current step (it may be
        # blocked waiting for exactly that step — head must not skip it)
        self._buf.clear()
        self._head = self._last_req
        self.graph_epoch += 1
        self._c_swaps.inc()
        self._g_epoch.set(self.graph_epoch)

    # ---------------- prefetching front ----------------

    def __call__(self, step: int) -> TrainBatch:
        if self._thread is None:
            with self._cond:
                self._apply_swap_locked()
            return self.batch_for(step)
        t0 = time.perf_counter()
        with self._cond:
            if self._err is not None:
                raise RuntimeError("sample loader worker died") from self._err
            self._last_req = step
            if (step not in self._buf and step != self._head
                    and step != self._inflight):
                # restart / out-of-order access (the step is neither
                # buffered, being computed, nor next in line): resync
                self._buf.clear()
                self._head = step
                self._c_resync.inc()
                self._cond.notify_all()
            while step not in self._buf:
                if self._err is not None:
                    raise RuntimeError(
                        "sample loader worker died") from self._err
                self._cond.wait(timeout=0.5)
            batch = self._buf.pop(step)
            self._cond.notify_all()
        # stall = how long device compute sat waiting on host-side
        # sampling/planning; ~0 means the double buffer is doing its job
        self._h_stall.observe(time.perf_counter() - t0)
        return batch

    batch_fn = __call__

    def _worker(self):
        try:
            while True:
                with self._cond:
                    self._apply_swap_locked()  # safe: no batch in flight
                    while not self._stop and len(self._buf) >= self.lc.prefetch:
                        self._cond.wait(timeout=0.5)
                        self._apply_swap_locked()
                    if self._stop:
                        return
                    step = self._head
                    self._head += 1
                    self._inflight = step
                batch = self.batch_for(step)       # heavy work, lock-free
                with self._cond:
                    self._inflight = None
                    if self._stop:
                        return
                    # drop the result if a resync moved past it (keeping it
                    # would pin a never-consumed entry in the buffer)
                    if step >= self._last_req:
                        self._buf[step] = batch
                    self._cond.notify_all()
        except BaseException as e:                 # propagate to consumer
            with self._cond:
                self._err = e
                self._cond.notify_all()

    def close(self):
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def stats(self) -> dict:
        return {"cache": self.cache.stats(),
                "steps_per_epoch": self.steps_per_epoch,
                "batches_built": int(self._c_batches.value),
                "resyncs": int(self._c_resync.value),
                "graph_epoch": self.graph_epoch,
                "graph_swaps": int(self._c_swaps.value),
                "sample_p50_ms": self._h_sample.percentile(50) * 1e3,
                "prefetch_stall_p99_ms": self._h_stall.percentile(99) * 1e3}


class SampledTrainStep:
    """``step_fn(state, batch)`` over sampled blocks, one jit per bucket.

    ``state = (params, opt_state)``; ``batch`` is a `TrainBatch`.  The
    jitted executable takes every schedule tensor as an argument, so all
    batches sharing ``batch.key`` (and therefore shapes) reuse one
    compilation; ``self.traces`` counts actual trace events (the
    bucket-reuse assertion in tests/bench).
    """

    def __init__(self, cfg: GNNConfig, opt, *, jit: bool = True):
        self.cfg = cfg
        self.opt = opt
        self.jit = jit
        self._fns: dict[tuple, object] = {}
        self.traces = 0

    def __call__(self, state, batch: TrainBatch):
        fn = self._fns.get(batch.key)
        if fn is None:
            fn = self._fns[batch.key] = self._build(batch)
        return fn(state, batch.feat, batch.labels, batch.mask,
                  self._block_args(batch))

    @property
    def num_buckets(self) -> int:
        return len(self._fns)

    @staticmethod
    def _block_args(batch: TrainBatch) -> tuple:
        # Plan.jit_args drops the (E,)-sized edge members by default: they
        # are unbucketed (would retrace every batch) and only the dynamic
        # edge-value path reads them, which the sampled trainer never
        # takes (static GCN/GIN edge values).
        return tuple(ent.plan.jit_args() for ent in batch.entries)

    def _build(self, batch: TrainBatch):
        import jax

        from repro.core.plan import Plan
        from repro.optim.adamw import adamw_update

        cfg, opt = self.cfg, self.opt
        statics = [ent.plan.jit_statics() for ent in batch.entries]

        def step(state, feat, labels, mask, blocks):
            self.traces += 1                       # trace-time side effect
            execs = [Plan.executor_from_args(st, args, backend=cfg.backend)
                     for st, args in zip(statics, blocks)]
            params, opt_state = state
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: gnn_block_loss(cfg, p, feat, labels, mask, execs),
                has_aux=True)(params)
            params, opt_state, om = adamw_update(opt, grads, opt_state,
                                                 params)
            return (params, opt_state), {**metrics, **om}

        return jax.jit(step) if self.jit else step


class ShardedSampledTrainStep:
    """Data-parallel sampled training over the ``"shard"`` mesh axis.

    ``step_fn(state, batches)`` consumes ``num_shards`` loader batches per
    optimizer step (drive it with ``batch_fn = lambda s: [loader(s *
    num_shards + p) for p in range(num_shards)]`` — the loader's
    determinism and prefetch buffer handle the interleaving).  Per-layer
    schedules are uniformized host-side (node statics to the max bucket,
    tile counts padded with no-op tiles) and stacked into ``(P, ...)``
    `shard_map` operands; each device runs its own forward/backward over
    its batch's blocks and gradients psum into the replicated global
    gradient of the UNION batch's masked loss — the sampled counterpart of
    `repro.distributed.graph_shard.make_sharded_train_step`, sharing the
    Plan IR's jit-argument convention (one executable per shape bucket).

    The P batches of one step must agree on schedule knobs (same
    `AggConfig` per layer) to share one set of `shard_map` statics.  Pow2
    bucketing makes that the common case, but block frontier sizes vary
    stochastically, so a step whose batches straddle a pow2 node-bucket
    boundary can mix configs — those minority batches are repartitioned
    under the step's widest-bucket config (memoized on their cache
    entries) rather than aborting the run.
    """

    def __init__(self, cfg: GNNConfig, opt, num_shards: int, *,
                 jit: bool = True, mesh=None,
                 registry: Optional[MetricsRegistry] = None):
        from repro.distributed.graph_shard import shard_mesh
        if cfg.arch not in ("gcn", "gin"):
            raise ValueError(
                f"sampled training supports gcn/gin, not {cfg.arch!r}")
        self.cfg = cfg
        self.opt = opt
        self.num_shards = num_shards
        self.mesh = mesh if mesh is not None else shard_mesh(num_shards)
        self.jit = jit
        self._fns: dict[tuple, object] = {}
        self.traces = 0
        self.registry = registry if registry is not None else MetricsRegistry()
        self._c_replans = self.registry.counter(
            "sampled_replans_total",
            desc="blocks repartitioned under a step-mate's wider bucket "
                 "config (pow2 bucket-boundary straddles)")
        self._h_skew = self.registry.histogram(
            "sampled_step_skew", unit="",
            bounds=(0.01, 0.02, 0.05, 0.1, 0.2, 0.3, 0.5, 0.75, 1.0),
            desc="per-step shard work skew: (max-min)/max of raw edge "
                 "counts over the step's loader batches")

    def __call__(self, state, batches: Sequence[TrainBatch]):
        if len(batches) != self.num_shards:
            raise ValueError(
                f"need {self.num_shards} batches per step, got {len(batches)}")
        work = [sum(b.raw_edges) for b in batches]
        self._h_skew.observe((max(work) - min(work)) / max(max(work), 1))
        key, operands, statics = self._stack(batches)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = self._build(statics)
        return fn(state, *operands)

    @property
    def num_buckets(self) -> int:
        return len(self._fns)

    # -------------- host-side uniformize + stack --------------

    def _replan(self, ent, cfg_t):
        """Repartition a cache entry's block under a different `AggConfig`
        (memoized on the entry): the rare batch whose pow2 node bucket —
        and therefore heuristic config — disagrees with its step-mates'.
        Static edge values are recovered from the schedule layout, exactly
        as `core.shard.shard_plan` does."""
        memo = ent.extras.setdefault("replans", {})
        plan = memo.get(cfg_t)
        if plan is None:
            self._c_replans.inc()
            from repro.core.partition import (partition_graph,
                                              transpose_graph)
            from repro.core.plan import Plan
            src = ent.plan
            ev = src.partition.edge_values_csr()
            part = partition_graph(src.graph, gs=cfg_t.gs, gpt=cfg_t.gpt,
                                   ont=cfg_t.ont, src_win=cfg_t.src_win,
                                   edge_vals=ev)
            part_bwd = eperm = None
            if src.partition_bwd is not None:
                gT, ev_t, eperm = transpose_graph(src.graph, ev)
                part_bwd = partition_graph(gT, gs=cfg_t.gs, gpt=cfg_t.gpt,
                                           ont=cfg_t.ont,
                                           src_win=cfg_t.src_win,
                                           edge_vals=ev_t)
            plan = memo[cfg_t] = Plan(
                graph=src.graph, partition=part, config=cfg_t,
                graph_props=None, arch=src.arch, perm=None, tuner=None,
                stats={}, reduce_dim_first=src.reduce_dim_first,
                partition_bwd=part_bwd, edge_perm_bwd=eperm)
        return plan

    def _stack(self, batches):
        import jax.numpy as jnp

        from repro.core.partition import pad_partition_tiles
        from repro.kernels.ops import sched_static, sched_statics_for

        statics, blocks, layer_shapes = [], [], []
        for l in range(self.cfg.num_layers):
            entries = [b.entries[l] for b in batches]
            plans = [e.plan for e in entries]
            # the widest node bucket's config fits every block of the step
            c = max(plans, key=lambda p: (p.partition.num_nodes,
                                          p.config.src_win)).config
            plans = [p if p.config == c else self._replan(e, c)
                     for e, p in zip(entries, plans)]
            n_t = max(p.partition.num_nodes for p in plans)
            t_f = max(p.partition.num_tiles for p in plans)
            parts = [pad_partition_tiles(p.partition, t_f) for p in plans]
            st_f = sched_statics_for(gs=c.gs, gpt=c.gpt, ont=c.ont,
                                     src_win=c.src_win, num_nodes=n_t)
            st_b = None
            arrs_b = None
            if plans[0].partition_bwd is not None:
                t_b = max(p.partition_bwd.num_tiles for p in plans)
                parts_b = [pad_partition_tiles(p.partition_bwd, t_b)
                           for p in plans]
                st_b = st_f
                arrs_b = self._stack_parts(parts_b, jnp)
            statics.append((st_f, st_b, c.dt, c.feat_dtype))
            blocks.append((self._stack_parts(parts, jnp), arrs_b))
            layer_shapes.append((n_t, t_f,
                                 None if st_b is None else arrs_b[0].shape))
        n0 = sched_static(statics[0][0], "num_nodes")
        n_last = sched_static(statics[-1][0], "num_nodes")
        feat = np.zeros((len(batches), n0, self.cfg.in_dim),
                        self.cfg.compute_dtype)
        labels = np.zeros((len(batches), n_last), np.int32)
        mask = np.zeros((len(batches), n_last), np.float32)
        for p, b in enumerate(batches):
            feat[p, : b.feat.shape[0]] = b.feat
            labels[p, : b.labels.shape[0]] = b.labels
            mask[p, : b.mask.shape[0]] = b.mask
        # bucket key = exactly what the executable depends on: the
        # uniformized statics + stacked operand shapes (NOT the raw
        # per-batch keys — their ordered product would fragment the cache)
        key = (tuple(statics), tuple(layer_shapes))
        return key, (jnp.asarray(feat), jnp.asarray(labels),
                     jnp.asarray(mask), tuple(blocks)), statics

    @staticmethod
    def _stack_parts(parts, jnp) -> tuple:
        # sched_arrays layout; edge members dropped (see SampledTrainStep)
        from repro.kernels.ops import _SCHED_ARRAY_FIELDS, N_TILE_FIELDS
        return tuple(
            jnp.stack([np.asarray(getattr(p, f)) for p in parts])
            for f in _SCHED_ARRAY_FIELDS[:N_TILE_FIELDS]
        ) + (None,) * (len(_SCHED_ARRAY_FIELDS) - N_TILE_FIELDS)

    # -------------- per-bucket executable --------------

    def _build(self, statics):
        import jax
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from repro.core.plan import Plan
        from repro.distributed.graph_shard import (SHARD_AXIS,
                                                   local_step_value_and_grad,
                                                   squeeze_shard_args)
        from repro.models.gnn import gnn_block_logits
        from repro.optim.adamw import adamw_update

        cfg, opt = self.cfg, self.opt

        def local_step(params, feat_l, labels_l, mask_l, blocks):
            feat_l, labels_l, mask_l = feat_l[0], labels_l[0], mask_l[0]
            execs = [Plan.executor_from_args(
                st, (squeeze_shard_args(a_f), squeeze_shard_args(a_b)),
                backend=cfg.backend)
                for st, (a_f, a_b) in zip(statics, blocks)]
            return local_step_value_and_grad(
                lambda p: gnn_block_logits(cfg, p, feat_l, execs),
                params, labels_l, mask_l)

        sm = shard_map(local_step, mesh=self.mesh,
                       in_specs=(P(), P(SHARD_AXIS), P(SHARD_AXIS),
                                 P(SHARD_AXIS), P(SHARD_AXIS)),
                       out_specs=(P(), P(), P()), check_vma=False)

        def step(state, feat, labels, mask, blocks):
            self.traces += 1                       # trace-time side effect
            params, opt_state = state
            grads, loss, metrics = sm(params, feat, labels, mask, blocks)
            params, opt_state, om = adamw_update(opt, grads, opt_state,
                                                 params)
            return (params, opt_state), {**metrics, **om}

        return jax.jit(step) if self.jit else step
