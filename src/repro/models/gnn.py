"""GNN models (the paper's own benchmarks): GCN, GIN and GAT built on the
GNNAdvisor aggregation engine.

Faithful to the paper's §4.2 placement rule:
  * GCN (type-1, order-independent, no edge values beyond the symmetric
    norm): REDUCE DIM FIRST — X @ W happens before aggregation, so the
    kernel aggregates the small hidden dim.
  * GIN (type-2-ish: (1+eps) self-weighting): aggregation runs on the FULL
    input dim before the MLP update, as the paper describes.
  * GAT (type-2: edge values computed every forward, arXiv:1710.10903):
    each layer projects first (X W, its heads side by side), scores every
    edge of A + I per head, and aggregates the projection with one value
    per edge and head — the kernel's head-indexed path.

Edge values: GCN uses the symmetric normalization 1/sqrt(d_u d_v) with
self-loops folded into the group schedule as weighted edges, so the whole
\\hat{A} X W happens inside the group_aggregate kernel.

Training runs on ANY backend: `build_gnn` attaches the transposed-schedule
backward partition whenever the backend is a Pallas one (or when
``with_backward=True`` is forced), so `jax.grad` of `GNNModel.loss` flows
through the group-aggregate kernel itself — backward aggregation is the
same kernel over the transposed graph's schedule (see docs/training.md).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.advisor import analyze, plan_for
from repro.core.aggregate import PlanExecutor
from repro.core.plan import Plan
from repro.graphs.csr import CSRGraph
from repro.kernels.ops import head_columns, resolve_backend, sched_arrays
from repro.obs.compile import install_compile_listener
from repro.obs.trace import process_tracer

Pytree = Any

__all__ = ["GNNConfig", "gcn_edge_values", "aggregation_graph", "build_gnn",
           "init_gnn_params", "GNNModel", "make_gnn_train_step", "planted_labels",
           "gnn_block_logits", "gnn_block_loss", "gnn_sharded_logits",
           "structural_labels"]


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    arch: str = "gcn"           # "gcn" | "gin" | "gat"
    in_dim: int = 128
    hidden_dim: int = 64
    num_classes: int = 8
    num_layers: int = 2
    gin_eps: float = 0.0
    gat_slope: float = 0.2      # LeakyReLU slope for attention logits
    # GAT: attention heads per layer (default one each) and each head's
    # width per layer (default ``hidden_dim``, the last layer's
    # ``num_classes``); the last layer averages its heads; an identity
    # skip runs across each layer listed in ``skip_layers`` (0-based;
    # widths must match)
    heads: tuple = ()
    head_dims: tuple = ()
    skip_layers: tuple = ()
    # the task: one class a node (softmax cross-entropy), or (N, C) 0/1
    # targets (sigmoid binary cross-entropy, every class its own)
    multi_label: bool = False
    # "xla" | "pallas" | "pallas_interpret"; None resolves by platform at
    # construction ("pallas" on a TPU, "xla" elsewhere)
    backend: Optional[str] = None
    # feature/activation dtype policy: "float32" | "bfloat16".  Parameters
    # and loss stay float32 (mixed precision with an f32 master copy);
    # matmuls and the aggregation kernel run on feat_dtype operands with
    # f32 accumulation, and logits are cast back to f32 before the loss.
    feat_dtype: str = "float32"

    def __post_init__(self):
        object.__setattr__(self, "backend", resolve_backend(self.backend))
        for f in ("heads", "head_dims", "skip_layers"):
            object.__setattr__(self, f, tuple(int(v) for v in getattr(self, f)))
        if self.arch != "gat":
            if self.heads or self.head_dims or self.skip_layers:
                raise ValueError("heads, head_dims and skip_layers are GAT's "
                                 f"keys, not {self.arch!r}'s")
            return
        L = self.num_layers
        heads = self.heads or (1,) * L
        dims = self.head_dims or (self.hidden_dim,) * (L - 1) + (
            self.num_classes,)
        object.__setattr__(self, "heads", heads)
        object.__setattr__(self, "head_dims", dims)
        if len(heads) != L or len(dims) != L:
            raise ValueError(f"{L} layers need {L} heads and head_dims, got "
                             f"{heads} and {dims}")
        if dims[-1] != self.num_classes:
            raise ValueError(f"the last layer gives {dims[-1]} columns, not "
                             f"num_classes={self.num_classes}")
        for i in self.skip_layers:
            if i not in range(L) or self.gat_in_dim(i) != self.gat_out_dim(i):
                raise ValueError(f"no identity skip across layer {i}: its "
                                 "input and output widths must match")

    @property
    def compute_dtype(self):
        return jnp.dtype(self.feat_dtype)

    def gat_in_dim(self, i: int) -> int:
        """Columns into GAT layer ``i``: the features, else the previous
        layer's heads side by side."""
        return self.in_dim if i == 0 else self.heads[i - 1] * self.head_dims[i - 1]

    def gat_out_dim(self, i: int) -> int:
        """Columns out of GAT layer ``i`` (its heads averaged on the last
        layer)."""
        if i == self.num_layers - 1:
            return self.head_dims[i]
        return self.heads[i] * self.head_dims[i]

    @property
    def agg_hidden_dim(self) -> int:
        """The projected width the kernel aggregates at, which `plan_for`
        tunes for: GAT's widest layer of heads x head width, else
        ``hidden_dim`` (GIN tunes at ``in_dim`` regardless)."""
        if self.arch == "gat":
            return max(h * d for h, d in zip(self.heads, self.head_dims))
        return self.hidden_dim

    @property
    def edge_heads(self) -> int:
        """Values an edge carries in the widest aggregation: GAT's most
        heads, else the one static value."""
        return max(self.heads) if self.arch == "gat" else 1


def _mmul(a: jax.Array, b: jax.Array, cdt) -> jax.Array:
    """Policy matmul: operands at the compute dtype, accumulation ALWAYS
    f32 (`preferred_element_type`), result cast back to the compute dtype
    so activations stay 16-bit between layers.  A no-op chain for f32."""
    return jnp.dot(a.astype(cdt), b.astype(cdt),
                   preferred_element_type=jnp.float32).astype(cdt)


def gcn_edge_values(g: CSRGraph) -> tuple[CSRGraph, np.ndarray]:
    """Add self-loops and compute \\hat{A}'s 1/sqrt(d_u d_v) edge weights."""
    g2 = g.with_self_loops()
    deg = g2.degrees.astype(np.float64)
    inv_sqrt = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    rows, cols = g2.to_coo()
    vals = (inv_sqrt[rows] * inv_sqrt[cols]).astype(np.float32)
    return g2, vals


def aggregation_graph(g: CSRGraph, arch: str):
    """The graph a model of ``arch`` aggregates over, with its static edge
    values: GCN's self-loops and \\hat{A} weights, GAT's A + I (attention
    makes its values every forward), GIN's A (None: unit weights)."""
    if arch == "gcn":
        return gcn_edge_values(g)
    if arch == "gat":
        return g.with_self_loops(), None
    return g, None


@dataclasses.dataclass
class GNNModel:
    cfg: GNNConfig
    plan: Plan
    executor: PlanExecutor
    params: Pytree

    def logits(self, params: Pytree, feat: jax.Array) -> jax.Array:
        """feat (N, in_dim) in the plan's node order -> (N, num_classes)
        float32 (intermediate activations follow ``cfg.feat_dtype``)."""
        cfg = self.cfg
        cdt = cfg.compute_dtype
        x = feat
        for i in range(cfg.num_layers):
            w = params[f"w{i}"]
            last = i == cfg.num_layers - 1
            if cfg.arch == "gcn":
                # type-1: reduce dim first, aggregate the projected features
                x = self.executor(_mmul(x, w, cdt))
            elif cfg.arch == "gat":
                h = self._attend(params, i, x)                # (N, H, F)
                h = h.mean(axis=1) if last else h.reshape(h.shape[0], -1)
                if not last:
                    h = jax.nn.elu(h)
                if i in cfg.skip_layers:
                    h = h + x.astype(jnp.float32)
                x = h.astype(cdt)
            else:
                # GIN: aggregate full-dim, then (1+eps)*x + agg -> 2-layer MLP
                agg = self.executor(x.astype(cdt))
                h = (1.0 + cfg.gin_eps) * x.astype(cdt) + agg.astype(cdt)
                x = _mmul(jax.nn.relu(_mmul(h, w, cdt)),
                          params[f"w{i}b"], cdt)
            if cfg.arch == "gcn" and not last:
                x = jax.nn.relu(x)
        return x.astype(jnp.float32)

    def _attend(self, params: Pytree, i: int, x: jax.Array) -> jax.Array:
        """GAT layer ``i``'s heads before they are joined: (N, H, F) f32.

        Type-2 aggregation with DYNAMIC per-edge values flowing through the
        same group schedule (paper §4.2: "edge features applied to each
        neighbor"), one value per edge and head.  Attention scores stay
        f32 — exp() of bf16 logits is the classic softmax-instability
        trap."""
        cfg, cdt = self.cfg, self.cfg.compute_dtype
        heads, width = cfg.heads[i], cfg.head_dims[i]
        rows, cols = self._edges
        n = x.shape[0]
        z = _mmul(x, params[f"w{i}"], cdt)                    # (N, H*F)
        zh = z.astype(jnp.float32).reshape(n, heads, width)
        # each head's dot with its column of a: a fused f32 multiply-sum
        # over the head's lanes (a matmul would transpose z first)
        score = lambda a: jnp.sum(zh * a.T[None], axis=-1)   # (N, H)
        e = jax.nn.leaky_relu(score(params[f"a{i}d"])[rows]
                              + score(params[f"a{i}s"])[cols],
                              negative_slope=cfg.gat_slope)   # (E, H)
        # the softmax over each destination's edges, shifted by that
        # destination's own max: a constant of the softmax, so no gradient
        emax = jax.lax.stop_gradient(jax.ops.segment_max(
            e, rows, num_segments=n, indices_are_sorted=True))
        wgt = jnp.exp(e - emax[rows])
        # the softmax sums are the aggregation of a column of ones a head:
        # beside each head's columns where the kernel's padding of a head
        # has room for one more, else in a pass of their own
        ones = jnp.ones((n, heads, 1), cdt)
        cols_of = lambda w: head_columns(self.executor.dt, heads * w, heads,
                                         cdt)
        if cols_of(width + 1) == cols_of(width):
            both = self.executor.aggregate_edges(
                jnp.concatenate([z.reshape(n, heads, width), ones], axis=2)
                .reshape(n, heads * (width + 1)), wgt)
            both = both.reshape(n, heads, width + 1)
            num, den = both[..., :width], both[..., width]
        else:
            num = self.executor.aggregate_edges(z, wgt)
            den = self.executor.aggregate_edges(ones[..., 0], wgt)
        # a node with an edge sums at least its max edge's exp(0) = 1; one
        # with none (a padded subgraph's rows) reads 0, not 0/0
        den = jnp.maximum(den.astype(jnp.float32), 1e-30)
        return num.astype(jnp.float32).reshape(n, heads, width) / den[..., None]

    @property
    def _edges(self):
        if not hasattr(self, "_edges_cache"):
            rows, cols = self.plan.graph.to_coo()
            object.__setattr__(self, "_edges_cache",
                               (jnp.asarray(rows), jnp.asarray(cols)))
        return self._edges_cache

    def rebind(self, plan: Plan, *,
               backend: Optional[str] = None) -> "GNNModel":
        """Same weights, different graph: run this model on another plan
        (the serving path — a prebuilt model applied to a batched
        ego-subgraph whose plan came from the plan cache)."""
        executor = PlanExecutor(plan, backend=backend or self.cfg.backend)
        return GNNModel(cfg=self.cfg, plan=plan, executor=executor,
                        params=self.params)

    def loss(self, params: Pytree, feat: jax.Array, labels: jax.Array,
             mask: Optional[jax.Array] = None):
        return _task_loss(self.cfg, self.logits(params, feat), labels, mask)


def _task_loss(cfg: GNNConfig, lg: jax.Array, labels: jax.Array,
               mask: Optional[jax.Array] = None):
    """The configured task's masked loss and accuracy over (N, C) logits."""
    if cfg.multi_label:
        return _masked_bce(lg, labels, mask)
    return _masked_xent(lg, labels, mask)


def _masked_bce(lg: jax.Array, labels: jax.Array,
                mask: Optional[jax.Array] = None):
    """Masked mean sigmoid binary cross-entropy, ``softplus(z) - y z`` over
    every class of every node, and per-class accuracy, over (N, C) logits
    and 0/1 targets."""
    y = labels.astype(jnp.float32)
    per = jnp.mean(jax.nn.softplus(lg) - y * lg, axis=-1)
    hit = jnp.mean(((lg > 0) == (y > 0.5)).astype(jnp.float32), axis=-1)
    if mask is None:
        mask = jnp.ones_like(per)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (per * mask).sum() / denom
    return loss, {"loss": loss, "accuracy": (hit * mask).sum() / denom}


def _masked_xent(lg: jax.Array, labels: jax.Array,
                 mask: Optional[jax.Array] = None):
    """Masked softmax cross-entropy + accuracy over (N, C) logits."""
    logp = jax.nn.log_softmax(lg, axis=-1)
    per = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    if mask is None:
        mask = jnp.ones_like(per)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (per * mask).sum() / denom
    acc = ((lg.argmax(-1) == labels) * mask).sum() / denom
    return loss, {"loss": loss, "accuracy": acc}


def gnn_block_logits(cfg: GNNConfig, params: Pytree, feat: jax.Array,
                     executors) -> jax.Array:
    """Sampled mini-batch forward: one bipartite block per layer.

    ``executors[l]`` aggregates layer l's block (square CSR with the
    block's source frontier as node set, dst nodes occupying the leading
    consecutive local ids — `repro.sampling.neighbor`).  ``feat`` is
    (num_src_0, in_dim) in block 0's local order.  After each layer the
    activation is cropped to the next block's (padded) source count; the
    rows dropped are exactly the nodes no deeper layer consumes.  Returns
    (num_nodes_last, num_classes) — rows beyond the seed count are padding
    (mask them in the loss).

    GCN keeps its reduce-dim-first placement; GIN aggregates full-dim then
    applies its MLP.  GAT is full-graph only: its per-destination softmax
    needs each block's own edge list and per-head edge values, which the
    sampled path does not carry.
    """
    if cfg.arch not in ("gcn", "gin"):
        raise NotImplementedError(
            f"sampled block forward supports gcn/gin, not {cfg.arch!r}: "
            "GAT trains full-graph only (build_gnn + make_gnn_train_step)")
    cdt = cfg.compute_dtype
    x = feat
    for i, ex in enumerate(executors):
        w = params[f"w{i}"]
        if cfg.arch == "gcn":
            x = ex(_mmul(x, w, cdt))
            if i < cfg.num_layers - 1:
                x = jax.nn.relu(x)
        else:
            agg = ex(x.astype(cdt))
            h = (1.0 + cfg.gin_eps) * x.astype(cdt) + agg.astype(cdt)
            x = _mmul(jax.nn.relu(_mmul(h, w, cdt)), params[f"w{i}b"], cdt)
        if i + 1 < len(executors):
            x = x[: executors[i + 1].sched.num_nodes]
    return x.astype(jnp.float32)


def gnn_block_loss(cfg: GNNConfig, params: Pytree, feat: jax.Array,
                   labels: jax.Array, mask: jax.Array, executors):
    """Masked loss over a sampled mini-batch's block chain (labels/mask are
    (num_nodes_last,); mask is 0 on shape-bucket padding rows)."""
    return _task_loss(cfg, gnn_block_logits(cfg, params, feat, executors),
                      labels, mask)


def gnn_sharded_logits(cfg: GNNConfig, params: Pytree, feat_local: jax.Array,
                       executor, *, axis: str = "shard") -> jax.Array:
    """Per-device body of the sharded full-graph forward (run it inside
    `shard_map` — `repro.distributed.graph_shard` builds the wrapper).

    ``feat_local`` is this shard's (n_local, in_dim) row slice of the
    parent plan's node order; ``executor`` aggregates the shard's OUTPUT
    rows from the full gathered feature matrix (a sub-`Plan` executor from
    `core.shard.shard_plan` — schedule num_nodes == padded global N, local
    rows leading).  Each layer all-gathers the current activations over
    ``axis`` (the halo exchange — its transpose is the psum-scatter that
    returns cotangents to their owner shards), aggregates locally, and
    slices back to the local range.  Returns (n_local, num_classes).
    """
    if cfg.arch not in ("gcn", "gin"):
        raise NotImplementedError(
            f"sharded forward supports gcn/gin, not {cfg.arch!r}: GAT runs "
            "on one device only (its attention needs every edge's scores "
            "beside the destination's other edges)")
    cdt = cfg.compute_dtype
    n_local = feat_local.shape[0]
    x = feat_local
    for i in range(cfg.num_layers):
        w = params[f"w{i}"]
        if cfg.arch == "gcn":
            # project BEFORE the exchange, in the policy dtype — under
            # bf16 the halo all-gather moves half the inter-device bytes
            z = _mmul(x, w, cdt)
            z_full = jax.lax.all_gather(z, axis, axis=0, tiled=True)
            x = executor(z_full)[:n_local]
            if i < cfg.num_layers - 1:
                x = jax.nn.relu(x)
        else:
            x_full = jax.lax.all_gather(x.astype(cdt), axis,
                                        axis=0, tiled=True)
            agg = executor(x_full)[:n_local]
            h = (1.0 + cfg.gin_eps) * x.astype(cdt) + agg.astype(cdt)
            x = _mmul(jax.nn.relu(_mmul(h, w, cdt)), params[f"w{i}b"], cdt)
    return x.astype(jnp.float32)


def structural_labels(g: CSRGraph, num_classes: int) -> np.ndarray:
    """Degree-quantile node labels — a deterministic, aggregation-learnable
    task that needs NO full-graph teacher forward (the `planted_labels`
    teacher is itself a full-batch inference pass, which is exactly what
    full-size Type III graphs cannot afford; sampled training uses this)."""
    deg = g.degrees.astype(np.float64)
    qs = np.quantile(deg, np.linspace(0, 1, num_classes + 1)[1:-1])
    return np.searchsorted(qs, deg, side="right").astype(np.int32)


def build_gnn(g: CSRGraph, cfg: GNNConfig, *, key: Optional[jax.Array] = None,
              reorder: str = "auto", tune_iters: int = 6,
              config=None, seed: int = 0,
              with_backward: Optional[bool] = None,
              with_executor: bool = True) -> GNNModel:
    """Run the advisor on the graph, build the plan executor + parameters.

    with_backward: attach the transposed-schedule backward partition so
    `jax.grad` works through the Pallas kernel.  Default (None) enables it
    exactly when the backend is a Pallas one — XLA differentiates natively,
    and inference-only Pallas use can pass False to skip the extra
    partitioning pass.

    with_executor=False skips instantiating the single-device executor
    (which uploads the full device-resident schedule): callers that only
    want the plan + params — sharded training re-plans per shard — avoid
    pinning a never-executed full-graph schedule on device 0.  The
    returned model's ``executor`` is None; don't call its ``logits``.

    Spans go to the process tracer (`repro.obs.process_tracer`): ``plan``
    around the whole call, and under it ``analyze`` (the aggregation graph
    and its edge values, graph properties, the renumbering decision and
    any renumbering), ``tune``, ``partition`` (forward, transposed and
    backward) and ``executor`` (the device schedule's upload, synced, and
    parameter init).  Its registry's gauge ``plan_edge_heads``
    (`GNNConfig.edge_heads`) describes the newest model, beside the plan's
    own gauges (``plan_agg_width``, the width the plan was tuned for, is
    set by `plan_for`).
    """
    tr = process_tracer()
    if with_backward is None:
        with_backward = cfg.backend.startswith("pallas")
    with tr.span("plan"):
        with tr.span("analyze"):
            g, vals = aggregation_graph(g, cfg.arch)
            g_run, vals_run, perm, props = analyze(g, vals, reorder=reorder,
                                                   seed=seed)
        plan = plan_for(g_run, arch=cfg.arch, in_dim=cfg.in_dim,
                        hidden_dim=cfg.agg_hidden_dim,
                        num_layers=cfg.num_layers,
                        edge_vals=vals_run, config=config,
                        tune_iters=tune_iters, seed=seed, props=props,
                        with_backward=with_backward,
                        feat_dtype=cfg.feat_dtype)
        plan.perm = perm
        tr.registry.gauge(
            "plan_edge_heads", desc="values per edge in the newest model's "
            "widest aggregation (repro.models.gnn)").set(cfg.edge_heads)
        with tr.span("executor", block=True) as sp:
            executor = (PlanExecutor(plan, backend=cfg.backend)
                        if with_executor else None)
            params = init_gnn_params(
                cfg, key if key is not None else jax.random.PRNGKey(seed))
            if executor is None:
                sp.sync(params)
            else:
                sp.sync((params, sched_arrays(executor.sched),
                         sched_arrays(executor.sched_bwd)))
    return GNNModel(cfg=cfg, plan=plan, executor=executor, params=params)


def planted_labels(g: CSRGraph, cfg: GNNConfig, feat: np.ndarray, *,
                   seed: int = 7) -> np.ndarray:
    """Labels from a frozen random teacher of the same architecture — a
    learnable planted node-classification task for the train drivers."""
    teacher = build_gnn(g, dataclasses.replace(cfg, backend="xla"),
                        reorder="off", tune_iters=2, seed=seed)
    return np.asarray(
        teacher.logits(teacher.params, jnp.asarray(feat)).argmax(-1))


def make_gnn_train_step(model: GNNModel, opt, *, jit: bool = True):
    """Build the `Trainer`-shaped step function for full-graph GNN training.

    opt: an `AdamWConfig`.  Returns ``step_fn(state, batch)`` where state is
    ``(params, opt_state)`` and batch is ``{"feat", "labels"[, "mask"]}`` in
    the plan's node order.  The value-and-grad runs through the model's
    configured backend — on "pallas"/"pallas_interpret" the backward pass is
    the transposed-schedule kernel, so the plan must carry
    ``partition_bwd`` (`build_gnn` attaches it for Pallas backends).

    The jitted step is named ``gnn_train_step``; building it installs the
    process's compile listener (`repro.obs.compile`), which times its
    trace, lowering and backend compile and counts its cache hits.
    """
    from repro.optim.adamw import adamw_update

    if model.cfg.backend.startswith("pallas") and (
            model.plan is not None and model.plan.partition_bwd is None):
        raise ValueError(
            "training on a Pallas backend needs a backward schedule: "
            "build the model with with_backward=True")

    # the name JAX's compile stages carry (`repro.obs.compile` labels its
    # spans and counters with it): stable, and distinct from other steps
    def gnn_train_step(state, batch):
        params, opt_state = state
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch["feat"], batch["labels"],
                                      batch.get("mask"))
        params, opt_state, om = adamw_update(opt, grads, opt_state, params)
        return (params, opt_state), {**metrics, **om}

    if not jit:
        return gnn_train_step
    install_compile_listener()
    return jax.jit(gnn_train_step)


def init_gnn_params(cfg: GNNConfig, key: jax.Array) -> Pytree:
    """Parameter init alone — the serving engine builds params without ever
    planning the full resident graph (plans come per-subgraph from the
    cache)."""
    params = {}
    dims = [cfg.in_dim] + [cfg.hidden_dim] * (cfg.num_layers - 1) + [cfg.num_classes]
    k = key
    for i in range(cfg.num_layers):
        k, k1, k2, k3 = jax.random.split(k, 4)
        fan_in = dims[i]
        if cfg.arch == "gcn":
            params[f"w{i}"] = (jax.random.normal(k1, (dims[i], dims[i + 1]))
                               / np.sqrt(fan_in)).astype(jnp.float32)
        elif cfg.arch == "gat":
            # w{i} projects to the heads side by side; a{i}s / a{i}d hold
            # each head's source / destination attention vector as a column
            fan_in, hd = cfg.gat_in_dim(i), cfg.head_dims[i]
            params[f"w{i}"] = (jax.random.normal(k1, (fan_in, cfg.heads[i] * hd))
                               / np.sqrt(fan_in)).astype(jnp.float32)
            params[f"a{i}s"] = (jax.random.normal(k2, (hd, cfg.heads[i]))
                                / np.sqrt(hd)).astype(jnp.float32)
            params[f"a{i}d"] = (jax.random.normal(k3, (hd, cfg.heads[i]))
                                / np.sqrt(hd)).astype(jnp.float32)
        else:
            params[f"w{i}"] = (jax.random.normal(k1, (dims[i], cfg.hidden_dim))
                               / np.sqrt(fan_in)).astype(jnp.float32)
            params[f"w{i}b"] = (jax.random.normal(k2, (cfg.hidden_dim, dims[i + 1]))
                                / np.sqrt(cfg.hidden_dim)).astype(jnp.float32)
    return params
