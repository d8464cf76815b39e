"""The Advisor — ties the whole §4-§7 loop together (paper Fig. 1/Fig. 7).

  input extractor -> performance evaluator (model+tuner) -> kernel & runtime
  crafter (renumbering + partition + kernel dispatch).

`advise()` is the one-call entry point: given a graph + GNN architecture it
returns an executable `Plan` with everything the runtime needs (the
`repro.core.plan` IR — `AggregationPlan` is its historical alias).

Planning records spans in the process tracer
(`repro.obs.process_tracer`), or in the tracer `plan_for` is handed:
``analyze`` (graph properties and the renumbering), ``tune`` and
``partition`` (forward, transposed and backward), each nested under
whatever span is open on that tracer; and it counts each plan's forward
tiles, padded slots and real edges in the same tracer's registry
(``plan_tiles_total``, ``plan_padded_slots_total``, ``plan_edges_total``),
with the node-block height and the aggregated feature width of the newest
plan (gauges ``plan_node_block_rows``, ``plan_agg_width``).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core.extractor import (GNNArchProps, GraphProps, extract_arch_props,
                                  extract_graph_props)
from repro.core.model import AggConfig, KernelModel
from repro.core.partition import (GroupPartition, partition_graph,
                                  partition_stats, transpose_graph)
from repro.core.plan import Plan
from repro.core.reorder import apply_renumbering, renumber
from repro.core.tuner import TunerResult, tune
from repro.graphs.csr import CSRGraph
from repro.obs.trace import SpanTracer, process_tracer

__all__ = ["AggregationPlan", "Plan", "advise", "analyze", "plan_for"]

# The plan dataclass itself now lives in `repro.core.plan` (the shared Plan
# IR); `AggregationPlan` is the historical name for the same type.
AggregationPlan = Plan


def analyze(g: CSRGraph, edge_vals: Optional[np.ndarray] = None, *,
            reorder: str = "auto", seed: int = 0):
    """Graph properties and the §6.1 renumbering decision.

    reorder="auto" applies renumbering unless the input already shows
    strong numbering locality (Type-II batched graphs arrive pre-localized —
    §8.2 notes their consecutive-ID structure) or community structure is too
    irregular to help (the `artist` pathology, §8.6.2).

    Returns ``(g_run, vals_run, perm, props)``: the graph and edge values
    to plan, renumbered when ``perm`` is not None, and their properties.
    """
    props = extract_graph_props(g)
    do_reorder = {"on": True, "off": False}.get(reorder)
    if do_reorder is None:
        already_local = props.numbering_spread < 0.02
        irregular = (props.community_size_stddev
                     > 1.5 * max(props.community_size_mean, 1.0))
        do_reorder = not already_local and not irregular
    if not do_reorder:
        return g, edge_vals, None, props
    perm = renumber(g, seed=seed)
    g_run = g.permute(perm)
    vals_run = (None if edge_vals is None
                else g.permute_edge_vals(perm, edge_vals))
    return (g_run, vals_run, perm,
            extract_graph_props(g_run, detect_communities=False))


def advise(g: CSRGraph, *, arch: str = "gcn", in_dim: int = 128,
           hidden_dim: int = 128, num_layers: int = 2,
           edge_vals: Optional[np.ndarray] = None,
           reorder: str = "auto",        # "auto" | "on" | "off"
           tune_mode: str = "model", tune_iters: int = 12,
           config: Optional[AggConfig] = None, seed: int = 0,
           with_backward: bool = False,
           feat_dtype: Optional[str] = None) -> AggregationPlan:
    """Run the full GNNAdvisor decision loop for one input: `analyze`
    (with its renumbering), then `plan_for` on the result."""
    with process_tracer().span("analyze"):
        g_run, vals_run, perm, props = analyze(g, edge_vals, reorder=reorder,
                                               seed=seed)
    plan = plan_for(g_run, arch=arch, in_dim=in_dim, hidden_dim=hidden_dim,
                    num_layers=num_layers, edge_vals=vals_run, config=config,
                    tune_mode=tune_mode, tune_iters=tune_iters, seed=seed,
                    props=props, with_backward=with_backward,
                    feat_dtype=feat_dtype)
    plan.perm = perm
    return plan


def plan_for(g: CSRGraph, *, arch: str = "gcn", in_dim: int = 128,
             hidden_dim: int = 128, num_layers: int = 2,
             edge_vals: Optional[np.ndarray] = None,
             config: Optional[AggConfig] = None,
             tune_mode: str = "model", tune_iters: int = 12,
             seed: int = 0, props: Optional[GraphProps] = None,
             with_backward: bool = False,
             feat_dtype: Optional[str] = None,
             tracer: Optional[SpanTracer] = None) -> AggregationPlan:
    """Pure planning: props -> (tune unless `config` given) -> partition.

    Unlike `advise` this never renumbers or mutates the input — it is the
    entry point the serving plan cache calls with memoized configs so a plan
    for a bucketed subgraph can be rebuilt without re-running the tuner.

    Arguments
    ---------
    g : CSRGraph — the graph to plan, in its final node numbering.
    arch : "gcn" | "gin" | "gat" — decides the §4.2 aggregation placement
        (which of in_dim/hidden_dim the kernel sees).
    edge_vals : optional (E,) float32 aligned with ``g.indices`` — static
        per-edge weights baked into the schedule (GCN's 1/sqrt(d_u d_v)).
    config : optional AggConfig — skip the tuner and partition with exactly
        these knobs (the plan-cache path).
    with_backward : also partition the TRANSPOSED graph under the same
        config and attach it as ``plan.partition_bwd`` (+``edge_perm_bwd``),
        so `PlanExecutor` can run `jax.grad` through the Pallas backends.
        Off by default — inference-only plans skip the extra partitioning.
    feat_dtype : optional feature/activation dtype policy ("float32" /
        "bfloat16").  Stamped onto the plan's `AggConfig` and handed to the
        tuner, which prices the halved window bytes and applies the
        dtype-tightened feasibility (Eq. 4 + dt alignment).  None keeps the
        given ``config``'s policy (or "float32" when tuning from scratch).
    tracer : where the ``analyze`` (only when ``props`` is None), ``tune``
        and ``partition`` spans and the plan counters go; None = the
        process tracer.

    Returns a `Plan`; feed it to `core.aggregate.PlanExecutor` (or call
    ``plan.executor(backend)``).

    Example
    -------
    >>> plan = plan_for(g, arch="gcn", edge_vals=vals, with_backward=True)
    >>> ex = PlanExecutor(plan)          # "pallas" on a TPU, else "xla"
    >>> grads = jax.grad(lambda f: ex(f).sum())(feat)      # transposed kernel
    """
    tr = tracer if tracer is not None else process_tracer()
    if props is None:
        with tr.span("analyze"):
            props = extract_graph_props(g, detect_communities=False)
    archp = extract_arch_props(arch, in_dim, hidden_dim, num_layers)
    # the §4.2 placement: the width the kernel aggregates at
    width = archp.hidden_dim if archp.reduce_dim_first else archp.in_dim
    tuner_res = None
    if config is None:
        with tr.span("tune"):
            tuner_res = tune(g, width,
                             props=props, mode=tune_mode, iters=tune_iters,
                             seed=seed, feat_dtype=feat_dtype or "float32")
        config = tuner_res.best
    else:
        if feat_dtype is not None and config.feat_dtype != feat_dtype:
            import dataclasses as _dc
            config = _dc.replace(config, feat_dtype=feat_dtype)
        # validate the FINAL dtype's dim-tile alignment for every caller-
        # supplied config (restamped or pre-stamped): an unaligned dt
        # would make dim_tile silently execute a different tile than the
        # plan/jit_statics/KernelModel claim.  Capacity feasibility stays
        # the caller's business — explicit configs are "exactly these
        # knobs" by contract.
        from repro.core.model import feat_dtype_align
        align = feat_dtype_align(config.feat_dtype)
        if config.dt % align:
            raise ValueError(
                f"config dt={config.dt} is not a multiple of the "
                f"{config.feat_dtype} alignment unit {align} — retune "
                f"with feat_dtype={config.feat_dtype!r} or pick an "
                f"aligned dt")
    with tr.span("partition"):
        part = partition_graph(g, gs=config.gs, gpt=config.gpt,
                               ont=config.ont, src_win=config.src_win,
                               edge_vals=edge_vals)
        part_bwd = edge_perm = None
        if with_backward:
            gT, vals_t, edge_perm = transpose_graph(g, edge_vals)
            part_bwd = partition_graph(gT, gs=config.gs, gpt=config.gpt,
                                       ont=config.ont, src_win=config.src_win,
                                       edge_vals=vals_t)
        stats = partition_stats(part)
    _count_plan(tr.registry, part, width)
    return Plan(
        graph=g, partition=part, config=config, graph_props=props,
        arch=archp, perm=None, tuner=tuner_res, stats=stats,
        reduce_dim_first=archp.reduce_dim_first,
        partition_bwd=part_bwd, edge_perm_bwd=edge_perm,
    )


def _count_plan(registry, part: GroupPartition, width: int) -> None:
    """The forward plan's work counts (`padded_slots_per_edge` is their
    ratio): tiles, slots the kernel visits, and real edges; and the node
    block height and the feature width it runs at."""
    for name, n in (("plan_tiles_total", part.num_tiles),
                    ("plan_padded_slots_total",
                     part.num_tiles * part.gpt * part.gs),
                    ("plan_edges_total", part.num_edges)):
        registry.counter(name, desc="forward plan counts "
                                    "(repro.core.advisor)").inc(int(n))
    registry.gauge("plan_node_block_rows",
                   desc="node-block height (ont) of the newest plan "
                        "(repro.core.advisor)").set(part.ont)
    registry.gauge("plan_agg_width",
                   desc="feature width the newest plan aggregates at, the "
                        "one it was tuned for (repro.core.advisor)").set(width)
