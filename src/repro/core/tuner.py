"""Estimating (paper §7.2) — hyper-parameter search with community profiling.

Implements the paper's three-step strategy:

  1. *Community profiling*: generate synthetic communities at 90/70/50%
     densities over the typical community sizes observed in the input, and
     evaluate candidate settings on them (here: with the white-box kernel
     model over EXACT tile counts from real partitions of the synthetic
     communities — the offline-profiling analogue).
  2. *Estimation*: score a given (graph, GNN) input with the calibrated
     model without building full schedules.
  3. *Evolutionary optimization*: population → keep elite → crossover +
     mutation, 10–15 iterations (paper: "10-15 iterations … enough").

The search space is the TPU knob set (gs, gpt, dt, src_win, ont)
constrained by the Eq. 3/4 feasibility re-derivations in `core.model`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Sequence

import numpy as np

from repro.core.extractor import GraphProps, extract_graph_props
from repro.core.model import (AggConfig, KernelModel, config_infeasibility,
                              paper_eq2_latency)
from repro.core.partition import partition_graph, partition_stats
from repro.graphs.csr import CSRGraph, random_community_graph
from repro.hw import TPUSpec, device_spec

__all__ = ["TunerResult", "evolve", "tune", "community_profile",
           "SEARCH_SPACE"]

SEARCH_SPACE = {
    "gs": [4, 8, 16, 32, 64],
    "gpt": [8, 16, 32, 64, 128],
    "dt": [64, 128, 256, 512],
    "src_win": [128, 256, 512, 1024, 2048],
    # node-block height (the paper's leader-node block, §5.2): tiles follow
    # (node block, window) buckets, so taller blocks pack more edges into
    # each tile.  Capped at 128: one MXU row block keeps the (ont, gpt)
    # scatter one-hot a single pass; 128 -> 256 removes few more tiles while
    # the scatter's rows double; and a mutation repartitions a whole block
    # per dirty row (`Plan.apply_delta`).
    "ont": [8, 16, 32, 64, 128],
}


@dataclasses.dataclass
class TunerResult:
    best: AggConfig
    best_score: float
    history: list  # (iteration, best_score)
    evaluations: int  # UNIQUE score-fn evaluations (duplicates are memoized)


def _random_config(rng: np.random.Generator,
                   base: AggConfig = AggConfig()) -> AggConfig:
    # the non-searched field (feat_dtype) rides along from `base`
    return dataclasses.replace(
        base, **{k: int(rng.choice(v)) for k, v in SEARCH_SPACE.items()})


def _crossover(a: AggConfig, b: AggConfig, rng: np.random.Generator) -> AggConfig:
    return dataclasses.replace(
        a, **{k: getattr(a if rng.random() < 0.5 else b, k)
              for k in SEARCH_SPACE})


def _mutate(c: AggConfig, rng: np.random.Generator, p: float = 0.25) -> AggConfig:
    kw = dataclasses.asdict(c)
    for k, space in SEARCH_SPACE.items():
        if rng.random() < p:
            vals = space
            i = vals.index(kw[k]) if kw[k] in vals else len(vals) // 2
            j = int(np.clip(i + rng.integers(-1, 2), 0, len(vals) - 1))
            kw[k] = vals[j]
    return AggConfig(**kw)


def evolve(score_fn: Callable[[AggConfig], float], *, pop: int = 16,
           iters: int = 12, elite: int = 4, seed: int = 0,
           base: AggConfig = AggConfig(),
           infeasibility_fn: Optional[
               Callable[[AggConfig], Optional[str]]] = None,
           max_attempts_per_member: int = 64) -> TunerResult:
    """Generic evolutionary loop (lower score = better).

    Duplicate configs are never re-scored: crossover of a small elite
    re-produces identical `AggConfig`s constantly, and profile-mode score
    functions build REAL partitions per call — a seen-map turns those
    repeats into dict hits.  ``TunerResult.evaluations`` therefore counts
    UNIQUE score-function evaluations (the tuner's true cost).

    ``base`` seeds the non-searched config field (feat_dtype); ``infeasibility_fn`` (reason string or None = feasible)
    overrides the default `config_infeasibility` — e.g. one bound to a
    small-VMEM `TPUSpec` or a bf16-tightened Eq. 4.  Rejection sampling is
    BOUNDED: a sparse-but-nonempty feasible region proceeds with the
    partial population it found; a fully infeasible space raises a
    `RuntimeError` naming the violated constraints instead of spinning
    forever."""
    rng = np.random.default_rng(seed)
    if infeasibility_fn is None:
        infeasibility_fn = config_infeasibility
    feasible_fn = lambda c: infeasibility_fn(c) is None
    population = []
    attempts, reasons = 0, []
    budget = max_attempts_per_member * pop
    while len(population) < pop:
        if attempts >= budget:
            if population:
                # sparse feasible region: run with what we found rather
                # than abort (the elites will breed inside it)
                break
            uniq = list(dict.fromkeys(reasons[-16:]))
            raise RuntimeError(
                f"tuner search space is infeasible: {attempts} rejection-"
                f"sampling attempts produced {len(population)}/{pop} "
                f"feasible configs (feat_dtype={base.feat_dtype}).  "
                f"Sample rejection reasons: {uniq}")
        c = _random_config(rng, base)
        attempts += 1
        reason = infeasibility_fn(c)
        if reason is None:
            population.append(c)
        else:
            reasons.append(reason)
    seen: dict[AggConfig, float] = {}

    def score(c: AggConfig) -> float:
        s = seen.get(c)
        if s is None:
            s = seen[c] = score_fn(c)
        return s

    history = []
    scored = [(score(c), c) for c in population]
    for it in range(iters):
        scored.sort(key=lambda x: x[0])
        history.append((it, scored[0][0]))
        keep = [c for _, c in scored[:elite]]
        children = []
        child_attempts = 0
        # the elites are feasible, so feasible children are normally easy to
        # produce — but a tight feasibility surface (bf16 Eq. 4 on a small
        # part) can make mutation near-always-reject; bound the attempts and
        # continue with a smaller brood rather than spin
        while (len(children) < pop - elite
               and child_attempts < max_attempts_per_member * pop):
            a, b = rng.choice(len(keep), 2, replace=True)
            child = _mutate(_crossover(keep[a], keep[b], rng), rng)
            child_attempts += 1
            if feasible_fn(child):
                children.append(child)
        scored = scored[:elite] + [(score(c), c) for c in children]
    scored.sort(key=lambda x: x[0])
    history.append((iters, scored[0][0]))
    return TunerResult(best=scored[0][1], best_score=scored[0][0],
                       history=history, evaluations=len(seen))


def community_profile(community_sizes: Sequence[int], dim: int, *,
                      densities: Sequence[float] = (0.9, 0.7, 0.5),
                      seed: int = 0) -> Callable[[AggConfig], float]:
    """Step 1: build a profiling score over synthetic communities.

    Returns a score function that evaluates a config by building REAL
    partitions over the synthetic community graphs and pricing them with the
    white-box model over exact tile counts.
    """
    graphs: list[CSRGraph] = []
    for cs in community_sizes:
        for rho in densities:
            g = random_community_graph(max(4, 2048 // max(cs, 2)), cs,
                                       p_intra=rho, p_inter_edges_per_node=0.2,
                                       seed=seed)
            graphs.append(g)
    props = [extract_graph_props(g, detect_communities=False) for g in graphs]
    km = KernelModel()

    def score(cfg: AggConfig) -> float:
        tot = 0.0
        for g, pr in zip(graphs, props):
            p = partition_graph(g, gs=cfg.gs, gpt=cfg.gpt, ont=cfg.ont,
                                src_win=cfg.src_win)
            tot += km.latency(pr, dim, cfg, tiles=p.num_tiles)
        return tot / len(graphs)

    return score


def tune(g: CSRGraph, dim: int, *, props: GraphProps | None = None,
         mode: str = "model", iters: int = 12, pop: int = 16,
         seed: int = 0, feat_dtype: str = "float32",
         hw: TPUSpec | None = None) -> TunerResult:
    """Pick (gs, gpt, dt, src_win, ont) for a given graph and embedding dim.

    mode="model":   white-box model over predicted tile counts (fast; §7.1).
    mode="profile": score by building real partitions (exact tiles; §7.2).
    mode="paper":   literal Eq. 2 surrogate (fidelity baseline).

    ``feat_dtype`` is the feature/activation dtype policy: every candidate
    is stamped with it, the kernel model prices its ``bytes_feat`` honestly
    (a bf16 feature window moves half the DMA bytes, so wider ``src_win``/
    ``dt`` become profitable), and feasibility uses the dtype-tightened
    Eq. 4 + alignment constraints — the returned ``best`` therefore passes
    ``config_is_feasible`` under its own dtype at width ``dim``.  ``hw``
    defaults to the local chip's spec (`repro.hw.device_spec`).
    """
    hw = hw or device_spec()
    pr = props or extract_graph_props(g, detect_communities=False)
    km = KernelModel(hw=hw)
    base = AggConfig(feat_dtype=feat_dtype)
    if mode == "model":
        score = lambda c: km.latency(pr, dim, c)
    elif mode == "profile":
        def score(c: AggConfig) -> float:
            p = partition_graph(g, gs=c.gs, gpt=c.gpt, ont=c.ont, src_win=c.src_win)
            return km.latency(pr, dim, c, tiles=p.num_tiles)
    elif mode == "paper":
        score = lambda c: paper_eq2_latency(pr, dim, c)
    else:
        raise ValueError(mode)
    return evolve(score, pop=pop, iters=iters, seed=seed, base=base,
                  infeasibility_fn=lambda c: config_infeasibility(
                      c, hw=hw, dim=dim))
