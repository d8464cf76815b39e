"""Dynamic-graph benchmark: incremental plan maintenance vs full rebuild.

The mutable-graphs tentpole's headline claim, measured: applying a small
interaction-stream delta (~1% of the nodes' worth of edge churn) through
`Plan.apply_delta` — which repartitions only the dirty node blocks and
keeps every clean tile verbatim (`repro.core.incremental`) — must beat
the from-scratch `plan_for` pipeline by >= 10x on a reddit-scale graph,
while aggregating EXACTLY like a scratch rebuild (parity <= 1e-5 on
forward and transposed-backward outputs).

Two baselines per delta, both reported:

  * ``t_scratch_ms`` — the full from-scratch `plan_for` pipeline
    (property extraction + tuner + partition), i.e. what a cold rebuild
    of the mutated graph actually costs.  This is what the incremental
    path amortizes and what the >= 10x gate compares against.
  * ``t_repartition_ms`` — `plan_for` with the resident plan's config
    pinned (partitioning only).  The patch still wins, but only by the
    sort-vs-memcpy ratio (~2-4x): clean tiles are *copied*, not
    re-derived, so the floor is the padded-tile memcpy, while the
    pinned rebuild re-sorts the same slots.

    PYTHONPATH=src python -m benchmarks.bench_dynamic [--smoke] \
        [--json-out BENCH_dynamic.json]

CSV contract per line: name,us_per_call,derived (us_per_call = one
`Plan.apply_delta` call).  ``--json-out`` writes the machine-validated
``BENCH_dynamic.json`` document (schema ``repro.bench_dynamic/v1``;
`tools.validate_metrics` checks it): run context, one config row per
applied delta, and the incremental-vs-scratch comparison verdict CI
asserts on.  ``--smoke`` shrinks the graph for CI; the >= 10x speedup
gate applies to the full-size run (small graphs amortize less), the
parity gate applies everywhere.

The full-size profile pins the resident plan's config rather than
letting the tuner pick it: at full reddit the model-mode tuner lands on
``gs=8, gpt=128, src_win=2048, ont=8`` whose tile padding factor is
~171x — ~38 GB of tile tensors per schedule, which is not a deployable
resident plan (and whose padded-slot memcpy swamps *both* the patch and
the pinned rebuild).  The pinned config keeps padding ~6x with the same
dirty-block granularity (ont=8).  The from-scratch baseline is NOT
pinned — a cold rebuild re-runs the whole advisor loop, tuner included.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

SCHEMA = "repro.bench_dynamic/v1"

CONFIG_KEYS = ("dataset", "backward", "nodes", "edges", "delta_edges",
               "dirty_frac", "mode", "t_scratch_ms", "t_repartition_ms",
               "t_incremental_ms", "speedup", "repartition_speedup",
               "parity")

PARITY_TOL = 1e-5


def _profile(smoke: bool) -> dict:
    # smoke bar is a sanity floor, not the headline: at 30k nodes the
    # advisor pipeline (props + tuner) is cheap relative to the patch, so
    # the amortization margin only opens up at full size (measured: 2.2-4x
    # at 30k vs ~63x at full reddit)
    if smoke:
        return dict(dataset="reddit", max_nodes=30_000, deltas=2,
                    min_speedup=1.5, config=None)
    from repro.core.model import AggConfig
    return dict(dataset="reddit", max_nodes=None, deltas=2,
                min_speedup=10.0,
                config=AggConfig(gs=8, gpt=32, dt=64, src_win=16384,
                                 ont=8))


def _parity(plan_a, plan_b) -> float:
    """Max |aggregate difference| between two plans over a shared random
    feature matrix — forward schedule and (when present) the transposed
    backward schedule."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import aggregate

    n = plan_a.graph.num_nodes
    rng = np.random.default_rng(7)
    feat = jnp.asarray(rng.standard_normal((n, 8)).astype(np.float32))
    err = float(jnp.abs(aggregate(feat, plan_a.sched(), backend="xla")
                        - aggregate(feat, plan_b.sched(), backend="xla")
                        ).max())
    if plan_a.partition_bwd is not None and plan_b.partition_bwd is not None:
        err = max(err, float(jnp.abs(
            aggregate(feat, plan_a.sched_bwd(), backend="xla")
            - aggregate(feat, plan_b.sched_bwd(), backend="xla")).max()))
    return err


def _measure(prof: dict, with_backward: bool) -> list:
    """Chain ``prof['deltas']`` stream batches through one plan: per batch,
    time `Plan.apply_delta` against (a) the full from-scratch `plan_for`
    pipeline and (b) a config-pinned repartition of the identical mutated
    graph, and cross-check aggregation parity against (b) — same config,
    so any difference is a patch bug, not tuner drift."""
    import numpy as np

    from benchmarks.common import emit
    from repro.core.advisor import plan_for
    from repro.graphs.datasets import interaction_stream, make_dataset

    g, spec, _ = make_dataset(prof["dataset"], max_nodes=prof["max_nodes"],
                              seed=0, max_dim=8)
    plan = plan_for(g, arch="gin", in_dim=8, hidden_dim=8, num_layers=2,
                    tune_iters=2, with_backward=with_backward,
                    config=prof["config"])
    # delta budget: ~1% of the nodes' worth of edge churn per batch (the
    # acceptance criterion's "small delta" regime)
    eb = max(64, g.num_nodes // 100)
    rows = []
    stream = interaction_stream(g, num_batches=prof["deltas"],
                                edges_per_batch=eb, seed=0)
    for i, delta in enumerate(stream):
        t0 = time.perf_counter()
        plan2 = plan.apply_delta(delta)
        t_inc = time.perf_counter() - t0
        g2 = plan.graph.apply_delta(delta).graph
        # baseline (a): the cold rebuild — property extraction, tuner,
        # partition; this is the pipeline the incremental path amortizes
        t0 = time.perf_counter()
        plan_for(g2, arch="gin", in_dim=8, hidden_dim=8, num_layers=2,
                 tune_iters=2, with_backward=with_backward)
        t_scr = time.perf_counter() - t0
        # baseline (b): repartition only, at the resident plan's config —
        # the patch's floor is the padded-tile memcpy, so this margin is
        # structurally ~2-4x, not 10x
        t0 = time.perf_counter()
        scratch = plan_for(g2, arch="gin", in_dim=8, hidden_dim=8,
                           num_layers=2, config=plan.config,
                           with_backward=with_backward)
        t_rep = time.perf_counter() - t0
        parity = _parity(plan2, scratch)
        row = {
            "dataset": prof["dataset"],
            "backward": with_backward,
            "nodes": plan2.graph.num_nodes,
            "edges": plan2.graph.num_edges,
            "delta_edges": int(delta.num_insertions
                               + len(np.ravel(delta.del_src
                                              if delta.del_src is not None
                                              else []))),
            "dirty_frac": float(plan2.stats.get("dirty_fraction", 0.0)),
            "mode": plan2.stats.get("incremental", "?"),
            "t_scratch_ms": t_scr * 1e3,
            "t_repartition_ms": t_rep * 1e3,
            "t_incremental_ms": t_inc * 1e3,
            "speedup": t_scr / max(t_inc, 1e-9),
            "repartition_speedup": t_rep / max(t_inc, 1e-9),
            "parity": parity,
        }
        rows.append(row)
        emit(f"dynamic/{prof['dataset']}/bwd{int(with_backward)}/d{i}",
             t_inc * 1e6,
             f"mode={row['mode']};dirty={row['dirty_frac']:.4f};"
             f"scratch_ms={row['t_scratch_ms']:.1f};"
             f"repart_ms={row['t_repartition_ms']:.1f};"
             f"speedup={row['speedup']:.1f};parity={parity:.1e}")
        plan = plan2
    return rows


def _comparison(rows: list, prof: dict) -> dict:
    """Verdict CI asserts on: every delta patched incrementally, exact
    aggregation parity, and the worst-case speedup above the profile's
    bar (>= 10x at full size, a sanity bar in smoke)."""
    worst = min((r["speedup"] for r in rows), default=0.0)
    parity = max((r["parity"] for r in rows), default=float("inf"))
    patched = all(r["mode"] == "patched" for r in rows)
    ok = (bool(rows) and patched and parity <= PARITY_TOL
          and worst >= prof["min_speedup"])
    return {
        "baseline": "plan_for(scratch, full advisor pipeline)",
        "candidate": "Plan.apply_delta",
        "deltas": len(rows),
        "all_patched": patched,
        "min_speedup": worst,
        "required_speedup": prof["min_speedup"],
        "max_parity": parity,
        "parity_tol": PARITY_TOL,
        "pass": ok,
    }


def run(smoke: bool = True, *, json_out: str | None = None) -> None:
    from repro.obs import run_context

    prof = _profile(smoke)
    configs = []
    for with_backward in (False, True):
        configs += _measure(prof, with_backward)
    comparison = _comparison(configs, prof)
    doc = {"schema": SCHEMA, "smoke": smoke, "context": run_context(),
           "configs": configs, "comparison": comparison}
    print(f"# dynamic comparison: min_speedup={comparison['min_speedup']:.1f}"
          f"x (need {comparison['required_speedup']:.1f}x) "
          f"parity={comparison['max_parity']:.1e} "
          f"-> {'PASS' if comparison['pass'] else 'FAIL'}")
    if json_out:
        with open(json_out, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"# wrote {json_out}")
    if not comparison["pass"]:
        raise RuntimeError(f"dynamic comparison failed: {comparison}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="small graph (CI budget); relaxes the speedup gate")
    p.add_argument("--json-out", default=None,
                   help="write the BENCH_dynamic.json document here")
    args = p.parse_args(argv)
    run(smoke=args.smoke, json_out=args.json_out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
