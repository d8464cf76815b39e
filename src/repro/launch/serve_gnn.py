"""GNN serving driver: replay a synthetic node-prediction request trace.

    # synchronous micro-batcher (the original driver)
    PYTHONPATH=src python -m repro.launch.serve_gnn \
        --num-nodes 20000 --requests 256 --batch-window 16

    # async SLO-aware tier: deadline batcher, 3 SLO tenants, open loop
    PYTHONPATH=src python -m repro.launch.serve_gnn \
        --policy deadline --slo-ms 250 --tenants 3 --rate 500

    # sharded executor behind the batcher (needs >= 2 visible devices:
    # XLA_FLAGS=--xla_force_host_platform_device_count=2)
    PYTHONPATH=src python -m repro.launch.serve_gnn --policy deadline --shards 2

Builds a power-law resident graph, initializes a GCN/GIN/GAT, then replays
a Zipf-popularity request trace.  ``--policy micro`` (default) drives the
synchronous `ServingEngine` (micro-batcher + plan cache) exactly as
before; ``--policy deadline|clock`` — or any of ``--tenants > 1`` /
``--shards > 1`` / an explicit ``--slo-ms`` — runs the async
`AsyncServingEngine` tier instead: bounded admission, SLO classes cycled
across tenants (gold/silver/bronze over ``--slo-ms``), deadline-aware or
fixed-window batching, EDF across tenants, and per-tenant
p50/p99/attainment reporting.

Stats are printed as the JSON metrics exporter's document (one registry
feeds both stdout and ``--metrics-out``, so they always agree —
docs/observability.md).  ``--smoke`` shrinks everything for CI.
"""
from __future__ import annotations

import argparse
import json
import math
import time


def build_trace(num_nodes: int, requests: int, *, zipf: float = 1.1,
                hot_fraction: float = 0.05, seed: int = 0):
    """Power-law seed popularity (back-compat wrapper over
    `serving.loadgen.zipf_seeds`): a small hot set dominates the trace,
    which is what makes plan/executor caching pay off in production."""
    from repro.serving.loadgen import zipf_seeds
    return zipf_seeds(num_nodes, requests, zipf=zipf,
                      hot_fraction=hot_fraction, seed=seed)


def _delta_stream(args, g):
    """Pre-draw the synthetic mutation stream for ``--stream-deltas``
    (docs/dynamic.md): ~1% of the resident edges per delta, new nodes
    carrying random features at the serving width."""
    from repro.graphs.datasets import interaction_stream
    return list(interaction_stream(
        g, num_batches=args.stream_deltas,
        edges_per_batch=max(16, g.num_edges // 100),
        feat_dim=args.in_dim, seed=args.seed))


def _write_trace(args, tracer) -> None:
    """--trace-out: span records as a Chrome/Perfetto trace JSON (open in
    ui.perfetto.dev or chrome://tracing — docs/observability.md)."""
    if not args.trace_out:
        return
    from repro.obs import run_context, write_chrome_trace
    write_chrome_trace(args.trace_out, tracer, context=run_context())
    print(f"[serve_gnn] wrote Chrome trace -> {args.trace_out}")


def _serve_async(args, g, feat, cfg, registry, tracer):
    """Replay the trace through the async SLO-aware tier; returns exit-ok."""
    import numpy as np

    from repro.obs import registry_to_json, run_context, write_metrics
    from repro.serving import (AsyncServingEngine, LoadSpec, ServingConfig,
                               ServingEngine, TenantSpec, build_schedule,
                               make_sharded_serve_fn, run_schedule,
                               slo_classes)

    t0 = time.time()
    if args.shards > 1:
        sharded_fn = make_sharded_serve_fn(g, feat, cfg,
                                           num_shards=args.shards,
                                           tune_iters=args.tune_iters,
                                           registry=registry)

        def serve_fn(seeds):
            # the sharded path has no engine-internal spans; one span per
            # batch keeps the Chrome trace's serve track populated
            with tracer.span("serve_sharded", block=True,
                             batch=len(seeds)) as sp:
                return sp.sync(sharded_fn(seeds))

        # the tracer wrapper hides the executor's mutation handler from
        # AsyncServingEngine's resolution — re-expose it
        serve_fn.update_graph = sharded_fn.update_graph
    else:
        sync = ServingEngine(
            g, feat, cfg,
            serving=ServingConfig(hops=args.hops, max_batch=args.batch_window,
                                  batch_mode=args.batch_mode,
                                  bucket_shapes=args.bucket,
                                  tune_iters=args.tune_iters,
                                  max_plans=(None if args.max_plans == 0
                                             else args.max_plans)),
            registry=registry, tracer=tracer)
        serve_fn = sync.serve_batch
    # warm the pow-2 batch-size buckets so measured batches replay cached
    # plans/executables instead of paying plan build + XLA compile
    wrng = np.random.default_rng(args.seed + 1)
    b = 1
    while True:
        serve_fn(wrng.integers(0, g.num_nodes, size=b).tolist())
        if b >= args.batch_window:
            break
        b = min(2 * b, args.batch_window)

    classes = slo_classes(args.slo_ms / 1e3)
    tenants = [TenantSpec(f"t{i}", serve_fn, slo=classes[i % len(classes)],
                          max_batch=args.batch_window)
               for i in range(args.tenants)]
    engine = AsyncServingEngine(tenants, policy=args.policy,
                                window=args.slo_ms / 2e3,
                                registry=registry)
    print(f"[serve_gnn] async tier: policy={args.policy} shards={args.shards} "
          f"tenants={[(t.name, t.slo.name) for t in tenants]} "
          f"(setup {time.time() - t0:.1f}s)")

    spec = LoadSpec(requests=args.requests,
                    rate_rps=(math.inf if args.rate <= 0 else args.rate),
                    zipf=args.zipf, tenants=tuple(t.name for t in tenants),
                    seed=args.seed)
    schedule = build_schedule(g.num_nodes, spec)
    if args.stream_deltas:
        # interleave graph mutations with the replay: the engine applies
        # each delta between fired batches (no request is dropped), and
        # only the final chunk is eligible for the verify cross-check
        # (earlier results answer against earlier snapshots)
        stream = _delta_stream(args, g)
        cuts = np.linspace(0, len(schedule), args.stream_deltas + 2
                           ).astype(int)
        parts, reqs, drained, completed, wall = [], [], True, 0, 0.0
        for ci in range(args.stream_deltas + 1):
            if ci:
                if not engine.update_graph(stream[ci - 1]).wait(60.0):
                    print("[serve_gnn] FAIL: graph update not applied")
                    drained = False
            part = run_schedule(engine, schedule[cuts[ci]:cuts[ci + 1]])
            parts.append(part)
            reqs = part["requests_detail"]
            drained = drained and part["drained"]
            completed += part["completed"]
            wall += part["wall_s"]
        all_reqs = [r for p in parts for r in p["requests_detail"]]
        res = {"requests": len(all_reqs), "completed": completed,
               "wall_s": wall, "throughput_rps": completed / max(wall, 1e-9),
               "drained": drained, "requests_detail": all_reqs}
        print(f"[serve_gnn] applied {args.stream_deltas} deltas "
              f"(updates="
              f"{int(engine.registry.counter('serve_graph_updates_total').value)})")
    else:
        res = run_schedule(engine, schedule)
        reqs = res["requests_detail"]
    acc = engine.accounting()
    summary = engine.summary()
    engine.close()

    doc = registry_to_json(registry, tracer=tracer, context=run_context())
    print(f"[serve_gnn] requests={res['requests']} "
          f"completed={res['completed']} "
          f"throughput={res['throughput_rps']:.1f} req/s")
    for name, s in summary.items():
        print(f"[serve_gnn]   {name} ({s['slo_class']} {s['slo_ms']:.0f}ms): "
              f"p50={s['p50_ms']:.1f}ms p99={s['p99_ms']:.1f}ms "
              f"attainment={s['slo_attainment']:.3f} "
              f"mean-batch={s['mean_batch']:.1f}")
    print(json.dumps(doc, indent=2))
    if args.metrics_out:
        if args.metrics_format == "json":
            with open(args.metrics_out, "w", encoding="utf-8") as f:
                json.dump(doc, f, indent=2)
                f.write("\n")
        else:
            write_metrics(registry, args.metrics_out, "prom")
        print(f"[serve_gnn] wrote metrics ({args.metrics_format}) -> "
              f"{args.metrics_out}")
    _write_trace(args, tracer)

    ok = res["drained"] and acc["outstanding"] == 0
    ok = ok and acc["submitted"] == acc["completed"] + acc["rejected"]
    if args.verify > 0:
        rng = np.random.default_rng(args.seed)
        done = [r for r in reqs if r.status == "done"]
        err = 0.0
        for i in rng.choice(len(done), size=min(args.verify, len(done)),
                            replace=False):
            single = np.asarray(serve_fn([done[i].seed]))[0]
            err = max(err, float((np.abs(single - done[i].result)
                                  / (1.0 + np.abs(single))).max()))
        tol = 1e-5 if args.dtype == "float32" else 2e-2
        ok = ok and err <= tol
        print(f"[serve_gnn] verify: max|batched - single|/(1+|single|) = "
              f"{err:.2e} ({'OK' if err <= tol else 'FAIL'} <= {tol:g})")
    if not ok:
        print(f"[serve_gnn] FAIL: accounting={acc} drained={res['drained']}")
    return ok


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--num-nodes", type=int, default=20_000)
    p.add_argument("--avg-degree", type=float, default=8.0)
    p.add_argument("--requests", type=int, default=256)
    p.add_argument("--batch-window", type=int, default=16,
                   help="micro-batch size budget (requests per batch)")
    p.add_argument("--arch", default="gcn", choices=["gcn", "gin", "gat"])
    p.add_argument("--in-dim", type=int, default=32)
    p.add_argument("--hidden-dim", type=int, default=32)
    p.add_argument("--classes", type=int, default=8)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hops", type=int, default=None,
                   help="ego radius (default: --layers)")
    p.add_argument("--backend", default=None,
                   choices=["xla", "pallas", "pallas_interpret"],
                   help="aggregation backend (default: pallas on a TPU, "
                        "xla elsewhere)")
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="feature/activation dtype policy "
                        "(docs/performance.md)")
    p.add_argument("--batch-mode", default="union",
                   choices=["union", "disjoint"])
    p.add_argument("--zipf", type=float, default=1.1)
    p.add_argument("--tune-iters", type=int, default=4)
    p.add_argument("--max-plans", type=int, default=64,
                   help="plan-cache LRU bound (0 = unbounded)")
    p.add_argument("--no-bucket", dest="bucket", action="store_false",
                   default=True, help="disable shape bucketing")
    p.add_argument("--verify", type=int, default=8,
                   help="cross-check N requests vs single-request inference")
    p.add_argument("--policy", default="micro",
                   choices=["micro", "deadline", "clock"],
                   help="micro = synchronous ServingEngine; deadline/clock "
                        "= async SLO-aware tier (docs/serving.md)")
    p.add_argument("--slo-ms", type=float, default=None,
                   help="gold-class SLO budget in ms for the async tier "
                        "(silver = 2x, bronze = 4x; default 250)")
    p.add_argument("--tenants", type=int, default=1,
                   help="number of tenants (SLO classes cycle across them); "
                        "> 1 implies the async tier")
    p.add_argument("--shards", type=int, default=1,
                   help="serve via the P-way sharded halo-exchange forward "
                        "(> 1 implies the async tier; needs that many "
                        "visible devices)")
    p.add_argument("--rate", type=float, default=500.0,
                   help="offered load in req/s for the async tier "
                        "(<= 0 = burst: all requests at t=0)")
    p.add_argument("--stream-deltas", type=int, default=0,
                   help="apply N synthetic interaction-stream deltas to "
                        "the resident graph, interleaved with the request "
                        "replay (docs/dynamic.md)")
    p.add_argument("--smoke", action="store_true",
                   help="tiny CI-sized run (overrides --num-nodes, "
                        "--requests, --batch-window, --tune-iters)")
    p.add_argument("--metrics-out", default=None,
                   help="write the run's metrics registry to this path "
                        "(docs/observability.md)")
    p.add_argument("--metrics-format", default="json",
                   choices=["json", "prom"],
                   help="exporter for --metrics-out")
    p.add_argument("--trace-out", default=None,
                   help="write the run's span records as a Chrome/Perfetto "
                        "trace JSON (open in ui.perfetto.dev; "
                        "docs/observability.md)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    use_async = (args.policy in ("deadline", "clock") or args.tenants > 1
                 or args.shards > 1 or args.slo_ms is not None)
    if use_async and args.policy == "micro":
        args.policy = "deadline"
    if args.slo_ms is None:
        args.slo_ms = 250.0
    if args.smoke:
        args.num_nodes = 1500
        args.requests = 24
        args.batch_window = 8
        args.tune_iters = 2
        args.verify = min(args.verify, 2)
    if args.batch_window < 1:
        p.error("--batch-window must be >= 1")
    if args.requests < 1:
        p.error("--requests must be >= 1")
    if args.tenants < 1:
        p.error("--tenants must be >= 1")
    if args.shards < 1:
        p.error("--shards must be >= 1")
    if args.slo_ms <= 0:
        p.error("--slo-ms must be > 0")

    import numpy as np

    from repro.graphs.csr import random_power_law
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.gnn import GNNConfig
    from repro.obs import (MetricsRegistry, SpanTracer, registry_to_json,
                           run_context, write_metrics)
    from repro.serving import ServingConfig, ServingEngine

    enable_compile_cache()
    t0 = time.time()
    registry = MetricsRegistry()
    tracer = SpanTracer(registry)
    g = random_power_law(args.num_nodes, args.avg_degree, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    feat = rng.standard_normal((g.num_nodes, args.in_dim)).astype(np.float32)
    cfg = GNNConfig(arch=args.arch, in_dim=args.in_dim,
                    hidden_dim=args.hidden_dim, num_classes=args.classes,
                    num_layers=args.layers, backend=args.backend,
                    feat_dtype=args.dtype)
    if use_async:
        return 0 if _serve_async(args, g, feat, cfg, registry, tracer) else 1

    engine = ServingEngine(
        g, feat, cfg,
        serving=ServingConfig(hops=args.hops, max_batch=args.batch_window,
                              batch_mode=args.batch_mode,
                              bucket_shapes=args.bucket,
                              tune_iters=args.tune_iters,
                              max_plans=(None if args.max_plans == 0
                                         else args.max_plans)),
        registry=registry, tracer=tracer)
    print(f"[serve_gnn] graph n={g.num_nodes} e={g.num_edges} arch={args.arch} "
          f"backend={cfg.backend} hops={engine.hops} "
          f"(setup {time.time() - t0:.1f}s)")

    trace = build_trace(g.num_nodes, args.requests, zipf=args.zipf,
                        seed=args.seed)
    if args.stream_deltas:
        # split the trace into chunks and mutate the resident graph
        # between them; verify only against the final snapshot's chunk
        stream = _delta_stream(args, g)
        cuts = np.linspace(0, len(trace), args.stream_deltas + 2).astype(int)
        reqs, all_reqs = [], []
        for ci in range(args.stream_deltas + 1):
            if ci:
                engine.update_graph(stream[ci - 1])
            reqs = engine.run_trace(list(trace[cuts[ci]:cuts[ci + 1]]))
            all_reqs.extend(reqs)
        print(f"[serve_gnn] applied {args.stream_deltas} deltas "
              f"(graph_epoch={engine.graph_epoch}, "
              f"n={engine.graph.num_nodes}, "
              f"invalidations={engine.cache.stats()['invalidations']})")
    else:
        reqs = engine.run_trace(trace)
    s = engine.summary()
    c = s["cache"]
    # one registry, one exporter: the stdout stats ARE the JSON metrics
    # document, and --metrics-out writes the same document (span durations
    # live in the registry as span_seconds{span=...} histograms)
    doc = registry_to_json(registry, tracer=tracer, context=run_context())
    print(f"[serve_gnn] requests={s['requests']} "
          f"throughput={s['req_per_s']:.1f} req/s "
          f"hit-rate={c['hit_rate']:.2f}")
    print(json.dumps(doc, indent=2))
    if args.metrics_out:
        if args.metrics_format == "json":
            with open(args.metrics_out, "w", encoding="utf-8") as f:
                json.dump(doc, f, indent=2)
                f.write("\n")
        else:
            write_metrics(registry, args.metrics_out, "prom")
        print(f"[serve_gnn] wrote metrics ({args.metrics_format}) -> "
              f"{args.metrics_out}")
    _write_trace(args, tracer)

    ok = True
    if args.verify > 0:
        pick = rng.choice(len(reqs), size=min(args.verify, len(reqs)),
                          replace=False)
        err = 0.0
        for i in pick:
            single = engine.serve_batch([reqs[i].seed])[0]
            # magnitude-normalized: GIN logits grow with degree sums, so raw
            # f32 accumulation-order noise scales with |logit|
            err = max(err, float((np.abs(single - reqs[i].result)
                                  / (1.0 + np.abs(single))).max()))
        # bf16 activations round per layer, so two paddings of the same ego
        # can differ by a few ulps (~1e-2 relative); f32 stays at 1e-5
        tol = 1e-5 if args.dtype == "float32" else 2e-2
        ok = err <= tol
        print(f"[serve_gnn] verify: max|batched - single|/(1+|single|) = "
              f"{err:.2e} ({'OK' if ok else 'FAIL'} <= {tol:g})")
    if c["hit_rate"] <= 0:
        print("[serve_gnn] WARNING: plan-cache hit rate is 0")
        # a short/diverse trace can legitimately never repeat a shape class;
        # only fail when the trace was long enough that caching should bite
        # (streamed deltas bump the epoch key, legitimately resetting reuse)
        if args.requests >= 4 * args.batch_window and not args.stream_deltas:
            ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
