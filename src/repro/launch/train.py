"""Training driver.

Runs the fault-tolerant Trainer loop over a (reduced or full) architecture
config.  On this CPU container you run reduced configs:

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --reduced \
        --steps 100 --global-batch 8 --seq-len 64 --ckpt-dir /tmp/ckpt

GNN archs (gcn / gin / gat) train a node classifier on a paper-dataset
replica through the advisor path.  On a TPU the default backend is the
compiled group-aggregate kernel, forward AND backward (the backward pass is
the transposed schedule — docs/training.md); elsewhere it is the XLA
reference, and ``--backend pallas_interpret`` runs the kernel body under
the Pallas interpreter:

    PYTHONPATH=src python -m repro.launch.train --arch gcn --dataset cora \
        --steps 50 --backend pallas_interpret

``--sampled`` switches to neighbor-sampled mini-batch training
(docs/sampling.md): per-step fanout-sampled bipartite blocks planned
through a plan cache, per-step memory bounded by the batch instead of the
graph — full-size Type III graphs train where full-batch cannot:

    PYTHONPATH=src python -m repro.launch.train --arch gcn --sampled \
        --dataset reddit --scale 1.0 --fanouts 10,5 --batch-nodes 512 \
        --steps 30

``--shards N`` runs multi-device halo-exchange execution over N graph
shards (docs/distributed.md): full-graph training splits the plan into
contiguous node-range sub-plans via the shard splitter, ``--sampled``
training goes data-parallel (N loader batches per step, psum'd grads).
On CPU force the devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.launch.train --arch gcn \
        --dataset cora --steps 20 --shards 4

On a real cluster the same driver runs the full config under
make_production_mesh() with per-host data sharding.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import tempfile
import time

GNN_ARCHS = ("gcn", "gin", "gat")


def _process_tracer():
    """The run's tracer: the process tracer, with JAX's compile listener
    on it.  The planner's spans and each function's compile stages and
    cache lookups then land in the registry the run exports
    (docs/observability.md)."""
    from repro.obs import install_compile_listener, process_tracer
    install_compile_listener()
    return process_tracer()


def _write_metrics(args, registry, tracer=None) -> None:
    if not args.metrics_out:
        return
    from repro.obs import run_context, write_metrics
    write_metrics(registry, args.metrics_out, args.metrics_format,
                  tracer=tracer, context=run_context())
    print(f"[train] wrote metrics ({args.metrics_format}) -> "
          f"{args.metrics_out}")


def _write_trace(args, tracer) -> None:
    """--trace-out: the run's span records (the Trainer's steps, the
    planner's spans, JAX's compile stages) as a Chrome/Perfetto trace
    (open in ui.perfetto.dev or chrome://tracing —
    docs/observability.md)."""
    if not getattr(args, "trace_out", None):
        return
    from repro.obs import run_context, write_chrome_trace
    write_chrome_trace(args.trace_out, tracer, context=run_context())
    print(f"[train] wrote Chrome trace -> {args.trace_out}")


class _DeltaStream:
    """Wrap a batch_fn: before step ``k*every`` is served, apply the next
    `interaction_stream` delta to the loader (docs/dynamic.md).  The swap
    happens at the loader's safe batch boundary; mutated steps resample
    from the new snapshot.  Restart-safe: a replayed step does not re-apply
    its delta (the mutation stream is consumed at most once per step)."""

    def __init__(self, batch_fn, loader, stream, every: int):
        self.batch_fn = batch_fn
        self.loader = loader
        self.stream = stream
        self.every = every
        self.applied = 0
        self._seen: set[int] = set()

    def __call__(self, step: int):
        if step and step % self.every == 0 and step not in self._seen:
            self._seen.add(step)
            delta = next(self.stream, None)
            if delta is not None:
                self.loader.update_graph(delta)
                self.applied += 1
        return self.batch_fn(step)

    def close(self):
        close = getattr(self.batch_fn, "close", None)
        (close or self.loader.close)()


class _ShardedBatches:
    """step -> list of `num_shards` loader batches (one per device), and a
    ``close()`` the Trainer forwards to the underlying loader."""

    def __init__(self, loader, num_shards: int):
        self.loader = loader
        self.num_shards = num_shards

    def __call__(self, step: int):
        return [self.loader(step * self.num_shards + p)
                for p in range(self.num_shards)]

    def close(self):
        self.loader.close()


def _main_gnn_sampled(args) -> int:
    """Neighbor-sampled mini-batch branch: fanout sampler -> per-block plan
    cache -> per-bucket jitted step -> fault-tolerant Trainer loop."""
    import jax

    from repro.graphs.datasets import make_dataset
    from repro.models.gnn import (GNNConfig, init_gnn_params,
                                  structural_labels)
    from repro.optim.adamw import AdamWConfig, adamw_init, cosine_schedule
    from repro.runtime.trainer import (FailureInjector, Trainer,
                                       TrainerConfig)
    from repro.sampling import (LoaderConfig, SampledLoader,
                                SampledTrainStep, ShardedSampledTrainStep)

    tracer = _process_tracer()
    registry = tracer.registry
    t0 = time.time()
    g, spec, feat = make_dataset(args.dataset, scale=args.scale,
                                 max_nodes=args.max_nodes, seed=args.seed,
                                 max_dim=128)
    in_dim = feat.shape[1]
    fanouts = tuple(int(f) for f in args.fanouts.split(","))
    cfg = GNNConfig(arch=args.arch, in_dim=in_dim,
                    hidden_dim=args.hidden_dim,
                    num_classes=spec.num_classes, num_layers=len(fanouts),
                    backend=args.backend, feat_dtype=args.dtype)
    # no full-graph teacher forward here — that is the very pass sampling
    # exists to avoid on full-size Type III inputs
    labels = structural_labels(g, cfg.num_classes)
    print(f"[train] sampled dataset={args.dataset} scale={args.scale} "
          f"N={g.num_nodes} E={g.num_edges} gen={time.time()-t0:.1f}s")

    loader = SampledLoader(
        g, feat, labels, cfg,
        LoaderConfig(fanouts=fanouts, batch_nodes=args.batch_nodes,
                     seed=args.seed, tune_iters=4),
        registry=registry)
    opt = AdamWConfig(lr=args.lr,
                      schedule=cosine_schedule(args.warmup, args.steps))
    if args.shards > 1:
        # data-parallel sampled training: every optimizer step consumes
        # `shards` loader batches, grads psum over the shard mesh axis
        step_fn = ShardedSampledTrainStep(cfg, opt, args.shards,
                                          registry=registry)
        batch_fn = _ShardedBatches(loader, args.shards)
    else:
        step_fn = SampledTrainStep(cfg, opt)
        batch_fn = loader
    if args.stream_deltas:
        from repro.graphs.datasets import interaction_stream
        eb = args.stream_edges or max(32, g.num_edges // 100)
        batch_fn = _DeltaStream(
            batch_fn, loader,
            interaction_stream(g, num_batches=args.steps // args.stream_deltas
                               + 1, edges_per_batch=eb, feat_dim=in_dim,
                               seed=args.seed),
            args.stream_deltas)
        print(f"[train] streaming deltas: every {args.stream_deltas} steps, "
              f"{eb} edges/batch")
    params = init_gnn_params(cfg, jax.random.PRNGKey(args.seed))
    ckpt_dir = args.ckpt_dir or os.path.join(
        tempfile.gettempdir(),
        f"repro_train_sampled_{args.arch}_{args.dataset}"
        f"_s{args.scale}_h{args.hidden_dim}_b{args.batch_nodes}"
        f"_p{args.shards}_{cfg.backend}_{args.seed}")
    trainer = Trainer(
        TrainerConfig(ckpt_dir=ckpt_dir, ckpt_every=args.ckpt_every,
                      log_every=10),
        step_fn, batch_fn, (params, adamw_init(params)),
        injector=FailureInjector(args.fail_at or ()), registry=registry,
        tracer=tracer)
    t1 = time.time()
    try:
        trainer.run(args.steps)
    finally:
        trainer.close()
    hist = trainer.metrics_history
    losses = (f"first_loss={hist[0]['loss']:.4f} "
              f"last_loss={hist[-1]['loss']:.4f} " if hist else "")
    st = loader.stats()
    cache = st["cache"]
    deltas = (f"graph_epoch={st['graph_epoch']} "
              if st.get("graph_swaps") else "")
    print(f"[train] arch={args.arch} backend={cfg.backend} "
          f"dtype={args.dtype} sampled "
          f"fanouts={fanouts} batch={args.batch_nodes} "
          f"shards={args.shards} steps={len(hist)} "
          f"{losses}{deltas}avg_step={trainer.avg_step_time()*1e3:.1f}ms "
          f"jit_buckets={step_fn.num_buckets} traces={step_fn.traces} "
          f"cache_hit_rate={cache['hit_rate']:.2f} "
          f"wall={time.time()-t1:.1f}s")
    _write_metrics(args, registry, tracer)
    _write_trace(args, tracer)
    return 0


def _main_gnn(args) -> int:
    """GNN training branch: dataset replica -> advisor plan (fwd+bwd
    schedules) -> jitted value_and_grad through the chosen backend."""
    import jax.numpy as jnp
    import numpy as np

    from repro.graphs.datasets import make_dataset
    from repro.models.gnn import (GNNConfig, build_gnn, make_gnn_train_step,
                                  planted_labels)
    from repro.optim.adamw import AdamWConfig, adamw_init, cosine_schedule
    from repro.runtime.trainer import (FailureInjector, Trainer,
                                       TrainerConfig)

    tracer = _process_tracer()
    registry = tracer.registry
    max_nodes = args.max_nodes if args.max_nodes is not None else 2000
    g, spec, feat = make_dataset(args.dataset, scale=args.scale,
                                 max_nodes=max_nodes, seed=args.seed)
    in_dim = min(spec.dim, 128)
    feat = feat[:, :in_dim].astype(np.float32)
    cfg = GNNConfig(arch=args.arch, in_dim=in_dim,
                    hidden_dim=args.hidden_dim,
                    num_classes=spec.num_classes, num_layers=2,
                    backend=args.backend, feat_dtype=args.dtype)
    # learnable planted task: labels from a frozen random teacher
    labels = planted_labels(g, cfg, feat, seed=args.seed + 7)

    # --shards forces the transposed backward pair (the sharded step's
    # custom VJP runs the kernel over per-shard transposed schedules) and
    # skips the single-device executor the sharded step never runs
    model = build_gnn(g, cfg, reorder="auto", tune_iters=6, seed=args.seed,
                      with_backward=True if args.shards > 1 else None,
                      with_executor=args.shards == 1)
    batch = {"feat": jnp.asarray(model.plan.renumber_features(feat)),
             "labels": jnp.asarray(model.plan.renumber_features(labels))}

    opt = AdamWConfig(lr=args.lr,
                      schedule=cosine_schedule(args.warmup, args.steps))
    if args.shards > 1:
        from repro.distributed.graph_shard import make_sharded_train_step
        shards = model.plan.shards(args.shards)
        st = shards.stats()
        print(f"[train] shards={args.shards} n_local={st['n_local']} "
              f"edges/shard={st['edges_per_shard']} "
              f"halo={st['halo_per_shard']} "
              f"edge_balance={st['edge_balance']:.2f}")
        step_fn = make_sharded_train_step(cfg, shards, opt,
                                          registry=registry)
    else:
        step_fn = make_gnn_train_step(model, opt)
    # unlike the LM branch, arch+seed does not determine parameter shapes —
    # key the auto-restore dir on everything that does
    ckpt_dir = args.ckpt_dir or os.path.join(
        tempfile.gettempdir(),
        f"repro_train_{args.arch}_{args.dataset}_h{args.hidden_dim}"
        f"_p{args.shards}_{cfg.backend}_{args.seed}")
    trainer = Trainer(
        TrainerConfig(ckpt_dir=ckpt_dir, ckpt_every=args.ckpt_every,
                      log_every=10),
        step_fn, lambda step: batch,
        (model.params, adamw_init(model.params)),
        injector=FailureInjector(args.fail_at or ()), registry=registry,
        tracer=tracer)
    t0 = time.time()
    trainer.run(args.steps)
    hist = trainer.metrics_history
    losses = (f"first_loss={hist[0]['loss']:.4f} "
              f"last_loss={hist[-1]['loss']:.4f} " if hist else "")
    print(f"[train] arch={args.arch} backend={cfg.backend} "
          f"dtype={args.dtype} "
          f"dataset={args.dataset} shards={args.shards} steps={len(hist)} "
          f"{losses}avg_step={trainer.avg_step_time()*1e3:.1f}ms "
          f"wall={time.time()-t0:.1f}s")
    _write_metrics(args, registry, tracer)
    _write_trace(args, tracer)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--backend", default=None,
                   choices=["xla", "pallas", "pallas_interpret"],
                   help="aggregation backend (GNN archs only; default: "
                        "pallas on a TPU, xla elsewhere)")
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="feature/activation dtype policy (GNN archs; "
                        "params and accumulation stay f32 — "
                        "docs/performance.md)")
    p.add_argument("--dataset", default="cora",
                   help="paper-dataset replica (GNN archs only)")
    p.add_argument("--max-nodes", type=int, default=None,
                   help="cap dataset size (default: 2000 full-batch, "
                        "uncapped with --sampled)")
    p.add_argument("--sampled", action="store_true",
                   help="neighbor-sampled mini-batch training (GNN archs; "
                        "docs/sampling.md)")
    p.add_argument("--shards", type=int, default=1,
                   help="data-parallel graph shards (GNN archs; needs that "
                        "many jax devices — on CPU set XLA_FLAGS="
                        "--xla_force_host_platform_device_count; "
                        "docs/distributed.md)")
    p.add_argument("--fanouts", default="10,5",
                   help="comma-separated per-layer fanouts (with --sampled)")
    p.add_argument("--batch-nodes", type=int, default=512,
                   help="seed nodes per sampled mini-batch")
    p.add_argument("--stream-deltas", type=int, default=0,
                   help="with --sampled: apply one synthetic interaction-"
                        "stream delta to the resident graph every N steps "
                        "(docs/dynamic.md)")
    p.add_argument("--stream-edges", type=int, default=0,
                   help="edges per streamed delta (default ~1%% of the "
                        "seed graph's edges)")
    p.add_argument("--scale", type=float, default=1.0,
                   help="dataset size multiplier (1.0 = paper size)")
    p.add_argument("--hidden-dim", type=int, default=32)
    p.add_argument("--reduced", action="store_true", default=True)
    p.add_argument("--full", dest="reduced", action="store_false")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--n-micro", type=int, default=1)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--fail-at", type=int, action="append", default=None,
                   help="inject a simulated failure at this step (repeatable)")
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

    if args.sampled and args.arch not in ("gcn", "gin"):
        p.error("--sampled supports gcn/gin only (GAT trains full-graph)")
    if args.stream_deltas and not args.sampled:
        p.error("--stream-deltas requires --sampled (the resident-graph "
                "loader owns the swap protocol)")
    if args.shards < 1:
        p.error("--shards must be >= 1")
    if args.shards > 1 and args.arch not in ("gcn", "gin"):
        p.error("--shards supports gcn/gin only (GAT trains on one "
                "device)")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.arch in GNN_ARCHS:
        return _main_gnn_sampled(args) if args.sampled else _main_gnn(args)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch
    from repro.data import PipelineConfig, TokenPipeline, make_lm_batch
    from repro.models.lm import make_train_step
    from repro.nn.transformer import lm_init
    from repro.optim.adamw import AdamWConfig, adamw_init, cosine_schedule
    from repro.runtime.trainer import (FailureInjector, Trainer, TrainerConfig)

    tracer = _process_tracer()
    registry = tracer.registry
    arch = get_arch(args.arch)
    cfg = arch.reduced() if args.reduced else arch.full()
    params, specs = lm_init(cfg, jax.random.PRNGKey(args.seed))
    opt = AdamWConfig(lr=args.lr,
                      schedule=cosine_schedule(args.warmup, args.steps))
    opt_state = adamw_init(params)
    fns = make_train_step(cfg, opt, n_micro=args.n_micro)

    pipe = TokenPipeline(PipelineConfig(
        vocab=cfg.vocab, seq_len=args.seq_len,
        global_batch=args.global_batch, seed=args.seed))

    def batch_fn(step: int):
        b = make_lm_batch(pipe.batch(step), frontend=cfg.frontend,
                          d_model=cfg.d_model, mrope=(cfg.rope == "mrope"),
                          seed=step)
        return {k: jnp.asarray(v) for k, v in b.items()}

    def step_fn(state, batch):
        params, opt_state = state
        params, opt_state, metrics = fns.step(params, opt_state, batch)
        return (params, opt_state), metrics

    ckpt_dir = args.ckpt_dir or os.path.join(
        tempfile.gettempdir(), f"repro_train_{args.arch}_{args.seed}")
    trainer = Trainer(
        TrainerConfig(ckpt_dir=ckpt_dir, ckpt_every=args.ckpt_every,
                      log_every=10),
        step_fn, batch_fn, (params, opt_state),
        injector=FailureInjector(args.fail_at or ()), registry=registry,
        tracer=tracer)
    t0 = time.time()
    trainer.run(args.steps)
    dt = time.time() - t0
    hist = trainer.metrics_history
    print(f"[train] arch={cfg.name} steps={len(hist)} "
          f"first_loss={hist[0]['loss']:.4f} last_loss={hist[-1]['loss']:.4f} "
          f"wall={dt:.1f}s")
    _write_metrics(args, registry, tracer)
    _write_trace(args, tracer)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
