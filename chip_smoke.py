"""Smoke run of the GNN runtime on a TPU through its user entry points.

    python chip_smoke.py                 # one chip: train, then serve
    python chip_smoke.py --four-chips    # four chips: sharded train step only

One chip: the paper's GCN (2 layers, hidden 16) at the full input width of
a full-size paper dataset replica (default soc-blogcatalog: 88,784 nodes,
D = 128, 39 classes), random weights from ``--seed``.

* train — `build_gnn` -> `make_gnn_train_step` -> `Trainer`, the calls
  `repro.launch.train` makes: the compiled step must hold the Pallas kernel
  (``tpu_custom_call``) for the forward and the transposed backward, one
  step's loss and gradients must match ``backend="xla"`` on the same chip,
  and the loss must stay finite and fall;
* serve — `ServingEngine` on the same resident graph answers Zipf
  requests (`repro.launch.serve_gnn.build_trace`); its forward must hold
  the kernel, and batched answers must match single-request answers.

``--four-chips`` runs only the sharded full-graph train step
(`Plan.shards(4)` -> `make_sharded_train_step`) against the single-chip
step on the same graph, and checks that the result spans four devices and
that the compiled step all-gathers.

Details go to earlier lines of stdout; the last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a TPU,
or when any phase fails, the script exits non-zero and prints no result.
Times printed are smoke times of one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# magnitude-normalised tolerances: max|a - b| / (1 + max|ref|) for losses
# and logits, max|a - b| / max|ref| per leaf for gradients
PARITY_TOL = 1e-4        # Pallas step vs the XLA reference, f32
VERIFY_TOL = 1e-5        # batched vs single-request serving, f32
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'
STEPS = 5                # train steps
REQUESTS = 64            # Zipf serving requests
LR = 1e-2                # AdamW learning rate


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / (1.0 + np.abs(want).max()))


def grad_err(got, want) -> float:
    """Worst leaf of max|got - want| / max|want| over two gradient trees."""
    import jax
    import numpy as np

    def leaf(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        leaf, got, want)))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"[chip_smoke] FAIL: {what}")


def device_phase(need: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    check(dev["platform"] == "tpu",
          f"no TPU: JAX's first device is {dev['platform']!r}")
    log(f"device kind={dev['kind']!r} count={dev['count']} "
        f"jax={jax.__version__}")
    check(dev["count"] >= need, f"need {need} chips, have {dev['count']}")
    return dev


def load_graph(args):
    from repro.configs.paper_gnn import gcn_config
    from repro.graphs.datasets import make_dataset
    from repro.models.gnn import structural_labels

    t0 = time.perf_counter()
    g, spec, feat = make_dataset(args.dataset, scale=1.0, seed=args.seed)
    cfg = gcn_config(in_dim=feat.shape[1], num_classes=spec.num_classes)
    labels = structural_labels(g, cfg.num_classes)
    log(f"dataset={args.dataset} nodes={g.num_nodes} edges={g.num_edges} "
        f"D={feat.shape[1]} classes={spec.num_classes} "
        f"(generated in {time.perf_counter() - t0:.1f}s)")
    check(cfg.backend == "pallas",
          f"default backend on a TPU is {cfg.backend!r}, not 'pallas'")
    return g, feat, labels, cfg


def build_model(g, feat, labels, cfg, args):
    import jax.numpy as jnp
    from repro.models.gnn import build_gnn

    t0 = time.perf_counter()
    model = build_gnn(g, cfg, reorder="auto", tune_iters=6, seed=args.seed)
    plan_s = time.perf_counter() - t0
    p, pb = model.plan.partition, model.plan.partition_bwd
    check(pb is not None, "the Pallas model carries no backward schedule")
    slots = p.num_tiles * p.gpt * p.gs / max(p.num_edges, 1)
    log(f"plan: {plan_s:.1f}s host time, config={model.plan.config}, "
        f"tiles fwd={p.num_tiles} bwd={pb.num_tiles}, "
        f"padded slots per edge={slots:.2f}")
    batch = {"feat": jnp.asarray(model.plan.renumber_features(feat)),
             "labels": jnp.asarray(model.plan.renumber_features(labels))}
    return model, batch


def peak_hbm() -> int:
    import jax
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def train_phase(model, batch) -> None:
    import jax
    import numpy as np
    from repro.models.gnn import make_gnn_train_step
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.runtime.trainer import Trainer, TrainerConfig

    step_fn = make_gnn_train_step(model, AdamWConfig(lr=LR))
    state = (model.params, adamw_init(model.params))

    t0 = time.perf_counter()
    n_fwd = jax.jit(model.logits).lower(
        model.params, batch["feat"]).compile().as_text().count(KERNEL_CALL)
    n_step = step_fn.lower(state, batch).compile().as_text().count(
        KERNEL_CALL)
    log(f"compiled forward holds {n_fwd} kernel calls, train step {n_step} "
        f"({time.perf_counter() - t0:.1f}s to compile both)")
    check(n_fwd >= 1, "no Pallas kernel in the compiled forward")
    check(n_step >= 2 * n_fwd,
          "the train step does not run the kernel over the backward too")

    # one step's loss and gradients against the XLA reference, same chip
    ref = model.rebind(model.plan, backend="xla")

    def loss_and_grads(m):
        fn = jax.jit(jax.value_and_grad(
            lambda p: m.loss(p, batch["feat"], batch["labels"])[0]))
        return fn(model.params)

    loss_k, grads_k = loss_and_grads(model)
    loss_x, grads_x = loss_and_grads(ref)
    err_loss, err_grad = rel_err(loss_k, loss_x), grad_err(grads_k, grads_x)
    log(f"pallas vs xla: loss {float(loss_k):.6f} vs {float(loss_x):.6f} "
        f"(rel {err_loss:.2e}), grads rel {err_grad:.2e} "
        f"(tolerance {PARITY_TOL:g})")
    check(err_loss <= PARITY_TOL and err_grad <= PARITY_TOL,
          "Pallas step disagrees with the XLA reference")

    with tempfile.TemporaryDirectory() as ckpt_dir:
        trainer = Trainer(
            TrainerConfig(ckpt_dir=ckpt_dir, ckpt_every=STEPS + 1,
                          log_every=STEPS + 1),
            step_fn, lambda step: batch, state, log_fn=log)
        trainer.run(STEPS)
        trainer.close()
    losses = [m["loss"] for m in trainer.metrics_history]
    log(f"train: {len(losses)} steps, losses "
        f"{' '.join(f'{x:.5f}' for x in losses)}; smoke step time "
        f"{trainer.avg_step_time() * 1e3:.2f} ms (first step excluded); "
        f"peak HBM {peak_hbm() / 2**30:.3f} GiB")
    check(all(math.isfinite(x) for x in losses), "non-finite training loss")
    check(losses[-1] < losses[0], "training loss did not fall")


def serve_phase(g, feat, cfg, model, args) -> None:
    import jax
    import numpy as np
    from repro.graphs.subgraph import extract_ego, pad_to_nodes
    from repro.launch.serve_gnn import build_trace
    from repro.models.gnn import GNNModel
    from repro.serving import ServingConfig, ServingEngine
    from repro.serving.plan_cache import bucket_pow2

    t0 = time.perf_counter()
    engine = ServingEngine(g, feat, cfg, params=model.params,
                           serving=ServingConfig(max_batch=16, tune_iters=4))
    trace = build_trace(g.num_nodes, REQUESTS, seed=args.seed)
    reqs = engine.run_trace(trace)
    s = engine.summary()
    log(f"serve: {s['requests']} requests answered, plan-cache hit rate "
        f"{s['cache']['hit_rate']:.3f}, {s['cache']['plans']} plans; "
        f"smoke wall {time.perf_counter() - t0:.1f}s "
        f"(p50 {s['p50_ms']:.2f} ms, p99 {s['p99_ms']:.2f} ms, compiles "
        f"included)")
    check(s["requests"] == REQUESTS, "not every request was answered")
    check(all(np.isfinite(r.result).all() for r in reqs),
          "non-finite serving logits")

    # the serving forward dispatches to the kernel: compile one request's
    # forward through its cache entry's executor
    ego = extract_ego(engine.src_graph, [reqs[0].seed], engine.hops,
                      engine.src_vals)
    sub = pad_to_nodes(ego.graph, bucket_pow2(ego.graph.num_nodes))
    ent = engine.cache.get_or_build(
        sub, arch=cfg.arch, in_dim=cfg.in_dim, hidden_dim=cfg.hidden_dim,
        num_layers=cfg.num_layers, edge_vals=ego.edge_vals,
        epoch=engine.graph_epoch)
    fwd = GNNModel(cfg=cfg, plan=ent.plan, executor=ent.executor,
                   params=engine.params)
    sub_feat = jax.ShapeDtypeStruct((sub.num_nodes, cfg.in_dim),
                                    cfg.compute_dtype)
    n_serve = jax.jit(fwd.logits).lower(
        engine.params, sub_feat).compile().as_text().count(KERNEL_CALL)
    log(f"serving forward holds {n_serve} kernel calls")
    check(n_serve >= 1, "no Pallas kernel in the serving forward")

    rng = np.random.default_rng(args.seed)
    err = 0.0
    for i in rng.choice(len(reqs), size=min(4, len(reqs)), replace=False):
        single = engine.serve_batch([reqs[i].seed])[0]
        err = max(err, rel_err(reqs[i].result, single))
    log(f"verify: batched vs single on 4 requests, rel {err:.2e} "
        f"(tolerance {VERIFY_TOL:g})")
    check(err <= VERIFY_TOL, "batched serving disagrees with single-request")


def four_chip_phase(model, batch, cfg) -> None:
    import jax
    from repro.distributed.graph_shard import make_sharded_train_step
    from repro.models.gnn import make_gnn_train_step
    from repro.optim.adamw import AdamWConfig, adamw_init

    opt = AdamWConfig(lr=LR)
    state = (model.params, adamw_init(model.params))
    shards = model.plan.shards(4)
    st = shards.stats()
    log(f"shards=4 n_local={st['n_local']} "
        f"edges/shard={st['edges_per_shard']} "
        f"edge_balance={st['edge_balance']:.2f}")
    sharded = make_sharded_train_step(cfg, shards, opt)
    hlo = sharded.lower(state, batch).compile().as_text()
    log(f"sharded step: {hlo.count('all-gather')} all-gather ops, "
        f"{hlo.count(KERNEL_CALL)} kernel calls")
    check("all-gather" in hlo, "the sharded step holds no all-gather")
    check(KERNEL_CALL in hlo, "no Pallas kernel in the sharded step")

    (p4, s4), m4 = sharded(state, batch)
    (p1, s1), m1 = make_gnn_train_step(model, opt)(state, batch)
    devices = {d for leaf in jax.tree_util.tree_leaves(p4)
               for d in leaf.devices()}
    # after one step Adam's first moment is (1 - b1) x the clipped
    # gradient: the step's gradient, compared linearly.  The updated
    # parameters are not: Adam's g / (|g| + eps) turns rounding-level
    # differences in near-zero gradient entries into lr-sized ones.
    err_loss = rel_err(m4["loss"], m1["loss"])
    err_grad = grad_err(s4.m, s1.m)
    log(f"4 chips vs 1: loss {float(m4['loss']):.6f} vs "
        f"{float(m1['loss']):.6f} (rel {err_loss:.2e}), gradient (Adam "
        f"first moment) rel {err_grad:.2e} (tolerance {PARITY_TOL:g}); "
        f"updated params max|diff| {grad_err(p4, p1):.2e} of max|param|; "
        f"result on {len(devices)} devices")
    check(len(devices) == 4, f"result spans {len(devices)} devices, not 4")
    check(err_loss <= PARITY_TOL and err_grad <= PARITY_TOL,
          "sharded step disagrees with the single-chip step")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step over four chips "
                         "and its single-chip comparison")
    ap.add_argument("--dataset", default="soc-blogcatalog",
                    help="paper-dataset replica (repro.graphs.datasets)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_phase(4 if args.four_chips else 1)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    g, feat, labels, cfg = load_graph(args)
    model, batch = build_model(g, feat, labels, cfg, args)
    if args.four_chips:
        four_chip_phase(model, batch, cfg)
    else:
        train_phase(model, batch)
        serve_phase(g, feat, cfg, model, args)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
