"""End-to-end driver: train a GCN node classifier on a paper-dataset replica
through the full GNNAdvisor pipeline (extract -> tune -> renumber ->
group-schedule -> train), with checkpoint/restart fault tolerance.

Training runs through the advisor path on any backend: with
``--backend pallas_interpret`` (or ``pallas`` on a TPU) the forward pass is
the group-aggregate kernel and the backward pass is the SAME kernel over the
transposed schedule (the custom VJP installed by `repro.kernels.ops`).

    PYTHONPATH=src python examples/train_gcn.py [--steps 300] [--dataset cora] \
        [--backend pallas_interpret]
"""
import argparse
import os
import tempfile

import numpy as np
import jax.numpy as jnp

from repro.graphs.datasets import make_dataset
from repro.models.gnn import (GNNConfig, build_gnn, make_gnn_train_step,
                              planted_labels)
from repro.optim.adamw import AdamWConfig, adamw_init, cosine_schedule
from repro.runtime.trainer import FailureInjector, Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cora")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--max-nodes", type=int, default=2708)
    ap.add_argument("--backend", default=None,
                    choices=["xla", "pallas", "pallas_interpret"],
                    help="default: pallas on a TPU, xla elsewhere")
    ap.add_argument("--arch", default="gcn", choices=["gcn", "gin", "gat"])
    ap.add_argument("--fail-at", type=int, default=150,
                    help="inject a simulated crash at this step (-1 = off)")
    args = ap.parse_args()

    g, spec, feat = make_dataset(args.dataset, max_nodes=args.max_nodes, seed=0)
    in_dim = min(spec.dim, 128)
    feat = feat[:, :in_dim].astype(np.float32)

    cfg = GNNConfig(arch=args.arch, in_dim=in_dim, hidden_dim=32,
                    num_classes=spec.num_classes, num_layers=2,
                    backend=args.backend)
    labels = planted_labels(g, cfg, feat)
    print(f"[train_gcn] {args.dataset}: {g.num_nodes} nodes, "
          f"{g.num_edges} edges, {spec.num_classes} classes")

    model = build_gnn(g, cfg, reorder="auto", tune_iters=8, seed=0)
    print(f"[train_gcn] advisor: gs={model.plan.config.gs} "
          f"gpt={model.plan.config.gpt} src_win={model.plan.config.src_win} "
          f"renumbered={model.plan.perm is not None} "
          f"tiles={model.plan.stats['tiles']} backend={cfg.backend} "
          f"bwd_tiles={model.plan.partition_bwd.num_tiles if model.plan.partition_bwd is not None else '-'}")
    featp = jnp.asarray(model.plan.renumber_features(feat))
    labp = jnp.asarray(model.plan.renumber_features(labels))

    opt = AdamWConfig(lr=1e-2, schedule=cosine_schedule(20, args.steps))
    step_fn = make_gnn_train_step(model, opt)
    batch = {"feat": featp, "labels": labp}

    ckpt = os.path.join(tempfile.gettempdir(), "repro_gcn_ckpt")
    trainer = Trainer(
        TrainerConfig(ckpt_dir=ckpt, ckpt_every=50, log_every=50),
        step_fn, lambda step: batch, (model.params, adamw_init(model.params)),
        injector=FailureInjector([args.fail_at] if args.fail_at >= 0 else []))
    (params, _) = trainer.run(args.steps)
    hist = trainer.metrics_history
    if hist:
        print(f"[train_gcn] loss: step0={hist[0]['loss']:.4f} -> "
              f"step{len(hist)}={hist[-1]['loss']:.4f}")
    loss, metrics = model.loss(params, featp, labp)
    print(f"[train_gcn] final loss={float(loss):.4f} "
          f"accuracy={float(metrics['accuracy']):.3f} "
          f"avg_step={trainer.avg_step_time()*1e3:.1f}ms "
          f"(survived {len(trainer.injector.fired)} injected failure(s))")


if __name__ == "__main__":
    main()
