"""GraphSAGE-style neighbor-sampled mini-batch training, end to end.

Where `examples/train_gcn.py` plans the WHOLE graph once and takes
full-batch steps, this driver samples a fanout-bounded frontier per step
(`repro.sampling`): every layer gets a bipartite block, every block gets an
advisor plan from the serving plan cache, and the jitted train step
compiles once per pow2 shape bucket.  Per-step cost is bounded by
``batch_nodes * prod(fanout_l + 1)`` regardless of graph size — the regime
full-size Type III graphs (reddit, amazon) require.

    PYTHONPATH=src python examples/train_sage.py [--steps 60] \
        [--dataset pubmed] [--backend xla] [--fanouts 10,5]

With ``--backend pallas_interpret`` forward AND backward aggregation of
every block run through the group-aggregate kernel (backward = transposed
schedule), exactly like the full-batch trainer.
"""
import argparse
import os
import tempfile

import jax
import numpy as np

from repro.graphs.datasets import make_dataset
from repro.models.gnn import GNNConfig, init_gnn_params, planted_labels
from repro.optim.adamw import AdamWConfig, adamw_init, cosine_schedule
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.sampling import LoaderConfig, SampledLoader, SampledTrainStep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="pubmed")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--max-nodes", type=int, default=6000)
    ap.add_argument("--batch-nodes", type=int, default=512)
    ap.add_argument("--fanouts", default="10,5")
    ap.add_argument("--backend", default=None,
                    choices=["xla", "pallas", "pallas_interpret"],
                    help="default: pallas on a TPU, xla elsewhere")
    args = ap.parse_args()

    fanouts = tuple(int(f) for f in args.fanouts.split(","))
    g, spec, feat = make_dataset(args.dataset, max_nodes=args.max_nodes,
                                 seed=0, max_dim=64)
    cfg = GNNConfig(arch="gcn", in_dim=feat.shape[1], hidden_dim=32,
                    num_classes=spec.num_classes, num_layers=len(fanouts),
                    backend=args.backend)
    # small enough here for a planted (teacher-labelled) task — full-size
    # graphs would use `structural_labels` (see repro.launch.train)
    labels = planted_labels(g, cfg, feat)
    print(f"[sage] {args.dataset}: N={g.num_nodes} E={g.num_edges} "
          f"fanouts={fanouts} batch={args.batch_nodes}")

    loader = SampledLoader(
        g, feat, labels, cfg,
        LoaderConfig(fanouts=fanouts, batch_nodes=args.batch_nodes, seed=0))
    step_fn = SampledTrainStep(
        cfg, AdamWConfig(lr=5e-3, schedule=cosine_schedule(10, args.steps)))
    params = init_gnn_params(cfg, jax.random.PRNGKey(0))
    trainer = Trainer(
        TrainerConfig(ckpt_dir=os.path.join(tempfile.gettempdir(),
                                            f"sage_{args.dataset}"),
                      ckpt_every=50, log_every=10),
        step_fn, loader, (params, adamw_init(params)))
    try:
        trainer.run(args.steps)
    finally:
        trainer.close()

    hist = trainer.metrics_history
    cache = loader.stats()["cache"]
    print(f"[sage] steps={len(hist)} "
          f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} "
          f"acc {hist[-1]['accuracy']:.3f} "
          f"avg_step={trainer.avg_step_time()*1e3:.1f}ms")
    print(f"[sage] plan-cache hit_rate={cache['hit_rate']:.2f} "
          f"(exact={cache['exact_hits']} config={cache['config_hits']} "
          f"miss={cache['misses']}) jit buckets={step_fn.num_buckets} "
          f"traces={step_fn.traces}")


if __name__ == "__main__":
    main()
