"""Serving-engine tour: resident graph in, per-node predictions out.

    PYTHONPATH=src python examples/serve_gnn.py

Walks the request path by hand — submit/step micro-batching, ego-graph
extraction sizes, plan-cache hits on a hot seed — then cross-checks a
batched answer against full-graph inference.
"""
import numpy as np
import jax.numpy as jnp

from repro.graphs.csr import random_power_law
from repro.models.gnn import GNNConfig, build_gnn
from repro.serving import ServingConfig, ServingEngine


def main():
    g = random_power_law(2000, 6.0, seed=0)
    cfg = GNNConfig(arch="gcn", in_dim=16, hidden_dim=16, num_classes=4,
                    num_layers=2)
    rng = np.random.default_rng(0)
    feat = rng.standard_normal((g.num_nodes, 16)).astype(np.float32)

    # train-or-load elsewhere; here a full-graph model donates its weights
    model = build_gnn(g, cfg, reorder="off", tune_iters=2)
    engine = ServingEngine(g, feat, cfg, params=model.params,
                           serving=ServingConfig(max_batch=8, tune_iters=2))
    print(f"resident graph: n={g.num_nodes} e={g.num_edges}, "
          f"ego radius = {engine.hops} hops")

    # --- request API: submit -> micro-batch -> per-seed logits ---
    reqs = [engine.submit(int(s)) for s in rng.integers(0, g.num_nodes, 12)]
    engine.step(force=True)
    print(f"served {len(reqs)} requests in "
          f"{engine.stats.batch_size.count} micro-batches; "
          f"avg subgraph = {engine.stats.sub_nodes.mean:.0f} nodes")

    # --- hot seed: second lookup is an exact plan-cache hit ---
    hot = int(reqs[0].seed)
    engine.serve_batch([hot])
    engine.serve_batch([hot])
    print(f"plan cache after hot repeat: {engine.cache.stats()}")

    # --- exactness: batched ego inference == full-graph inference ---
    full = np.asarray(model.logits(model.params, jnp.asarray(feat)))
    seeds = [7, 130, 1999]
    out = engine.serve_batch(seeds)
    err = np.abs(out - full[seeds]).max()
    print(f"batched vs full-graph max err: {err:.2e}")
    assert err <= 1e-5


if __name__ == "__main__":
    main()
