"""GNN models (the paper's benchmarks): GCN/GIN vs dense-adjacency oracles +
training improves a planted node-classification task."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.graphs.csr import random_community_graph
from repro.graphs.datasets import PAPER_DATASETS, make_dataset
from repro.models.gnn import GNNConfig, build_gnn, gcn_edge_values


def _dense_adj(g, vals=None):
    A = np.zeros((g.num_nodes, g.num_nodes), np.float32)
    rows, cols = g.to_coo()
    if vals is None:
        vals = np.ones(g.num_edges, np.float32)
    # dedup-safe accumulation
    np.add.at(A, (rows, cols), vals)
    return A


def test_gcn_matches_dense_oracle(community_graph, rng):
    g = community_graph
    cfg = GNNConfig(arch="gcn", in_dim=12, hidden_dim=8, num_classes=4,
                    num_layers=2, backend="xla")
    model = build_gnn(g, cfg, reorder="off", tune_iters=2)
    feat = rng.standard_normal((g.num_nodes, 12)).astype(np.float32)
    got = model.logits(model.params, jnp.asarray(feat))
    g2, vals = gcn_edge_values(g)
    A = _dense_adj(g2, vals)
    x = feat
    for i in range(2):
        x = A @ (x @ np.asarray(model.params[f"w{i}"]))
        if i < 1:
            x = np.maximum(x, 0)
    np.testing.assert_allclose(got, x, atol=1e-2, rtol=1e-3)


def test_gin_matches_dense_oracle(community_graph, rng):
    g = community_graph
    eps = 0.1
    cfg = GNNConfig(arch="gin", in_dim=10, hidden_dim=8, num_classes=3,
                    num_layers=2, gin_eps=eps, backend="xla")
    model = build_gnn(g, cfg, reorder="off", tune_iters=2)
    feat = rng.standard_normal((g.num_nodes, 10)).astype(np.float32)
    got = model.logits(model.params, jnp.asarray(feat))
    A = _dense_adj(g)
    x2 = feat
    for i in range(2):
        h = (1 + eps) * x2 + A @ x2
        x2 = np.maximum(h @ np.asarray(model.params[f"w{i}"]), 0) \
            @ np.asarray(model.params[f"w{i}b"])
    np.testing.assert_allclose(got, x2, atol=1e-2, rtol=1e-3)


def test_gcn_learns_planted_communities():
    """Nodes labeled by community; a 2-layer GCN must beat chance easily."""
    g = random_community_graph(4, 30, p_intra=0.5,
                               p_inter_edges_per_node=0.1, seed=3)
    n = g.num_nodes
    labels = np.repeat(np.arange(4), 30)[:n].astype(np.int32)
    rng = np.random.default_rng(0)
    feat = (rng.standard_normal((n, 16)) * 0.5
            + labels[:, None] * 0.0).astype(np.float32)  # uninformative feats
    cfg = GNNConfig(arch="gcn", in_dim=16, hidden_dim=16, num_classes=4,
                    num_layers=2, backend="xla")
    model = build_gnn(g, cfg, reorder="off", tune_iters=2)
    # order features to match the plan's node order
    featj = jnp.asarray(model.plan.renumber_features(feat))
    labj = jnp.asarray(labels if model.plan.perm is None
                       else labels[np.argsort(model.plan.perm)][...])
    if model.plan.perm is not None:
        inv = np.empty(n, np.int64); inv[model.plan.perm] = np.arange(n)
        labj = jnp.asarray(labels[inv])
    params = model.params
    lr = 0.05
    loss0 = float(model.loss(params, featj, labj)[0])
    for _ in range(60):
        grads = jax.grad(lambda p: model.loss(p, featj, labj)[0])(params)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    loss1, metrics = model.loss(params, featj, labj)
    assert float(loss1) < loss0
    assert float(metrics["accuracy"]) > 0.5      # 4 classes, chance = 0.25


def test_paper_dataset_replicas():
    for name in ["cora", "proteins_full", "artist"]:
        g, spec, feat = make_dataset(name, max_nodes=2000, seed=0)
        # community-structured replicas overshoot the cap by sampled sizes
        assert g.num_nodes <= 2000 * 1.3
        assert feat.shape == (g.num_nodes, spec.dim)
        assert g.num_edges > 0
    assert len(PAPER_DATASETS) == 16       # Table 1 replicas + reddit


def test_gat_matches_dense_oracle(community_graph, rng):
    """One-head GAT: dynamic edge values through the group schedule must
    equal the dense softmax-attention oracle over A + I, each destination's
    softmax shifted by its own max (paper §4.2 type-2 with per-forward
    edge features)."""
    import jax
    g = community_graph
    cfg = GNNConfig(arch="gat", in_dim=10, hidden_dim=8, num_classes=5,
                    num_layers=2, backend="xla")
    model = build_gnn(g, cfg, reorder="off", tune_iters=2)
    assert model.params["a0s"].shape == (8, 1)          # (head width, heads)
    feat = rng.standard_normal((g.num_nodes, 10)).astype(np.float32)
    got = np.asarray(model.logits(model.params, jnp.asarray(feat)))

    # dense oracle
    A = (_dense_adj(g.with_self_loops()) > 0)
    x = feat
    for i in range(2):
        z = x @ np.asarray(model.params[f"w{i}"])
        s_src = z @ np.asarray(model.params[f"a{i}s"])[:, 0]
        s_dst = z @ np.asarray(model.params[f"a{i}d"])[:, 0]
        e = s_dst[:, None] + s_src[None, :]
        e = np.where(e > 0, e, 0.2 * e)                 # leaky relu
        e = np.where(A, e, -np.inf)
        e = e - e.max(axis=1, keepdims=True)
        w = np.where(A, np.exp(e), 0.0)
        x = (w @ z) / w.sum(1, keepdims=True)
        if i < 1:
            x = np.where(x > 0, x, np.exp(np.minimum(x, 0)) - 1)   # elu
    np.testing.assert_allclose(got, x, atol=1e-3, rtol=1e-3)


def test_gat_dynamic_values_pallas_backend(community_graph, rng):
    """The dynamic-edge-value path must agree between xla and the Pallas
    interpret kernel."""
    import jax
    g = community_graph
    cfg_x = GNNConfig(arch="gat", in_dim=6, hidden_dim=4, num_classes=3,
                      num_layers=1, backend="xla")
    model = build_gnn(g, cfg_x, reorder="off", tune_iters=2)
    feat = jnp.asarray(rng.standard_normal((g.num_nodes, 6)), jnp.float32)
    got_x = model.logits(model.params, feat)
    model.executor.backend = "pallas_interpret"
    model.executor.dt = 8
    got_p = model.logits(model.params, feat)
    np.testing.assert_allclose(got_x, got_p, atol=1e-3, rtol=1e-3)
