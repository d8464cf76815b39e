"""Multi-head GAT through the normal path (`build_gnn` -> `PlanExecutor` ->
group kernel -> `make_gnn_train_step`) against the benchmark's plain
float32 reference, `chipbench/lib/arch/gat.py`, at a small size: 4
communities of 64 nodes, heads [2, 2, 3] of width [8, 8, 5], D = 12, 5
labels, on both the XLA path and the interpreted kernel."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from chipbench.lib import graphs, reference  # noqa: E402
from chipbench.lib.arch import gat  # noqa: E402
from repro.core.advisor import plan_for  # noqa: E402
from repro.core.aggregate import PlanExecutor  # noqa: E402
from repro.core.model import AggConfig  # noqa: E402
from repro.graphs.csr import CSRGraph  # noqa: E402
from repro.models.gnn import (GNNConfig, build_gnn,  # noqa: E402
                              make_gnn_train_step)
from repro.obs import process_tracer  # noqa: E402
from repro.optim.adamw import AdamWConfig, adamw_init  # noqa: E402

BACKENDS = ["xla", "pallas_interpret"]
MODEL = {"arch": "gat", "num_layers": 3, "heads": [2, 2, 3],
         "head_dims": [8, 8, 5], "skip_layers": [1], "gat_slope": 0.2}
GRAPH = {"feat_dim": 12, "num_classes": 5, "labels": "multi"}
OPT = {"lr": 5e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.0,
       "grad_clip": None}
CONFIG = AggConfig(gs=4, gpt=8, ont=8, src_win=64, dt=8)
NUM = reference.Numerics("highest")


@pytest.fixture(scope="module")
def csr():
    return graphs.community(4, 64, p_intra=0.15, seed=3)


@pytest.fixture(scope="module")
def inputs(csr):
    n = len(csr[0]) - 1
    kp, kf, kl = jax.random.split(jax.random.PRNGKey(11), 3)
    params = reference.init_params(kp, MODEL, 12, 5)
    feat = jax.random.normal(kf, (n, 12), jnp.float32)
    labels = jax.random.bernoulli(kl, 0.5, (n, 5)).astype(jnp.float32)
    return params, feat, labels


def _model(csr, backend, model=MODEL, graph=GRAPH):
    cfg = GNNConfig(**gat.program_config(model, graph, "float32"),
                    backend=backend)
    return build_gnn(CSRGraph(*csr), cfg, reorder="off", config=CONFIG)


def _ref_graph(csr):
    return gat.graph_arrays(*csr)


def _close(got, want, rtol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=rtol * max(np.abs(want).max(), 1e-30))


@pytest.mark.parametrize("backend", BACKENDS)
def test_logits_and_loss_match_reference(csr, inputs, backend):
    params, feat, labels = inputs
    m = _model(csr, backend)
    g = _ref_graph(csr)
    _close(m.logits(params, feat), gat.logits(params, feat, g, MODEL, NUM))
    loss, aux = m.loss(params, feat, labels)
    _close(loss, reference.loss(params, feat, labels, g, MODEL, NUM))
    assert 0.0 <= float(aux["accuracy"]) <= 1.0


@pytest.mark.parametrize("backend", BACKENDS)
def test_every_gradient_leaf_matches_reference(csr, inputs, backend):
    params, feat, labels = inputs
    m = _model(csr, backend)
    got = jax.grad(lambda p: m.loss(p, feat, labels)[0])(params)
    want = jax.grad(reference.loss)(params, feat, labels, _ref_graph(csr),
                                    MODEL, NUM)
    assert set(got) == set(want) == set(gat.param_shapes(MODEL, 12, 5))
    for k in want:
        _close(got[k], want[k], rtol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_three_adam_steps_match_reference(csr, inputs, backend):
    params, feat, labels = inputs
    m = _model(csr, backend)
    step = make_gnn_train_step(m, AdamWConfig(
        lr=OPT["lr"], b1=OPT["b1"], b2=OPT["b2"], eps=OPT["eps"],
        weight_decay=OPT["weight_decay"], grad_clip=OPT["grad_clip"]),
        jit=False)
    state, losses = (params, adamw_init(params)), []
    for _ in range(3):
        state, out = step(state, {"feat": feat, "labels": labels})
        losses.append(float(out["loss"]))
    ref_losses, _, ref_params = reference.adamw_steps(
        params, feat, labels, _ref_graph(csr), MODEL, OPT, NUM, 3)
    _close(losses, ref_losses)
    for k in ref_params:
        # the change over three steps, each leaf against its own size
        _close(state[0][k] - params[k], ref_params[k] - params[k], rtol=1e-4)


def _far_below(csr, inputs):
    """One head-averaged layer whose scores depend on the destination
    alone (a_s = 0), and node 0's scores, on every head, 200 below the
    rest's: LeakyReLU(-1000) = -200."""
    model = {**MODEL, "num_layers": 1, "heads": [2], "head_dims": [5],
             "skip_layers": []}
    params0, feat, _ = inputs
    params = {"w0": params0["w0"][:, :10],
              "a0s": jnp.zeros((5, 2)), "a0d": params0["a0d"][:5]}
    z0 = (feat[0] @ params["w0"]).reshape(2, 5)
    s = jnp.sum(z0 * params["a0d"].T, axis=1)                 # (2,)
    # scale node 0 to -1000 on head 0, then head 1's vector to match
    feat = feat.at[0].multiply(-1000.0 / s[0])
    params["a0d"] = params["a0d"].at[:, 1].multiply(s[0] / s[1])
    return model, params, feat


@pytest.mark.parametrize("backend", BACKENDS)
def test_destination_far_below_the_global_max(csr, inputs, backend):
    model, params, feat = _far_below(csr, inputs)
    g = _ref_graph(csr)
    rows, cols = np.asarray(g.rows), np.asarray(g.cols)
    z = np.asarray(feat @ params["w0"], np.float64).reshape(-1, 2, 5)
    s_d = np.sum(z * np.asarray(params["a0d"]).T, axis=-1)
    e = np.where(s_d > 0, s_d, 0.2 * s_d)[rows]                 # (E, 2)
    assert (e[rows == 0] < e.max() - 199).all()
    # a shift by the global max underflows node 0's every weight to 0
    assert not np.exp((e - e.max()).astype(np.float32))[rows == 0].any()

    m = _model(csr, backend, model)
    got = np.asarray(m.logits(params, feat))
    want = np.asarray(gat.logits(params, feat, g, model, NUM))
    assert np.isfinite(got).all()
    _close(got, want)
    # equal scores: node 0 averages its neighbours' heads, then the heads
    nbrs = cols[rows == 0]
    _close(got[0], z[nbrs].mean(axis=(0, 1)))


@pytest.mark.parametrize("heads,width", [(1, 8), (3, 8), (4, 1), (2, 130)],
                         ids=["one_head", "three_heads", "ones_columns",
                              "two_tiles_a_head"])
def test_per_head_edge_cotangent_matches_reference_grad(csr, heads, width):
    """Per edge and head, the dot over that head's columns only, through
    the gather-dot kernel, against `jax.grad` of the reference's
    aggregation head by head; and the feature cotangent through the
    transposed schedule."""
    g = CSRGraph(*csr).with_self_loops()
    plan = plan_for(g, arch="gat", in_dim=12, hidden_dim=heads * width,
                    config=CONFIG, with_backward=True)
    kf, kv, kc = jax.random.split(jax.random.PRNGKey(heads * width), 3)
    n, e = g.num_nodes, g.num_edges
    feat = jax.random.normal(kf, (n, heads * width))
    ev = jax.random.uniform(kv, (e, heads), minval=0.1)
    cot = jax.random.normal(kc, (n, heads * width))
    rows, cols = (jnp.asarray(a) for a in g.to_coo())

    def ref(f, v):
        out = jnp.concatenate([NUM.aggregate(
            f[:, h * width:(h + 1) * width],
            reference.Graph(rows, cols, v[:, h])) for h in range(heads)],
            axis=1)
        return jnp.sum(cot * out)

    want = jax.grad(ref, argnums=(0, 1))(feat, ev)
    ex = PlanExecutor(plan, backend="pallas_interpret")
    got = jax.grad(lambda f, v: jnp.sum(cot * ex.aggregate_edges(f, v)),
                   argnums=(0, 1))(feat, ev)
    assert got[1].shape == (e, heads)
    for a, b in zip(got, want):
        _close(a, b, rtol=1e-5)


@pytest.mark.parametrize("arch,want_width,want_heads", [
    ("gat", 16, 3), ("gcn", 8, 1)])
def test_tuner_plans_at_the_aggregated_width(csr, arch, want_width,
                                            want_heads):
    """GAT is tuned at its widest heads x head width (2 x 8 here), not at
    its input width; GCN at its hidden width as before."""
    if arch == "gat":
        cfg = GNNConfig(**gat.program_config(MODEL, GRAPH, "float32"),
                        backend="xla")
    else:
        cfg = GNNConfig(arch="gcn", in_dim=12, hidden_dim=8, num_classes=5,
                        backend="xla")
    m = build_gnn(CSRGraph(*csr), cfg, reorder="off", tune_iters=2)
    reg = process_tracer().registry
    assert reg.get("plan_agg_width", {}).value == want_width
    assert reg.get("plan_edge_heads", {}).value == want_heads
    assert m.plan.reduce_dim_first and m.plan.arch.hidden_dim == want_width


def test_gnn_config_rejects_an_unknown_key():
    with pytest.raises(TypeError, match="attention_heads"):
        GNNConfig(arch="gat", attention_heads=(4, 4, 6))


@pytest.mark.parametrize("bad", [
    {"heads": (2, 2)},
    {"head_dims": (8, 8, 4)},
    {"skip_layers": (0,)},
    {"arch": "gcn", "heads": (2, 2, 3), "head_dims": ()},
], ids=["heads_per_layer", "last_width", "skip_across_widths",
        "heads_on_gcn"])
def test_gnn_config_rejects_inconsistent_gat_keys(bad):
    kw = {**gat.program_config(MODEL, GRAPH, "float32"), **bad}
    if kw["arch"] == "gcn":
        kw["skip_layers"] = ()
    with pytest.raises(ValueError):
        GNNConfig(**kw)


def test_one_head_gat_is_the_default_layout():
    """GAT with no GAT keys is the one-head case: each layer one head of
    the hidden width, the last of the classes."""
    cfg = GNNConfig(arch="gat", in_dim=6, hidden_dim=4, num_classes=3,
                    num_layers=2)
    assert cfg.heads == (1, 1) and cfg.head_dims == (4, 3)
    assert cfg.agg_hidden_dim == 4 and cfg.edge_heads == 1
    assert dataclasses.replace(cfg, heads=[1, 1]).heads == (1, 1)
