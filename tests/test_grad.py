"""Gradient correctness for the differentiable Pallas aggregation path.

The custom VJP's backward pass is the group-aggregate kernel over the
TRANSPOSED schedule (feat cotangent) plus the group_edge_grad kernel over
the forward schedule (edge-value cotangent).  Everything here compares
`jax.grad` through the interpreted Pallas kernel against the natively
differentiated XLA reference.
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.advisor import plan_for
from repro.core.aggregate import PlanExecutor
from repro.core.model import AggConfig
from repro.core.partition import partition_graph, transpose_graph
from repro.graphs.csr import from_edges, random_power_law
from repro.kernels.ops import DeviceSchedule, aggregate
from repro.models.gnn import GNNConfig, build_gnn


def _scheds(g, ev, *, gs=8, gpt=8, ont=8, src_win=64):
    p = partition_graph(g, gs=gs, gpt=gpt, ont=ont, src_win=src_win,
                        edge_vals=ev)
    gT, evT, perm = transpose_graph(g, ev)
    pT = partition_graph(gT, gs=gs, gpt=gpt, ont=ont, src_win=src_win,
                         edge_vals=evT)
    return DeviceSchedule(p), DeviceSchedule(pT, edge_perm=perm)


@pytest.mark.parametrize("gs", [4, 8, 16])
def test_grad_feat_static_edge_values(gs, rng):
    """Static (GCN-style) edge values: d out / d feat via the transposed
    schedule matches XLA autodiff, across group sizes."""
    g = random_power_law(150, 5.0, seed=11)
    ev = rng.uniform(0.5, 1.5, g.num_edges).astype(np.float32)
    sched, sched_bwd = _scheds(g, ev, gs=gs)
    feat = jnp.asarray(rng.standard_normal((g.num_nodes, 24)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((g.num_nodes, 24)), jnp.float32)

    gx = jax.grad(lambda f: (aggregate(f, sched, dt=16, backend="xla")
                             * cot).sum())(feat)
    gp = jax.grad(lambda f: (aggregate(f, sched, dt=16,
                                       backend="pallas_interpret",
                                       sched_bwd=sched_bwd)
                             * cot).sum())(feat)
    np.testing.assert_allclose(gp, gx, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("gs", [4, 8, 16])
def test_grad_dynamic_edge_value_cotangents(gs, rng):
    """Dynamic (GAT-style) edge values: BOTH cotangents — feat via the
    transposed schedule, edge values via the per-edge gather-dot kernel —
    across group sizes."""
    g = random_power_law(130, 4.0, seed=12)
    ev0 = rng.uniform(0.5, 1.5, g.num_edges).astype(np.float32)
    sched, sched_bwd = _scheds(g, ev0, gs=gs)
    feat = jnp.asarray(rng.standard_normal((g.num_nodes, 20)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((g.num_nodes, 20)), jnp.float32)
    evj = jnp.asarray(ev0)

    def loss(backend, sb):
        def f(feat, ev):
            out = aggregate(feat, sched, dt=16, backend=backend,
                            edge_values=ev, sched_bwd=sb)
            return (out * cot).sum()
        return f

    gx_f, gx_e = jax.grad(loss("xla", None), argnums=(0, 1))(feat, evj)
    gp_f, gp_e = jax.grad(loss("pallas_interpret", sched_bwd),
                          argnums=(0, 1))(feat, evj)
    np.testing.assert_allclose(gp_f, gx_f, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(gp_e, gx_e, atol=1e-4, rtol=1e-4)


def test_grad_works_under_jit(rng):
    """The custom VJP composes with jit (the trainer's step function)."""
    g = random_power_law(80, 4.0, seed=13)
    ev = np.ones(g.num_edges, np.float32)
    sched, sched_bwd = _scheds(g, ev)
    feat = jnp.asarray(rng.standard_normal((g.num_nodes, 8)), jnp.float32)

    @jax.jit
    def gfn(f):
        return jax.grad(lambda x: aggregate(
            x, sched, dt=8, backend="pallas_interpret",
            sched_bwd=sched_bwd).sum())(f)

    gx = jax.grad(lambda x: aggregate(x, sched, dt=8,
                                      backend="xla").sum())(feat)
    np.testing.assert_allclose(gfn(feat), gx, atol=1e-4, rtol=1e-4)


def test_missing_edge_perm_raises(rng):
    g = random_power_law(40, 3.0, seed=14)
    ev = np.ones(g.num_edges, np.float32)
    p = partition_graph(g, gs=4, gpt=4, ont=8, src_win=32, edge_vals=ev)
    gT, evT, _ = transpose_graph(g, ev)
    pT = partition_graph(gT, gs=4, gpt=4, ont=8, src_win=32, edge_vals=evT)
    sched = DeviceSchedule(p)
    sched_bwd = DeviceSchedule(pT)          # no edge_perm attached
    feat = jnp.zeros((g.num_nodes, 4), jnp.float32)
    with pytest.raises(ValueError, match="edge_perm"):
        aggregate(feat, sched, backend="pallas_interpret",
                  edge_values=jnp.asarray(ev), sched_bwd=sched_bwd)


# ---------------------------------------------------------------------------
# transposed-schedule structure
# ---------------------------------------------------------------------------

def test_transpose_involution():
    """transpose(transpose(g)) == g at the partition level, and the edge
    permutations compose to the identity."""
    g = random_power_law(90, 5.0, seed=21)
    ev = np.random.default_rng(21).uniform(0.1, 2.0, g.num_edges
                                           ).astype(np.float32)
    gT, evT, perm1 = transpose_graph(g, ev)
    gTT, evTT, perm2 = transpose_graph(gT, evT)
    np.testing.assert_array_equal(gTT.indptr, g.indptr)
    np.testing.assert_array_equal(gTT.indices, g.indices)
    np.testing.assert_allclose(evTT, ev)
    np.testing.assert_array_equal(perm1[perm2], np.arange(g.num_edges))
    # identical partitions from identical graphs
    pa = partition_graph(g, gs=4, gpt=4, ont=8, src_win=32, edge_vals=ev)
    pb = partition_graph(gTT, gs=4, gpt=4, ont=8, src_win=32, edge_vals=evTT)
    np.testing.assert_array_equal(pa.nbrs, pb.nbrs)
    np.testing.assert_allclose(pa.edge_val, pb.edge_val)


def test_transpose_preserves_edge_multiset():
    """The transposed graph is the exact reversed edge multiset (no dedup,
    no symmetrization)."""
    src = np.array([0, 2, 2, 3, 1, 4])
    dst = np.array([1, 1, 0, 2, 4, 0])
    g = from_edges(5, src, dst, dedup=False)
    gT, _, perm = transpose_graph(g)
    rows, cols = g.to_coo()
    rT, cT = gT.to_coo()
    fwd = sorted(zip(cols.tolist(), rows.tolist()))
    bwd = sorted(zip(rT.tolist(), cT.tolist()))
    assert fwd == bwd
    assert gT.num_edges == g.num_edges
    # perm maps transposed edge order back to forward edge order
    np.testing.assert_array_equal(rows[perm], cT)
    np.testing.assert_array_equal(cols[perm], rT)


def test_tall_node_block_matches_xla(rng):
    """At the tallest node block the tuner searches (128 rows, here over a
    padded last block), the interpreted kernel matches XLA on a plan built
    with its backward: the forward and its gradient with the plan's static
    edge values, and with dynamic ones both cotangents — feat through the
    transposed schedule, edge values through the gather-dot kernel."""
    g = random_power_law(300, 5.0, seed=34)
    ev = jnp.asarray(rng.uniform(0.5, 1.5, g.num_edges), jnp.float32)
    plan = plan_for(g, in_dim=24, with_backward=True,
                    config=AggConfig(gs=8, gpt=16, dt=16, src_win=128,
                                     ont=128))
    assert plan.partition.ont == plan.partition_bwd.ont == 128
    feat = jnp.asarray(rng.standard_normal((g.num_nodes, 24)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((g.num_nodes, 24)), jnp.float32)

    def run(backend):
        ex = PlanExecutor(plan, backend=backend)
        loss = lambda f, e: (ex.aggregate_edges(f, e) * cot).sum()
        return (ex(feat), jax.grad(lambda f: (ex(f) * cot).sum())(feat),
                ex.aggregate_edges(feat, ev),
                *jax.grad(loss, argnums=(0, 1))(feat, ev))

    for got, want in zip(run("pallas_interpret"), run("xla")):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# end-to-end: 2-layer models through the advisor path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["gcn", "gat"])
def test_model_grad_pallas_matches_xla(arch, rng):
    """Acceptance: jax.grad of a 2-layer model loss through
    backend="pallas_interpret" matches backend="xla" within 1e-4 on a
    200+ node random graph."""
    g = random_power_law(220, 5.0, seed=31)
    cc = AggConfig(gs=8, gpt=8, ont=8, src_win=64, dt=16)
    feat = jnp.asarray(rng.standard_normal((g.num_nodes, 16)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 4, g.num_nodes).astype(np.int32))
    cfg = GNNConfig(arch=arch, in_dim=16, hidden_dim=8, num_classes=4,
                    num_layers=2, backend="xla")
    mx = build_gnn(g, cfg, reorder="off", config=cc, seed=0)
    mp = build_gnn(g, dataclasses.replace(cfg, backend="pallas_interpret"),
                   reorder="off", config=cc, seed=0)
    assert mp.plan.partition_bwd is not None    # auto-attached for pallas
    gx = jax.grad(lambda p: mx.loss(p, feat, labels)[0])(mx.params)
    gp = jax.grad(lambda p: mp.loss(p, feat, labels)[0])(mp.params)
    for k in gx:
        np.testing.assert_allclose(gp[k], gx[k], atol=1e-4, rtol=1e-4,
                                   err_msg=k)


@pytest.mark.parametrize("gs", [4, 8, 16])
def test_model_grad_both_variants(gs, rng):
    """The kernel differentiates correctly end to end at every group size
    (the (gpt, gs, src_win) compare mask is the shape gs changes)."""
    g = random_power_law(210, 4.0, seed=32)
    cc = AggConfig(gs=gs, gpt=8, ont=8, src_win=64, dt=16)
    feat = jnp.asarray(rng.standard_normal((g.num_nodes, 12)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 3, g.num_nodes).astype(np.int32))
    cfg = GNNConfig(arch="gcn", in_dim=12, hidden_dim=8, num_classes=3,
                    num_layers=2, backend="xla")
    mx = build_gnn(g, cfg, reorder="off", config=cc, seed=1)
    mp = build_gnn(g, dataclasses.replace(cfg, backend="pallas_interpret"),
                   reorder="off", config=cc, seed=1)
    gx = jax.grad(lambda p: mx.loss(p, feat, labels)[0])(mx.params)
    gp = jax.grad(lambda p: mp.loss(p, feat, labels)[0])(mp.params)
    for k in gx:
        np.testing.assert_allclose(gp[k], gx[k], atol=1e-4, rtol=1e-4,
                                   err_msg=k)


def test_training_step_decreases_loss_on_pallas(rng):
    """A few optimizer steps through the Pallas kernel reduce the loss."""
    from repro.models.gnn import make_gnn_train_step
    from repro.optim.adamw import AdamWConfig, adamw_init

    g = random_power_law(150, 4.0, seed=33)
    cc = AggConfig(gs=8, gpt=8, ont=8, src_win=64, dt=16)
    cfg = GNNConfig(arch="gcn", in_dim=10, hidden_dim=8, num_classes=3,
                    num_layers=2, backend="pallas_interpret")
    model = build_gnn(g, cfg, reorder="off", config=cc, seed=0)
    feat = jnp.asarray(rng.standard_normal((g.num_nodes, 10)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 3, g.num_nodes).astype(np.int32))
    step_fn = make_gnn_train_step(model, AdamWConfig(lr=5e-2), jit=False)
    state = (model.params, adamw_init(model.params))
    batch = {"feat": feat, "labels": labels}
    losses = []
    for _ in range(5):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
