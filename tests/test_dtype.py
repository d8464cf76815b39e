"""The mixed-precision (bf16) pipeline + the dtype/alignment bugfix sweep.

Covers the end-to-end dtype policy (docs/performance.md): kernel-level
bf16 forward/grad parity against the f32 XLA reference, odd-feature-dim
alignment (the `dim_tile` regression), the dtype-aware tuner (honest
bytes_feat pricing, bounded rejection sampling), `Plan` round-tripping,
the schedule-static unvisited-block mask, the edge-value permute dedup,
and a 2-shard bf16-vs-f32 loss-curve comparison on cora (subprocess with
forced host devices).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.model import (AggConfig, KernelModel, config_infeasibility,
                              config_is_feasible, feat_dtype_align,
                              feat_dtype_bytes)
from repro.core.partition import partition_graph, transpose_graph
from repro.graphs.csr import random_power_law
from repro.kernels.ops import DeviceSchedule, aggregate, dim_tile

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

BACKENDS = ["xla", "pallas_interpret"]
if jax.default_backend() == "tpu":
    BACKENDS.append("pallas")


def _scheds(g, ev, *, gs=8, gpt=8, ont=8, src_win=64):
    p = partition_graph(g, gs=gs, gpt=gpt, ont=ont, src_win=src_win,
                        edge_vals=ev)
    gT, evT, perm = transpose_graph(g, ev)
    pT = partition_graph(gT, gs=gs, gpt=gpt, ont=ont, src_win=src_win,
                         edge_vals=evT)
    return DeviceSchedule(p), DeviceSchedule(pT, edge_perm=perm)


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))


# ---------------- dim-tile alignment (odd-dim bugfix) ----------------


def test_dim_tile_alignment_units():
    # f32: 8-aligned; 16-bit types: 16-aligned
    assert dim_tile(128, 100, np.float32) == 104
    assert dim_tile(128, 100, jnp.bfloat16) == 112
    assert dim_tile(128, 130, np.float32) == 128        # clamp to dt
    assert dim_tile(128, 4, np.float32) == 8            # min one unit
    assert dim_tile(8, 24, jnp.bfloat16) == 32          # one tile spans D
    assert dim_tile(64, 200, np.float32) == 128         # narrower: 128 lanes
    for d in range(1, 300, 7):
        for dt in (8, 64, 128, 256):
            for dtype, unit in ((np.float32, 8), (jnp.bfloat16, 16)):
                t = dim_tile(dt, d, dtype)
                d_pad = -(-d // unit) * unit
                # the chip's block rule: a tile narrower than the padded
                # width is a multiple of 128 lanes, else it spans it
                assert t == d_pad or (t < d_pad and t % 128 == 0)


@pytest.mark.parametrize("dim", [100, 52, 9])
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_odd_dim_forward_parity(dim, dtype, rng):
    """Regression: non-multiple-of-8 feature dims used to produce a
    lane-unaligned dim tile (dt_eff = D) that only interpret mode
    tolerates; now D rounds up to the dtype's alignment unit first."""
    g = random_power_law(150, 5.0, seed=7)
    ev = rng.uniform(0.5, 1.5, g.num_edges).astype(np.float32)
    sched, _ = _scheds(g, ev)
    feat32 = rng.standard_normal((g.num_nodes, dim)).astype(np.float32)
    want = aggregate(jnp.asarray(feat32), sched, dt=128, backend="xla")
    got = aggregate(jnp.asarray(feat32, dtype=dtype), sched, dt=128,
                    backend="pallas_interpret")
    tol = 1e-4 if dtype == np.float32 else 5e-2
    assert _rel_err(got, want) < tol


@pytest.mark.parametrize("dim", [100, 20])
def test_odd_dim_edge_grad_parity(dim, rng):
    """The second kernel entry point (group_edge_grad) under odd dims:
    dynamic edge-value cotangents match XLA autodiff."""
    g = random_power_law(120, 4.0, seed=8)
    ev0 = rng.uniform(0.5, 1.5, g.num_edges).astype(np.float32)
    sched, sched_bwd = _scheds(g, ev0)
    feat = jnp.asarray(rng.standard_normal((g.num_nodes, dim)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((g.num_nodes, dim)), jnp.float32)
    evj = jnp.asarray(ev0)

    def loss(backend):
        return lambda e: (aggregate(feat, sched, dt=128, backend=backend,
                                    edge_values=e, sched_bwd=sched_bwd)
                          * cot).sum()

    gx = jax.grad(loss("xla"))(evj)
    gp = jax.grad(loss("pallas_interpret"))(evj)
    np.testing.assert_allclose(gp, gx, atol=1e-3, rtol=1e-3)


# ---------------- bf16 kernel parity ----------------


@pytest.mark.parametrize("gs", [4, 8, 16])
def test_bf16_forward_parity(gs, rng):
    """bf16 features through the Pallas kernel vs the f32 XLA reference:
    rounding-of-inputs error only (accumulation is f32)."""
    g = random_power_law(200, 5.0, seed=11)
    ev = rng.uniform(0.5, 1.5, g.num_edges).astype(np.float32)
    sched, _ = _scheds(g, ev, gs=gs)
    feat32 = rng.standard_normal((g.num_nodes, 32)).astype(np.float32)
    want = aggregate(jnp.asarray(feat32), sched, dt=32, backend="xla")
    got = aggregate(jnp.asarray(feat32, jnp.bfloat16), sched, dt=32,
                    backend="pallas_interpret", out_dtype=jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    assert _rel_err(got, want) < 5e-2


def test_out_dtype_default_is_f32(rng):
    g = random_power_law(100, 4.0, seed=12)
    ev = np.ones(g.num_edges, np.float32)
    sched, _ = _scheds(g, ev)
    feat = jnp.asarray(rng.standard_normal((g.num_nodes, 16)), jnp.bfloat16)
    out = aggregate(feat, sched, dt=16, backend="pallas_interpret")
    assert out.dtype == jnp.float32          # historical contract


@pytest.mark.parametrize("dynamic", [False, True])
def test_bf16_grad_parity(dynamic, rng):
    """bf16 custom VJP (static + dynamic edge values) vs f32 XLA autodiff;
    cotangents come back in the primal dtypes."""
    g = random_power_law(150, 5.0, seed=13)
    ev0 = rng.uniform(0.5, 1.5, g.num_edges).astype(np.float32)
    sched, sched_bwd = _scheds(g, ev0)
    feat32 = rng.standard_normal((g.num_nodes, 24)).astype(np.float32)
    cot = jnp.asarray(rng.standard_normal((g.num_nodes, 24)), jnp.float32)
    kw = dict(dt=16, sched_bwd=sched_bwd)
    if dynamic:
        kw["edge_values"] = jnp.asarray(ev0)

    gx = jax.grad(lambda f: (aggregate(
        f, sched, backend="xla", **kw) * cot).sum())(jnp.asarray(feat32))
    gp = jax.grad(lambda f: (aggregate(
        f, sched, backend="pallas_interpret", out_dtype=jnp.bfloat16,
        **kw).astype(jnp.float32) * cot).sum())(
        jnp.asarray(feat32, jnp.bfloat16))
    assert gp.dtype == jnp.bfloat16
    assert _rel_err(gp, gx) < 6e-2

    if dynamic:
        ge = jax.grad(lambda e: (aggregate(
            jnp.asarray(feat32, jnp.bfloat16), sched,
            backend="pallas_interpret", edge_values=e, sched_bwd=sched_bwd)
            .astype(jnp.float32) * cot).sum())(
            jnp.asarray(ev0, jnp.bfloat16))
        assert ge.dtype == jnp.bfloat16
        gex = jax.grad(lambda e: (aggregate(
            jnp.asarray(feat32), sched, backend="xla", edge_values=e,
            sched_bwd=sched_bwd) * cot).sum())(jnp.asarray(ev0))
        assert _rel_err(ge, gex) < 6e-2


# ---------------- dtype-aware model + tuner ----------------


def test_feat_dtype_helpers():
    assert feat_dtype_bytes("float32") == 4
    assert feat_dtype_bytes("bfloat16") == 2
    assert feat_dtype_align("float32") == 8
    assert feat_dtype_align("bfloat16") == 16
    with pytest.raises(ValueError):
        feat_dtype_bytes("int8")


def test_feasibility_is_dtype_aware():
    # dt=8 is f32-legal but bf16-illegal (lane-tile alignment)
    c = AggConfig(gs=8, gpt=8, dt=8, src_win=64)
    assert config_is_feasible(c)
    c16 = dataclasses.replace(c, feat_dtype="bfloat16")
    reason = config_infeasibility(c16)
    assert reason is not None and "alignment" in reason
    # a VMEM-busting f32 config can become legal at bf16 (halved window)
    from repro.hw import TPU_V5E
    big = AggConfig(gs=4, gpt=8, dt=512, src_win=2048)
    big16 = dataclasses.replace(big, feat_dtype="bfloat16")
    from repro.core.model import vmem_working_set
    assert vmem_working_set(big16) < vmem_working_set(big)


def test_tune_bf16_prices_bytes_and_is_feasible(small_graph):
    from repro.core.extractor import extract_graph_props
    from repro.core.tuner import tune
    r = tune(small_graph, 64, iters=3, seed=0, feat_dtype="bfloat16")
    assert r.best.feat_dtype == "bfloat16"
    assert config_is_feasible(r.best)            # under its OWN dtype
    km = KernelModel()
    pr = extract_graph_props(small_graph, detect_communities=False)
    t16 = km.terms(pr, 64, r.best)
    t32 = km.terms(pr, 64, dataclasses.replace(r.best,
                                               feat_dtype="float32"))
    # windows halve; meta/out bytes don't — strict inequality either way
    assert t16["bytes"] < t32["bytes"]


def test_tuner_infeasible_space_raises(small_graph):
    """Regression: `evolve` used to loop forever when config_is_feasible
    rejects the whole search space; now it raises naming the constraint."""
    from repro.core.tuner import tune
    from repro.hw import TPUSpec
    tiny = TPUSpec(name="tiny", peak_flops_bf16=1e12, peak_flops_f32=5e11,
                   hbm_bw=1e11, hbm_bytes=2**30, vmem_bytes=1024,
                   smem_bytes=2**10, ici_link_bw=1e9, ici_links=1,
                   grid_step_overhead_s=1e-6)
    with pytest.raises(RuntimeError, match="infeasible.*VMEM"):
        tune(small_graph, 64, iters=2, hw=tiny)


# ---------------- Plan round-trip + statics ----------------


def test_plan_for_rejects_infeasible_restamp():
    """Restamping a caller-supplied config with a dtype it is illegal
    under (f32-tuned dt=8 -> bf16 needs dt%16) must raise, not silently
    run a different dim tile than the plan claims."""
    from repro.core.advisor import plan_for
    g = random_power_law(100, 4.0, seed=4)
    cfg = AggConfig(gs=8, gpt=8, dt=8, src_win=64)
    with pytest.raises(ValueError, match="alignment"):
        plan_for(g, arch="gcn", in_dim=8, config=cfg,
                 feat_dtype="bfloat16")


def test_plan_roundtrips_feat_dtype(tmp_path):
    from repro.core.advisor import plan_for
    from repro.core.plan import Plan
    g = random_power_law(200, 5.0, seed=2)
    plan = plan_for(g, arch="gcn", in_dim=16, feat_dtype="bfloat16",
                    tune_iters=2, with_backward=True)
    assert plan.config.feat_dtype == "bfloat16"
    assert plan.jit_statics()[-1] == "bfloat16"
    path = str(tmp_path / "plan.npz")
    plan.save(path)
    loaded = Plan.load(path)
    assert loaded.config == plan.config
    # the loaded executor honors the policy
    feat = jnp.ones((g.num_nodes, 16), jnp.bfloat16)
    out = loaded.executor("xla")(feat)
    assert out.dtype == jnp.bfloat16


# ---------------- unvisited-block mask (schedule-static) ----------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_bipartite_unvisited_blocks_read_zero(backend, rng):
    """Blocks no tile names (bipartite/padded rows) must read as TRUE
    zeros — the kernel's accumulator starts zeroed and only visited
    blocks are written."""
    from repro.graphs.subgraph import pad_to_nodes
    g = random_power_law(60, 4.0, seed=5)
    gp = pad_to_nodes(g, 256)            # rows 60..255 have no edges
    ev = np.ones(gp.num_edges, np.float32)
    p = partition_graph(gp, gs=8, gpt=8, ont=8, src_win=64, edge_vals=ev)
    sched = DeviceSchedule(p)
    visited = np.zeros(p.padded_out_rows // p.ont, bool)
    visited[p.tile_node_block] = True
    assert not visited.all()             # the padded tail IS unvisited
    feat = jnp.asarray(rng.standard_normal((gp.num_nodes, 16)), jnp.float32)
    out = np.asarray(aggregate(feat, sched, dt=16, backend=backend))
    assert np.all(out[g.num_nodes:] == 0.0)
    assert np.all(np.isfinite(out))


def test_block_visited_flows_through_jit_args(rng):
    """Unvisited blocks read zero through the jit-ARGUMENT convention too
    (shared executables see the schedule as operands, not closure
    constants)."""
    from repro.core.advisor import plan_for
    from repro.core.plan import Plan
    from repro.graphs.subgraph import pad_to_nodes
    g = pad_to_nodes(random_power_law(50, 4.0, seed=6), 128)
    plan = plan_for(g, arch="gin", in_dim=8, tune_iters=2)
    args = plan.jit_args()
    statics = plan.jit_statics()
    feat = jnp.asarray(rng.standard_normal((g.num_nodes, 8)), jnp.float32)

    @jax.jit
    def fwd(feat, args):
        ex = Plan.executor_from_args(statics, args, backend="pallas_interpret")
        return ex(feat)

    out = np.asarray(fwd(feat, args))
    assert np.all(out[50:] == 0.0) and np.all(np.isfinite(out))


# ---------------- edge-value permute dedup ----------------


def test_permute_edge_vals_matches_permute_order(rng, community_graph):
    """`CSRGraph.permute_edge_vals` must track `permute`'s exact edge
    order: the (src, dst, val) triple multiset is preserved."""
    g = community_graph
    ev = rng.uniform(0.1, 2.0, g.num_edges).astype(np.float32)
    perm = np.random.default_rng(3).permutation(g.num_nodes)
    g2 = g.permute(perm)
    ev2 = g.permute_edge_vals(perm, ev)
    rows, cols = g.to_coo()
    rows2, cols2 = g2.to_coo()
    trip = sorted(zip(perm[rows].tolist(), perm[cols].tolist(),
                      ev.tolist()))
    trip2 = sorted(zip(rows2.tolist(), cols2.tolist(), ev2.tolist()))
    assert trip == trip2


def test_advise_reorder_uses_graph_permute_edge_vals(rng):
    """End-to-end parity: a reordered GCN plan aggregates identically to
    the unreordered one after mapping back to original node order (the
    advisor now delegates edge-value permutation to the graph method)."""
    from repro.core.advisor import advise
    from repro.models.gnn import gcn_edge_values
    g0 = random_power_law(180, 5.0, seed=9)
    g, vals = gcn_edge_values(g0)
    feat = rng.standard_normal((g.num_nodes, 12)).astype(np.float32)
    plan_off = advise(g, arch="gcn", in_dim=12, edge_vals=vals,
                      reorder="off", tune_iters=2)
    plan_on = advise(g, arch="gcn", in_dim=12, edge_vals=vals,
                     reorder="on", tune_iters=2)
    out_off = np.asarray(plan_off.executor("xla")(jnp.asarray(feat)))
    ex_on = plan_on.executor("xla")
    out_on = np.asarray(ex_on.aggregate_original_order(jnp.asarray(feat)))
    np.testing.assert_allclose(out_on, out_off, atol=1e-5, rtol=1e-5)


# ---------------- model-level bf16 ----------------


@pytest.mark.parametrize("arch", ["gcn", "gin"])
def test_model_bf16_logits_close_to_f32(arch):
    from repro.models.gnn import GNNConfig, build_gnn
    g = random_power_law(250, 5.0, seed=14)
    # local generator, not the shared session `rng`: that stream's position
    # here depends on which parametrized tests ran first, and the gin bound
    # below is tight enough that an unlucky draw crosses it
    feat = np.random.default_rng(14).standard_normal(
        (g.num_nodes, 16)).astype(np.float32)
    key = jax.random.PRNGKey(0)
    cfg32 = GNNConfig(arch=arch, in_dim=16, hidden_dim=16, num_classes=4,
                      num_layers=2, backend="xla")
    m32 = build_gnn(g, cfg32, key=key, reorder="off", tune_iters=2)
    cfg16 = dataclasses.replace(cfg32, feat_dtype="bfloat16",
                                backend="pallas_interpret")
    m16 = build_gnn(g, cfg16, key=key, reorder="off", tune_iters=2,
                    config=dataclasses.replace(m32.plan.config,
                                               feat_dtype="bfloat16"),
                    with_backward=True)
    lg32 = np.asarray(m32.logits(m32.params, jnp.asarray(feat)))
    lg16 = np.asarray(m16.logits(m16.params,
                                 jnp.asarray(feat, jnp.bfloat16)))
    assert lg16.dtype == np.float32          # logits cast back for the loss
    # GCN's reduce-dim-first path stays ~5e-2; GIN aggregates the full
    # input dim and compounds rounding through its per-layer MLP
    assert _rel_err(lg16, lg32) < (8e-2 if arch == "gcn" else 1.5e-1)
    # gradients through the bf16 pipeline are finite and close
    def loss(m, params, f):
        lg = m.logits(params, f)
        return (lg ** 2).mean()
    g32 = jax.grad(lambda p: loss(m32, p, jnp.asarray(feat)))(m32.params)
    g16 = jax.grad(lambda p: loss(
        m16, p, jnp.asarray(feat, jnp.bfloat16)))(m16.params)
    for a, b in zip(jax.tree_util.tree_leaves(g16),
                    jax.tree_util.tree_leaves(g32)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        assert np.all(np.isfinite(a))
        # normalize by the LEAF's grad magnitude, not per element: GIN's
        # O(100) logits make dL/dp rounding proportional to the largest
        # grads in a leaf, so per-element relative error blows up wherever
        # large contributions cancel (draw-dependent, up to ~3x)
        assert float(np.abs(a - b).max()) < 0.25 * (1.0 + np.abs(b).max())


def test_sampled_loader_ships_bf16_batches():
    from repro.models.gnn import GNNConfig, structural_labels
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.sampling import (LoaderConfig, SampledLoader,
                                SampledTrainStep)
    g = random_power_law(400, 6.0, seed=15)
    rng = np.random.default_rng(0)
    feat = rng.standard_normal((g.num_nodes, 8)).astype(np.float32)
    cfg = GNNConfig(arch="gcn", in_dim=8, hidden_dim=8, num_classes=4,
                    num_layers=2, backend="xla", feat_dtype="bfloat16")
    labels = structural_labels(g, 4)
    with SampledLoader(g, feat, labels, cfg,
                       LoaderConfig(fanouts=(4, 3), batch_nodes=64),
                       start_thread=False) as loader:
        batch = loader.batch_for(0)
        assert batch.feat.dtype == jnp.bfloat16
        assert "bfloat16" in batch.key
        from repro.models.gnn import init_gnn_params
        step = SampledTrainStep(cfg, AdamWConfig(lr=1e-2))
        params = init_gnn_params(cfg, jax.random.PRNGKey(0))
        state = (params, adamw_init(params))
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))


def test_serving_engine_bf16_policy(rng):
    from repro.models.gnn import GNNConfig
    from repro.serving import ServingConfig, ServingEngine
    g = random_power_law(300, 5.0, seed=16)
    feat = rng.standard_normal((g.num_nodes, 8)).astype(np.float32)
    key = jax.random.PRNGKey(1)
    mk = lambda dt: ServingEngine(
        g, feat, GNNConfig(arch="gcn", in_dim=8, hidden_dim=8,
                           num_classes=4, num_layers=2, backend="xla",
                           feat_dtype=dt),
        key=key, serving=ServingConfig(tune_iters=2))
    e32, e16 = mk("float32"), mk("bfloat16")
    seeds = [3, 77, 150]
    lg32 = e32.serve_batch(seeds)
    lg16 = e16.serve_batch(seeds)
    assert _rel_err(lg16, lg32) < 8e-2
    # the two policies never share cache identities
    assert not (set(e16.cache._plans) & set(e32.cache._plans))


# ---------------- 2-shard bf16 halo exchange vs f32 (cora) ----------------


def test_sharded_bf16_matches_f32_loss_curve_on_cora():
    """Acceptance: a 2-shard train run with bf16 halo exchange matches its
    own f32 loss curve to >= 3 decimals on cora."""
    code = """
        import dataclasses
        import numpy as np, jax, jax.numpy as jnp
        from repro.distributed.graph_shard import make_sharded_train_step
        from repro.graphs.datasets import make_dataset
        from repro.models.gnn import GNNConfig, build_gnn, structural_labels
        from repro.optim.adamw import AdamWConfig, adamw_init

        g, spec, feat = make_dataset("cora", max_nodes=800, seed=0)
        feat = feat[:, :16].astype(np.float32)
        labels = structural_labels(g, spec.num_classes)
        losses = {}
        plan_cfg = None
        for dt in ("float32", "bfloat16"):
            cfg = GNNConfig(arch="gcn", in_dim=16, hidden_dim=16,
                            num_classes=spec.num_classes, num_layers=2,
                            backend="xla", feat_dtype=dt)
            model = build_gnn(
                g, cfg, reorder="on", tune_iters=2, seed=0,
                with_backward=True,
                config=(None if plan_cfg is None else
                        dataclasses.replace(plan_cfg, feat_dtype=dt)))
            if plan_cfg is None:
                plan_cfg = model.plan.config
            batch = {"feat": jnp.asarray(model.plan.renumber_features(feat)),
                     "labels": jnp.asarray(
                         model.plan.renumber_features(labels))}
            step = make_sharded_train_step(
                cfg, model.plan.shards(2), AdamWConfig(lr=1e-2))
            state = (model.params, adamw_init(model.params))
            curve = []
            for _ in range(5):
                state, m = step(state, batch)
                curve.append(float(m["loss"]))
            losses[dt] = curve
        d = np.abs(np.array(losses["float32"])
                   - np.array(losses["bfloat16"]))
        print("curves", losses, "maxdiff", d.max())
        assert d.max() < 1e-3, (losses, d.max())
        print("OK")
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout
