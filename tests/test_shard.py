"""Shardable Plan IR + multi-device halo-exchange execution.

Device-parity tests run in subprocesses with forced host devices (the main
pytest process must keep seeing 1 device); the host-side splitter / Plan IR
tests run in-process.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 4, timeout=600):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


# ---------------- host-side: splitter + Plan IR ----------------


def _gcn_plan(n=400, seed=3, with_backward=True, reorder=False):
    from repro.core.advisor import advise, plan_for
    from repro.graphs.csr import random_power_law
    from repro.models.gnn import gcn_edge_values
    g, vals = gcn_edge_values(random_power_law(n, 6.0, seed=seed))
    if reorder:
        return advise(g, arch="gcn", in_dim=16, edge_vals=vals, reorder="on",
                      tune_iters=2, with_backward=with_backward)
    return plan_for(g, arch="gcn", in_dim=16, edge_vals=vals,
                    tune_iters=2, with_backward=with_backward)


def test_shard_splitter_invariants():
    """Contiguous ranges, full edge coverage, exact halo sets, uniform
    tile counts and statics across shards."""
    plan = _gcn_plan()
    g = plan.graph
    for P in (1, 2, 4, 3):
        shards = plan.shards(P)
        spec = shards.spec
        assert spec.num_shards == P
        assert spec.padded_nodes >= g.num_nodes
        # edge ranges tile the CSR edge array exactly
        assert shards.edge_ranges[0][0] == 0
        assert shards.edge_ranges[-1][1] == g.num_edges
        for (a, b), (c, d) in zip(shards.edge_ranges[:-1],
                                  shards.edge_ranges[1:]):
            assert b == c
        # per-shard sub-graphs: local rows hold exactly the global rows
        stat0 = shards.plans[0].jit_statics()
        for p, sub in enumerate(shards.plans):
            assert sub.partition.num_tiles == shards.plans[0].partition.num_tiles
            assert sub.jit_statics() == stat0
            lo = p * spec.n_local
            hi = min(lo + spec.n_local, g.num_nodes)
            np.testing.assert_array_equal(
                sub.graph.indices, g.indices[g.indptr[lo]:g.indptr[hi]])
            # halo = unique remote sources of the shard's rows
            srcs = np.unique(sub.graph.indices)
            expect = srcs[(srcs < lo) | (srcs >= lo + spec.n_local)]
            np.testing.assert_array_equal(shards.halo[p], expect)
        st = shards.stats()
        assert sum(st["edges_per_shard"]) == g.num_edges
        assert len(st["halo_frac"]) == P


def test_shard_static_edge_values_roundtrip():
    """The splitter recovers per-edge values from the parent schedule: the
    per-shard schedules must hold exactly the parent's values."""
    plan = _gcn_plan()
    ev = plan.partition.edge_values_csr()
    shards = plan.shards(3)
    got = [sub.partition.edge_values_csr() for sub in shards.plans]
    np.testing.assert_allclose(np.concatenate(got), ev, rtol=0, atol=0)


def test_plan_save_load_roundtrip(tmp_path):
    import jax.numpy as jnp
    from repro.core.plan import Plan
    plan = _gcn_plan(reorder=True)
    path = str(tmp_path / "plan.npz")
    plan.save(path)
    plan2 = Plan.load(path)
    assert plan2.config == plan.config
    assert plan2.partition_bwd is not None
    np.testing.assert_array_equal(plan2.perm, plan.perm)
    feat = np.random.default_rng(0).standard_normal(
        (plan.graph.num_nodes, 16)).astype(np.float32)
    a = np.asarray(plan.executor("xla")(jnp.asarray(feat)))
    b = np.asarray(plan2.executor("xla")(jnp.asarray(feat)))
    np.testing.assert_array_equal(a, b)


def test_plan_jit_args_convention():
    """jit_args/jit_statics + executor_from_args reproduce the plan's own
    executor (the one convention serving/sampling/sharding share): the
    kernel from the default (edge-less) args, and the XLA reference —
    which sums over the schedule's real edges when it has them — from
    the args that keep the edge members."""
    import jax.numpy as jnp
    from repro.core.plan import Plan
    plan = _gcn_plan()
    feat = jnp.asarray(np.random.default_rng(1).standard_normal(
        (plan.graph.num_nodes, 16)).astype(np.float32))
    for backend, args in (("pallas_interpret", plan.jit_args()),
                          ("xla", plan.jit_args(with_edges=True))):
        ex = Plan.executor_from_args(plan.jit_statics(), args,
                                     backend=backend)
        np.testing.assert_array_equal(np.asarray(ex(feat)),
                                      np.asarray(plan.executor(backend)(feat)))
    # default drops the unbucketed edge members (they sit after the
    # tile-shaped fields); with_edges keeps them
    from repro.kernels.ops import N_TILE_FIELDS
    assert plan.jit_args()[0][N_TILE_FIELDS - 1] is not None  # tile_window
    assert plan.jit_args()[0][N_TILE_FIELDS] is None          # edge_slot
    assert plan.jit_args(with_edges=True)[0][N_TILE_FIELDS] is not None


def test_plan_cache_lru_bounds():
    """max_plans LRU-evicts ready plans; max_configs bounds the memo; both
    eviction counters surface in stats()."""
    from repro.graphs.csr import random_power_law
    from repro.serving.plan_cache import PlanCache
    cache = PlanCache(backend="xla", tune_iters=2, max_plans=2,
                      max_configs=2)
    graphs = [random_power_law(64 * (i + 1), 4.0, seed=i) for i in range(4)]
    for g in graphs:
        cache.get_or_build(g, arch="gcn", in_dim=8, hidden_dim=8,
                           num_layers=2)
    st = cache.stats()
    assert st["plans"] == 2
    assert st["evictions"] == 2
    assert st["configs"] <= 2
    assert st["config_evictions"] == st["misses"] - st["configs"]
    # unbounded back-compat: max_plans=None keeps everything
    cache2 = PlanCache(backend="xla", tune_iters=2, max_plans=None)
    for g in graphs:
        cache2.get_or_build(g, arch="gcn", in_dim=8, hidden_dim=8,
                            num_layers=2)
    assert cache2.stats()["plans"] == 4
    assert cache2.stats()["evictions"] == 0


def test_plan_cache_max_plans_none_is_unbounded():
    """Explicit max_plans=None means unbounded (the ServingConfig
    contract); omitting it falls back to the legacy max_entries knob."""
    from repro.serving.plan_cache import PlanCache
    assert PlanCache().max_plans == 64
    assert PlanCache(max_entries=2).max_plans == 2
    assert PlanCache(max_plans=None).max_plans is None
    assert PlanCache(max_plans=5).max_plans == 5


def test_sharded_sampled_config_mismatch_replans():
    """Shard batches that disagree on AggConfig (pow2 node-bucket
    straddle) are repartitioned under the widest config, not rejected,
    and the bucket key ignores per-batch key ordering."""
    import dataclasses

    import jax

    from repro.graphs.csr import random_power_law
    from repro.models.gnn import (GNNConfig, init_gnn_params,
                                  structural_labels)
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.sampling import (LoaderConfig, SampledLoader,
                                ShardedSampledTrainStep)
    from repro.serving.plan_cache import CacheEntry

    g = random_power_law(2000, 6.0, seed=2)
    cfg = GNNConfig(arch="gcn", in_dim=8, hidden_dim=8, num_classes=4,
                    num_layers=2, backend="xla")
    feat = np.random.default_rng(0).standard_normal(
        (g.num_nodes, 8)).astype(np.float32)
    with SampledLoader(g, feat, structural_labels(g, 4), cfg,
                       LoaderConfig(fanouts=(4, 3), batch_nodes=64),
                       start_thread=False) as loader:
        step = ShardedSampledTrainStep(cfg, AdamWConfig(lr=1e-2), 1)
        b0, b1 = loader(0), loader(1)
        ent = b1.entries[0]
        other = dataclasses.replace(ent.plan.config,
                                    src_win=ent.plan.config.src_win * 2)
        forced = step._replan(ent, other)
        assert forced.config == other
        assert forced.partition.num_edges == ent.plan.partition.num_edges
        b1.entries[0] = CacheEntry(plan=forced,
                                   executor=forced.executor("xla"))
        params = init_gnn_params(cfg, jax.random.PRNGKey(0))
        state = (params, adamw_init(params))
        state, m0 = step(state, [b0])          # normal bucket
        state, m1 = step(state, [b1])          # mismatched layer: replans
        assert np.isfinite(float(m1["loss"]))
        # a second normal batch reuses the first bucket (key is statics +
        # shapes, not the per-batch key tuple)
        state, _ = step(state, [loader(2)])
        assert step.num_buckets == 2, step.num_buckets


def test_tuner_dedup_unique_evaluations():
    """evolve never re-scores a config; evaluations counts unique ones."""
    from repro.core.tuner import evolve
    calls = []

    def score(c):
        assert c not in calls, f"re-scored {c}"
        calls.append(c)
        return float(c.gs * c.gpt)

    res = evolve(score, pop=8, iters=6, seed=0)
    assert res.evaluations == len(calls)
    assert res.best_score == min(float(c.gs * c.gpt) for c in calls)


# ---------------- multi-device parity (forced host devices) ----------------


def test_sharded_aggregation_matches_single():
    """Shard counts {1,2,4} reproduce the single-device PlanExecutor to
    1e-5, static and DYNAMIC edge values, plus grad parity through the
    sharded custom-VJP backward (transposed shard plans)."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.advisor import plan_for
        from repro.core.aggregate import PlanExecutor
        from repro.distributed.graph_shard import ShardedExecutor
        from repro.graphs.csr import random_power_law
        from repro.models.gnn import gcn_edge_values

        g, vals = gcn_edge_values(random_power_law(500, 6.0, seed=3))
        plan = plan_for(g, arch="gcn", in_dim=16, edge_vals=vals,
                        tune_iters=2, with_backward=True)
        feat = jnp.asarray(np.random.default_rng(0).standard_normal(
            (g.num_nodes, 16)).astype(np.float32))
        ref_ex = PlanExecutor(plan, backend="xla")
        ref = np.asarray(ref_ex(feat))
        gref = np.asarray(jax.grad(lambda f: (ref_ex(f) ** 2).sum())(feat))

        planD = plan_for(g, arch="gat", in_dim=16, config=plan.config,
                         with_backward=True)
        ev = jnp.asarray(np.random.default_rng(1).standard_normal(
            g.num_edges).astype(np.float32))
        refD_ex = PlanExecutor(planD, backend="xla")
        refD = np.asarray(refD_ex.aggregate_edges(feat, ev))
        grefD = np.asarray(jax.grad(
            lambda e: (refD_ex.aggregate_edges(feat, e) ** 2).sum())(ev))

        for P in (1, 2, 4):
            ex = ShardedExecutor(plan.shards(P), backend="xla")
            assert np.abs(np.asarray(ex(feat)) - ref).max() < 1e-5, P
            gsh = np.asarray(jax.grad(lambda f: (ex(f) ** 2).sum())(feat))
            assert np.abs(gsh - gref).max() < 1e-4, P
            exD = ShardedExecutor(planD.shards(P), backend="xla")
            assert np.abs(np.asarray(exD.aggregate_edges(feat, ev))
                          - refD).max() < 1e-5, P
            gshD = np.asarray(jax.grad(
                lambda e: (exD.aggregate_edges(feat, e) ** 2).sum())(ev))
            assert np.abs(gshD - grefD).max() < 1e-3 * (
                1 + np.abs(grefD).max()), P
        print("OK")
    """)
    assert "OK" in out


def test_sharded_dynamic_edges_through_the_kernel():
    """Dynamic edge values through the interpreted kernel on 2 and 4
    shards of unequal edge counts match one device on ``xla``, values
    and both gradients: every shard's padded slots read the 0 past the
    padded edge values, in the forward and the transposed schedule."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.advisor import plan_for
        from repro.core.aggregate import PlanExecutor
        from repro.distributed.graph_shard import ShardedExecutor
        from repro.graphs.csr import random_power_law

        g = random_power_law(300, 6.0, seed=5).with_self_loops()
        plan = plan_for(g, arch="gat", in_dim=16, hidden_dim=16,
                        tune_iters=2, with_backward=True)
        rng = np.random.default_rng(0)
        feat = jnp.asarray(rng.standard_normal((g.num_nodes, 16)),
                           jnp.float32)
        ev = jnp.asarray(rng.random(g.num_edges) + 0.5, jnp.float32)
        ref_ex = PlanExecutor(plan, backend="xla")
        loss = lambda ex, f, e: (ex.aggregate_edges(f, e) ** 2).sum()
        ref = np.asarray(ref_ex.aggregate_edges(feat, ev))
        grefs = jax.grad(lambda f, e: loss(ref_ex, f, e), (0, 1))(feat, ev)
        for P in (2, 4):
            shards = plan.shards(P)
            assert len({hi - lo for lo, hi in shards.edge_ranges}) > 1, P
            ex = ShardedExecutor(shards, backend="pallas_interpret")
            got = np.asarray(ex.aggregate_edges(feat, ev))
            assert np.abs(got - ref).max() < 1e-4 * (
                1 + np.abs(ref).max()), P
            gshs = jax.grad(lambda f, e: loss(ex, f, e), (0, 1))(feat, ev)
            for gsh, gref in zip(gshs, grefs):
                gref = np.asarray(gref)
                assert np.abs(np.asarray(gsh) - gref).max() < 1e-4 * (
                    1 + np.abs(gref).max()), P
        print("OK")
    """)
    assert "OK" in out


def test_sharded_model_matches_single():
    """gcn + gin on a reorder-renumbered graph: sharded logits match the
    single-device model to 1e-5 and a sharded train step reproduces the
    1-device loss/params (shard counts {1,2,4}).  Logit and loss errors
    are magnitude-normalised, max|a - b| / (1 + max|ref|): GIN logits reach
    O(100), where f32 accumulation-order differences between schedules
    are ~1e-5 absolute."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.distributed.graph_shard import (make_sharded_logits_fn,
                                                   make_sharded_train_step)
        from repro.graphs.csr import random_power_law
        from repro.models.gnn import (GNNConfig, build_gnn,
                                      make_gnn_train_step, planted_labels)
        from repro.optim.adamw import AdamWConfig, adamw_init

        g = random_power_law(600, 6.0, seed=1)
        for arch in ("gcn", "gin"):
            cfg = GNNConfig(arch=arch, in_dim=12, hidden_dim=16,
                            num_classes=5, num_layers=2, backend="xla")
            model = build_gnn(g, cfg, reorder="on", tune_iters=2, seed=0,
                              with_backward=True)
            rng = np.random.default_rng(0)
            feat0 = rng.standard_normal((g.num_nodes, 12)).astype(np.float32)
            feat = jnp.asarray(model.plan.renumber_features(feat0))
            labels = jnp.asarray(model.plan.renumber_features(
                planted_labels(g, cfg, feat0, seed=3)))
            ref_lg = np.asarray(model.logits(model.params, feat))
            opt = AdamWConfig(lr=1e-2)
            state0 = (model.params, adamw_init(model.params))
            batch = {"feat": feat, "labels": labels}
            s0, m0 = make_gnn_train_step(model, opt)(state0, batch)
            for P in (1, 2, 4):
                shards = model.plan.shards(P)
                lg = make_sharded_logits_fn(cfg, shards)(model.params, feat)
                err = (np.abs(np.asarray(lg) - ref_lg).max()
                       / (1.0 + np.abs(ref_lg).max()))
                assert err < 1e-5, (arch, P, err)
                s1, m1 = make_sharded_train_step(cfg, shards, opt)(
                    state0, batch)
                err = (abs(float(m1["loss"]) - float(m0["loss"]))
                       / (1.0 + abs(float(m0["loss"]))))
                assert err < 1e-4, (arch, P, err)
                d = max(float(jnp.abs(a - b).max()) for a, b in
                        zip(jax.tree_util.tree_leaves(s0[0]),
                            jax.tree_util.tree_leaves(s1[0])))
                assert d < 1e-4, (arch, P, d)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_sampled_step():
    """Data-parallel sampled training: P loader batches per step through
    one shard_map'd executable; loss decreases, buckets are reused."""
    out = _run("""
        import numpy as np, jax
        from repro.graphs.csr import random_power_law
        from repro.models.gnn import (GNNConfig, init_gnn_params,
                                      structural_labels)
        from repro.optim.adamw import AdamWConfig, adamw_init
        from repro.sampling import (LoaderConfig, SampledLoader,
                                    ShardedSampledTrainStep)

        g = random_power_law(3000, 8.0, seed=2)
        cfg = GNNConfig(arch="gcn", in_dim=16, hidden_dim=16, num_classes=4,
                        num_layers=2, backend="xla")
        feat = np.random.default_rng(0).standard_normal(
            (g.num_nodes, 16)).astype(np.float32)
        labels = structural_labels(g, 4)
        with SampledLoader(g, feat, labels, cfg,
                           LoaderConfig(fanouts=(5, 3),
                                        batch_nodes=128)) as loader:
            P = 4
            step = ShardedSampledTrainStep(cfg, AdamWConfig(lr=1e-2), P)
            params = init_gnn_params(cfg, jax.random.PRNGKey(0))
            state = (params, adamw_init(params))
            losses = []
            for s in range(6):
                state, m = step(state, [loader(s * P + p) for p in range(P)])
                losses.append(float(m["loss"]))
            assert step.num_buckets <= 2, step.num_buckets
            assert step.traces <= 2, step.traces
            assert losses[-1] < losses[0], losses
        print("OK")
    """)
    assert "OK" in out


def test_sharded_pallas_interpret_backend():
    """The per-device body runs the Pallas kernel (interpret mode on CPU)
    with its custom-VJP backward over transposed shard schedules."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.advisor import plan_for
        from repro.core.aggregate import PlanExecutor
        from repro.distributed.graph_shard import ShardedExecutor
        from repro.graphs.csr import random_power_law
        from repro.models.gnn import gcn_edge_values

        g, vals = gcn_edge_values(random_power_law(300, 5.0, seed=7))
        plan = plan_for(g, arch="gcn", in_dim=16, edge_vals=vals,
                        tune_iters=2, with_backward=True)
        feat = jnp.asarray(np.random.default_rng(0).standard_normal(
            (g.num_nodes, 16)).astype(np.float32))
        ref_ex = PlanExecutor(plan, backend="xla")
        ref = np.asarray(ref_ex(feat))
        gref = np.asarray(jax.grad(lambda f: (ref_ex(f) ** 2).sum())(feat))
        ex = ShardedExecutor(plan.shards(2), backend="pallas_interpret")
        assert np.abs(np.asarray(ex(feat)) - ref).max() < 1e-4
        gsh = np.asarray(jax.grad(lambda f: (ex(f) ** 2).sum())(feat))
        assert np.abs(gsh - gref).max() < 1e-4 * (1 + np.abs(gref).max())
        print("OK")
    """, devices=2)
    assert "OK" in out
