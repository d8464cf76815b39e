"""§6.1 renumbering + §7 advisor loop tests."""
import numpy as np
import pytest

from repro.core.advisor import advise, plan_for
from repro.core.aggregate import PlanExecutor
from repro.core.extractor import extract_graph_props
from repro.core.model import AggConfig, KernelModel, config_is_feasible, paper_eq2_latency
from repro.core.partition import partition_graph, partition_stats
from repro.core.reorder import renumber
from repro.core.tuner import (SEARCH_SPACE, _crossover, _mutate,
                              community_profile, evolve, tune)
from repro.graphs.csr import random_community_graph, random_power_law


def test_renumber_is_permutation(community_graph):
    perm = renumber(community_graph, seed=0)
    n = community_graph.num_nodes
    assert sorted(perm.tolist()) == list(range(n))


def test_renumber_improves_locality():
    """Scrambled community graph: renumbering must reduce tile count
    (fewer feature-window DMAs — the Fig. 12b analogue)."""
    g = random_community_graph(16, 24, p_intra=0.5,
                               p_inter_edges_per_node=0.2, seed=5)
    # scramble the natural (already-local) ordering first
    rng = np.random.default_rng(0)
    scramble = rng.permutation(g.num_nodes)
    g_bad = g.permute(scramble)
    tiles_bad = partition_stats(partition_graph(g_bad, gs=8, gpt=8, ont=8,
                                                src_win=64))["tiles"]
    perm = renumber(g_bad, seed=0)
    g_fix = g_bad.permute(perm)
    tiles_fix = partition_stats(partition_graph(g_fix, gs=8, gpt=8, ont=8,
                                                src_win=64))["tiles"]
    assert tiles_fix < tiles_bad, (tiles_fix, tiles_bad)


def test_permute_preserves_edges(community_graph):
    g = community_graph
    perm = renumber(g, seed=1)
    g2 = g.permute(perm)
    e1 = set()
    for v in range(g.num_nodes):
        for u in g.neighbors(v):
            e1.add((perm[v], perm[u]))
    e2 = set()
    for v in range(g2.num_nodes):
        for u in g2.neighbors(v):
            e2.add((v, int(u)))
    assert e1 == e2


def test_extractor_props(small_graph):
    props = extract_graph_props(small_graph)
    assert props.num_nodes == small_graph.num_nodes
    assert props.num_edges == small_graph.num_edges
    assert props.max_degree >= props.avg_degree
    assert 0.15 <= props.alpha <= 0.3


def test_paper_eq2_shape_of_surface(small_graph):
    """Eq. 2 sanity: finite/positive everywhere; the (1 + |gs - pivot|)
    penalty grows when gs moves away from the pivot at fixed 1/gs factor."""
    props = extract_graph_props(small_graph, detect_communities=False)
    vals = [paper_eq2_latency(props, 64, AggConfig(gs=gs, gpt=g, dt=d))
            for gs in (4, 16, 64) for g in (8, 32) for d in (64, 256)]
    assert all(np.isfinite(v) and v > 0 for v in vals)
    # penalty factor isolated: same gs denominator, larger |gs - pivot|
    pivot = props.alpha * props.num_nodes / props.num_edges
    lat = lambda gs: paper_eq2_latency(props, 64, AggConfig(gs=gs)) * gs
    assert lat(64) >= lat(max(int(round(pivot)), 1))


def test_feasibility_constraints():
    assert config_is_feasible(AggConfig(gs=16, gpt=16, dt=128, src_win=512))
    # VMEM blow-up must be rejected (Eq. 4 analogue)
    assert not config_is_feasible(AggConfig(gs=16, gpt=128, dt=512,
                                            src_win=8192))


@pytest.mark.parametrize("feat_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("gpt", [8, 32])
def test_kernel_model_prices_group_onehot_matmul(small_graph, gpt,
                                                 feat_dtype):
    """Each grid step's MXU work is the group one-hot sum (gpt, gpt*gs) @
    (gpt*gs, src_win) — it grows as gpt² — the gather (gpt, src_win) @
    (src_win, dt) and the node scatter (ont, gpt) @ (gpt, dt).  f32
    matmuls run at HIGHEST, six bf16 passes; a bf16 window makes the
    gather one pass."""
    props = extract_graph_props(small_graph, detect_communities=False)
    cfg = AggConfig(gs=8, gpt=gpt, dt=128, src_win=256, feat_dtype=feat_dtype)
    t = KernelModel().terms(props, 128, cfg, tiles=10)
    onehot, gather = 2 * gpt * gpt * 8 * 256, 2 * gpt * 256 * 128
    scatter = 2 * 8 * gpt * 128
    gather_passes = 1 if feat_dtype == "bfloat16" else 6
    assert t["steps"] == 10
    assert t["mxu_flops"] == 10 * (onehot + gather + scatter)
    assert t["mxu_passes"] == 10 * (6 * (onehot + scatter)
                                     + gather_passes * gather)


def test_tuner_monotone_and_feasible(small_graph):
    res = tune(small_graph, 64, mode="model", iters=8, seed=0)
    scores = [s for _, s in res.history]
    assert scores[-1] <= scores[0]
    assert config_is_feasible(res.best)
    assert res.evaluations > 0


@pytest.fixture(scope="module")
def power_law_3k():
    return random_power_law(3000, 8.0, seed=3)


@pytest.mark.parametrize("dim", [16, 64])
def test_tuner_picks_node_block_height(power_law_3k, dim):
    """``ont`` is searched: the pick comes from the search space, and its
    plan runs fewer tiles than the same knobs at the old fixed 8 rows."""
    c = tune(power_law_3k, dim, iters=6, seed=0).best
    assert c.ont in SEARCH_SPACE["ont"]
    tiles = lambda ont: partition_graph(power_law_3k, gs=c.gs, gpt=c.gpt,
                                        ont=ont, src_win=c.src_win).num_tiles
    assert tiles(c.ont) < tiles(8)


def test_crossover_and_mutate_carry_node_block_height():
    """Crossover takes ``ont`` from one parent and keeps a shared one;
    mutation moves it along the search space and never off it."""
    rng = np.random.default_rng(0)
    a, b = AggConfig(ont=8), AggConfig(ont=128)
    assert {_crossover(a, b, rng).ont for _ in range(64)} == {8, 128}
    assert all(_crossover(a, a, rng).ont == 8 for _ in range(16))
    assert all(_mutate(b, rng, p=0.0).ont == 128 for _ in range(16))
    space = SEARCH_SPACE["ont"]
    moved = {_mutate(AggConfig(ont=32), rng, p=1.0).ont for _ in range(64)}
    assert moved == {16, 32, 64}
    assert {_mutate(b, rng, p=1.0).ont for _ in range(64)} <= set(space)


def test_pinned_config_keeps_its_node_block_height(power_law_3k):
    """A caller's config skips the tuner: ``ont=8`` plans exactly as the
    partitioner does at 8 rows."""
    cfg = AggConfig(gs=4, gpt=32, dt=128, src_win=2048, ont=8)
    plan = plan_for(power_law_3k, in_dim=16, config=cfg)
    ref = partition_graph(power_law_3k, gs=4, gpt=32, ont=8, src_win=2048)
    assert plan.config == cfg and plan.tuner is None
    assert plan.partition.ont == 8
    for f in ("nbrs", "edge_val", "local_node", "tile_node_block",
              "tile_window", "edge_slot", "edge_pos"):
        np.testing.assert_array_equal(getattr(plan.partition, f),
                                      getattr(ref, f), err_msg=f)


def test_tuner_profile_mode(community_graph):
    res = tune(community_graph, 32, mode="profile", iters=4, pop=8, seed=0)
    assert config_is_feasible(res.best)


def test_community_profile_scorer():
    score = community_profile([16, 32], dim=32, seed=0)
    a = score(AggConfig(gs=8, gpt=16, dt=64, src_win=128))
    b = score(AggConfig(gs=64, gpt=128, dt=512, src_win=2048))
    assert a > 0 and b > 0 and np.isfinite([a, b]).all()


def test_advisor_end_to_end(community_graph, rng):
    import jax.numpy as jnp
    from repro.kernels import ref
    plan = advise(community_graph, arch="gcn", in_dim=32, hidden_dim=16,
                  tune_iters=3)
    ex = PlanExecutor(plan, backend="xla")
    feat = rng.standard_normal((community_graph.num_nodes, 32)).astype(np.float32)
    out = ex.aggregate_original_order(jnp.asarray(feat))
    rows, cols = community_graph.to_coo()
    want = ref.segment_aggregate_ref(
        jnp.asarray(feat), jnp.asarray(cols), jnp.asarray(rows),
        jnp.ones(community_graph.num_edges), community_graph.num_nodes)
    np.testing.assert_allclose(out, want, atol=1e-3)


def test_advisor_skips_reorder_for_local_graphs():
    """Type-II graphs arrive pre-localized — reorder='auto' must skip."""
    g = random_community_graph(20, 16, p_intra=0.6,
                               p_inter_edges_per_node=0.0, seed=7)
    plan = advise(g, arch="gcn", in_dim=8, hidden_dim=8, reorder="auto",
                  tune_iters=2)
    assert plan.perm is None
