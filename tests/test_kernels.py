"""Pallas group_aggregate kernel vs pure-jnp oracle: shape/dtype sweeps +
hypothesis property tests (interpret=True executes the kernel body on CPU).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, strategies as st

from repro.core.partition import partition_graph
from repro.core.tuner import SEARCH_SPACE
from repro.graphs.csr import from_edges, grid_graph, random_power_law
from repro.kernels import ref
from repro.kernels.group_aggregate import group_aggregate_pallas
from repro.kernels.ops import DeviceSchedule, aggregate


def _oracle(g, feat, ev):
    rows, cols = g.to_coo()
    return ref.segment_aggregate_ref(jnp.asarray(feat), jnp.asarray(cols),
                                     jnp.asarray(rows), jnp.asarray(ev),
                                     g.num_nodes)


def _run(g, feat, ev, *, gs, gpt, ont, src_win, dt, backend):
    p = partition_graph(g, gs=gs, gpt=gpt, ont=ont, src_win=src_win,
                        edge_vals=ev)
    sched = DeviceSchedule(p)
    return aggregate(jnp.asarray(feat), sched, dt=dt, backend=backend)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
@pytest.mark.parametrize("dim", [8, 48, 130])
@pytest.mark.parametrize("gs", [4, 8, 16])
def test_kernel_shape_dtype_sweep(dtype, dim, gs, rng):
    g = random_power_law(200, 5.0, seed=3)
    feat = rng.standard_normal((g.num_nodes, dim)).astype(dtype)
    ev = rng.uniform(0.5, 1.5, g.num_edges).astype(np.float32)
    want = _oracle(g, feat.astype(np.float32), ev)
    got = _run(g, feat, ev, gs=gs, gpt=16, ont=8, src_win=64, dt=16,
               backend="pallas_interpret")
    tol = 1e-4 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("gs,gpt,ont,src_win,dt", [
    (4, 8, 8, 32, 8),
    (16, 8, 16, 128, 32),
    (32, 32, 8, 256, 64),
])
def test_kernel_config_sweep(gs, gpt, ont, src_win, dt, rng):
    g = random_power_law(150, 7.0, seed=4)
    feat = rng.standard_normal((g.num_nodes, 24)).astype(np.float32)
    ev = np.ones(g.num_edges, np.float32)
    want = _oracle(g, feat, ev)
    got = _run(g, feat, ev, gs=gs, gpt=gpt, ont=ont, src_win=src_win, dt=dt,
               backend="pallas_interpret")
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_kernel_grid_graph_exact(rng):
    """Deterministic graph: each node sums its neighbors exactly."""
    g = grid_graph(6, 7)
    feat = rng.standard_normal((g.num_nodes, 16)).astype(np.float32)
    ev = np.ones(g.num_edges, np.float32)
    want = _oracle(g, feat, ev)
    got = _run(g, feat, ev, gs=4, gpt=8, ont=8, src_win=32, dt=16,
               backend="pallas_interpret")
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_xla_backend_matches(rng, small_graph):
    g = small_graph
    feat = rng.standard_normal((g.num_nodes, 32)).astype(np.float32)
    ev = rng.uniform(0.1, 2.0, g.num_edges).astype(np.float32)
    want = _oracle(g, feat, ev)
    got = _run(g, feat, ev, gs=8, gpt=16, ont=8, src_win=128, dt=32,
               backend="xla")
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(20, 120),
    avg_deg=st.floats(1.0, 8.0),
    dim=st.integers(1, 40),
    gs=st.sampled_from([4, 8, 16]),
    seed=st.integers(0, 10_000),
)
def test_kernel_property_random(n, avg_deg, dim, gs, seed):
    """Property: for ANY graph/config, kernel == segment-sum oracle."""
    g = random_power_law(n, avg_deg, seed=seed)
    r = np.random.default_rng(seed)
    feat = r.standard_normal((g.num_nodes, dim)).astype(np.float32)
    ev = r.uniform(-1.0, 1.0, g.num_edges).astype(np.float32)
    want = _oracle(g, feat, ev)
    got = _run(g, feat, ev, gs=gs, gpt=8, ont=8, src_win=64, dt=8,
               backend="pallas_interpret")
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-3)


def test_edge_and_node_centric_baselines_agree(rng, small_graph):
    g = small_graph
    feat = rng.standard_normal((g.num_nodes, 12)).astype(np.float32)
    ev = rng.uniform(0.5, 1.5, g.num_edges).astype(np.float32)
    rows, cols = g.to_coo()
    want = ref.segment_aggregate_ref(jnp.asarray(feat), jnp.asarray(cols),
                                     jnp.asarray(rows), jnp.asarray(ev),
                                     g.num_nodes)
    got_e = ref.edge_centric_aggregate_ref(jnp.asarray(feat), jnp.asarray(cols),
                                           jnp.asarray(rows), jnp.asarray(ev),
                                           g.num_nodes)
    np.testing.assert_allclose(got_e, want, atol=1e-4)
    # node-centric padded form
    degs = g.degrees
    md = int(degs.max())
    nbrs = np.zeros((g.num_nodes, md), np.int32)
    mask = np.zeros((g.num_nodes, md), np.float32)
    evp = np.zeros((g.num_nodes, md), np.float32)
    pos = 0
    for v in range(g.num_nodes):
        d = int(degs[v])
        nbrs[v, :d] = g.indices[g.indptr[v]:g.indptr[v + 1]]
        mask[v, :d] = 1.0
        evp[v, :d] = ev[pos:pos + d]
        pos += d
    got_n = ref.node_centric_aggregate_ref(jnp.asarray(feat), jnp.asarray(nbrs),
                                           jnp.asarray(mask), jnp.asarray(evp),
                                           g.num_nodes)
    np.testing.assert_allclose(got_n, want, atol=1e-4)


@pytest.mark.parametrize("kernel", ["aggregate", "edge_grad"])
def test_multi_launch_matches_reference(kernel, rng, monkeypatch):
    """Schedules longer than one launch's SMEM budget run as several
    launches accumulating in place — node blocks straddling a launch
    boundary included."""
    from repro.core.partition import transpose_graph
    from repro.kernels import group_aggregate

    monkeypatch.setattr(group_aggregate, "MAX_TILES_PER_CALL", 8)
    jax.clear_caches()
    g = random_power_law(200, 5.0, seed=9)
    ev = rng.uniform(0.5, 1.5, g.num_edges).astype(np.float32)
    p = partition_graph(g, gs=4, gpt=8, ont=8, src_win=64, edge_vals=ev)
    assert p.num_tiles > 3 * 8                    # several launches
    sched = DeviceSchedule(p)
    feat = jnp.asarray(rng.standard_normal((g.num_nodes, 16)), jnp.float32)
    if kernel == "aggregate":
        got = aggregate(feat, sched, dt=16, backend="pallas_interpret")
        want = _oracle(g, np.asarray(feat), ev)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    else:
        gT, evT, perm = transpose_graph(g, ev)
        sched_bwd = DeviceSchedule(
            partition_graph(gT, gs=4, gpt=8, ont=8, src_win=64,
                            edge_vals=evT), edge_perm=perm)
        cot = jnp.asarray(rng.standard_normal((g.num_nodes, 16)), jnp.float32)

        def loss(backend):
            return lambda e: (aggregate(feat, sched, dt=16, backend=backend,
                                        edge_values=e, sched_bwd=sched_bwd)
                              * cot).sum()

        evj = jnp.asarray(ev)
        np.testing.assert_allclose(jax.grad(loss("pallas_interpret"))(evj),
                                   jax.grad(loss("xla"))(evj),
                                   atol=1e-4, rtol=1e-4)
    jax.clear_caches()


# every group size the tuner searches, at its narrowest gpt and GCN's, with
# one head and four; then a schedule over several launches, and sources
# repeated inside a group (a multigraph)
_FOLD_CASES = [
    pytest.param(gs, gpt, heads, False, False, id=f"gs{gs}-gpt{gpt}-h{heads}")
    for gs in SEARCH_SPACE["gs"] for gpt in (8, 32) for heads in (1, 4)
] + [pytest.param(4, 8, 1, True, False, id="two_launches"),
     pytest.param(8, 8, 4, False, True, id="repeated_source")]


@pytest.mark.parametrize("gs,gpt,heads,multi_launch,multigraph", _FOLD_CASES)
def test_folded_gather_matches_reference(gs, gpt, heads, multi_launch,
                                         multigraph, rng, monkeypatch):
    """The kernel folds each group's gs slots into its gather-matrix row
    one slot position at a time: it matches the segment-sum oracle at every
    plan, head-indexed values included."""
    from repro.kernels import group_aggregate

    if multi_launch:
        monkeypatch.setattr(group_aggregate, "MAX_TILES_PER_CALL", 8)
        jax.clear_caches()
    g = random_power_law(160, 6.0, seed=gs + gpt)
    if multigraph:                              # every edge twice
        dst, src = g.to_coo()
        g = from_edges(g.num_nodes, np.tile(src, 2), np.tile(dst, 2),
                       dedup=False)
    src_win, ont, dt = 64, 8, 8
    p = partition_graph(g, gs=gs, gpt=gpt, ont=ont, src_win=src_win)
    T = p.num_tiles
    if multi_launch:
        assert T > 8
    ev = rng.uniform(0.5, 1.5, (g.num_edges, heads)).astype(np.float32)
    slot_ev = np.zeros((heads, T * gpt * gs), np.float32)
    slot_ev[:, p.edge_slot * gs + p.edge_pos] = ev.T
    slot_ev = slot_ev.reshape(heads, T, gpt, gs)
    if multigraph:                              # a group gathers a row twice
        real = slot_ev[0] != 0
        assert any(len(set(nb[m])) < m.sum()
                   for nb, m in zip(p.nbrs.reshape(-1, gs),
                                    real.reshape(-1, gs)))
    d = 2 * dt if heads == 1 else heads * dt
    feat = rng.standard_normal((-(-g.num_nodes // src_win) * src_win, d))
    feat = feat.astype(np.float32)
    got = group_aggregate_pallas(
        jnp.asarray(feat), jnp.asarray(p.nbrs),
        jnp.asarray(slot_ev if heads > 1 else slot_ev[0]),
        jnp.asarray(p.local_node), jnp.asarray(p.tile_node_block),
        jnp.asarray(p.tile_window), gs=gs, gpt=gpt, ont=ont,
        src_win=src_win, dt=dt, out_rows=p.padded_out_rows, interpret=True)
    want = _oracle(g, feat[:g.num_nodes], ev if heads > 1 else ev[:, 0])
    np.testing.assert_allclose(got[:g.num_nodes], want, atol=1e-4, rtol=1e-4)
    if multi_launch:
        jax.clear_caches()
