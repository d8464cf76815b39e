"""FLOP and byte counts against shapes worked by hand."""
import pytest

from chipbench.lib import counts

GCN = {"arch": "gcn", "num_layers": 2, "hidden_dim": 16}
GIN = {"arch": "gin", "num_layers": 5, "hidden_dim": 64}


def test_gcn_passes_aggregate_projected_widths_with_self_loops():
    passes = counts.train_agg_passes(GCN, nodes=10, edges=30, in_dim=128,
                                     num_classes=7)
    assert [(p.dim, p.edges, p.weighted) for p in passes] == [
        (16, 40, True), (7, 40, True), (7, 40, True), (16, 40, True)]


def test_gin_has_no_backward_pass_into_the_input_features():
    passes = counts.train_agg_passes(GIN, nodes=10, edges=30, in_dim=89,
                                     num_classes=2)
    assert [p.dim for p in passes] == [89, 64, 64, 64, 64, 64, 64, 64, 64]
    assert all(p.edges == 30 and not p.weighted for p in passes)


def test_agg_flops_and_bytes_by_hand():
    p = counts.AggPass(nodes=10, edges=40, dim=16, weighted=True)
    assert counts.agg_flops(p) == 2 * 40 * 16
    # indptr 11 x 4, edges 40 x (4 + 4), features in and out 2 x 10 x 16 x 4
    assert counts.agg_bytes(p) == 44 + 320 + 1280
    q = counts.AggPass(nodes=10, edges=40, dim=16, weighted=False)
    assert counts.agg_bytes(q) == 44 + 160 + 1280


def test_least_time_is_the_larger_bound():
    p = counts.AggPass(nodes=10, edges=40, dim=16, weighted=True)
    assert counts.agg_least_s(p, 1e3, 1e9) == pytest.approx(1280 / 1e3)
    assert counts.agg_least_s(p, 1e12, 1.0) == pytest.approx(1644.0)


def test_dense_flops_by_hand():
    # GCN 2 x 16, N = 10, D = 128, C = 7: X W0 (no input gradient) and
    # H W1 (with one)
    mm0, mm1 = 2 * 10 * 128 * 16, 2 * 10 * 16 * 7
    assert counts.dense_train_flops(GCN, 10, 128, 7) == 2 * mm0 + 3 * mm1
    # GIN: the first layer's first matmul alone has no input gradient
    gin1 = {"arch": "gin", "num_layers": 1, "hidden_dim": 4}
    a, b = 2 * 3 * 5 * 4, 2 * 3 * 4 * 2
    assert counts.dense_train_flops(gin1, 3, 5, 2) == 2 * a + 3 * b


def test_step_flops_add_aggregation_and_dense():
    n, e = 100, 400
    total = counts.train_step_flops(GIN, n, e, 89, 2)
    agg = sum(counts.agg_flops(p)
              for p in counts.train_agg_passes(GIN, n, e, 89, 2))
    assert total == agg + counts.dense_train_flops(GIN, n, 89, 2)
