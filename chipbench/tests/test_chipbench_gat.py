"""GAT's module of the benchmark (`lib/arch/gat.py`): its reference against
a dense oracle, its work counts by hand, the keys it hands the program;
the readers the `gat-ppi.train` cell adds, on synthetic traces; the
manifest's entries for that cell; and a whole run of it at a CPU size."""
import copy
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.lib import arch, counts, graphs, harness, reference, train
from chipbench.lib import trace as T
from chipbench.lib.arch import gat
from chipbench.lib.peaks import peak_for

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "gat-ppi.train"
NEW_METRICS = ("edge_grad_ms", "edge_grad_roofline", "non_agg_ms")
SMALL = {"arch": "gat", "num_layers": 3, "heads": [2, 2, 3],
         "head_dims": [8, 8, 5], "skip_layers": [1], "gat_slope": 0.2}
I = T.Interval


def _leaky(x, slope=0.2):
    return np.where(x > 0, x, slope * x)


def test_reference_matches_a_dense_oracle():
    indptr, indices = graphs.community(3, 20, p_intra=0.3, seed=5)
    n = len(indptr) - 1
    params, feat, _ = train.make_inputs(9, SMALL, n, 12, 5, "multi")
    got = gat.logits(params, feat, gat.graph_arrays(indptr, indices), SMALL,
                     reference.Numerics("highest"))
    A = np.eye(n, dtype=bool)
    rows, cols = reference.coo(indptr, indices)
    A[rows, cols] = True
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    x = np.asarray(feat, np.float64)
    for i, (H, F) in enumerate(zip(SMALL["heads"], SMALL["head_dims"])):
        z = x @ p[f"w{i}"]
        outs = []
        for h in range(H):
            zh = z[:, h * F:(h + 1) * F]
            e = _leaky((zh @ p[f"a{i}d"][:, h])[:, None]
                       + (zh @ p[f"a{i}s"][:, h])[None, :])
            e = np.where(A, e, -np.inf)
            w = np.exp(e - e.max(axis=1, keepdims=True))
            outs.append(w @ zh / w.sum(axis=1, keepdims=True))
        y = (np.mean(outs, axis=0) if i == 2
             else np.concatenate(outs, axis=1))
        if i < 2:
            y = np.where(y > 0, y, np.expm1(np.minimum(y, 0)))
        x = y + x if i == 1 else y
    np.testing.assert_allclose(got, x, rtol=1e-5, atol=1e-5)


def test_param_shapes_put_fan_in_first():
    assert gat.param_shapes(SMALL, 12, 5) == {
        "w0": (12, 16), "a0s": (8, 2), "a0d": (8, 2),
        "w1": (16, 16), "a1s": (8, 2), "a1d": (8, 2),
        "w2": (16, 15), "a2s": (5, 3), "a2d": (5, 3)}


def test_program_config_passes_every_key_the_reference_reads():
    from repro.models.gnn import GNNConfig

    graph = {"feat_dim": 12, "num_classes": 5, "labels": "multi"}
    got = gat.program_config(SMALL, graph, "float32")
    assert got == {"arch": "gat", "in_dim": 12, "num_classes": 5,
                   "feat_dtype": "float32", "multi_label": True,
                   "num_layers": 3, "heads": (2, 2, 3),
                   "head_dims": (8, 8, 5), "skip_layers": (1,),
                   "gat_slope": 0.2}
    assert set(SMALL) - {"arch"} <= set(got)
    cfg = GNNConfig(**got)
    assert cfg.agg_hidden_dim == 16 and cfg.edge_heads == 3


def test_ppi_counts_by_hand():
    """Per layer: numerator and normaliser forward, the numerator's
    transposed pass, and both edge cotangents, over A + I with a value per
    edge and head; the transposed passes last layer first."""
    cfg = json.loads((ROOT / "chipbench" / "configs"
                      / "gat-ppi.json").read_text())
    model = cfg["model"]
    n, e = 56952, 1626762
    passes = counts.train_agg_passes(model, n, e, 50, 121)
    assert len(passes) == 15
    assert {p.edges for p in passes} == {e + n}
    assert all(p.weighted and p.nodes == n for p in passes)
    assert [(p.dim, p.heads) for p in passes[:6]] == [
        (1024, 4), (4, 4), (1024, 4), (4, 4), (726, 6), (6, 6)]
    assert [p.dim for p in passes[6:9]] == [726, 1024, 1024]
    edge = gat.train_edge_grad_passes(model, n, e, 50, 121)
    assert passes[9:] == edge == passes[:6]
    assert counts.agg_flops(passes[0]) == 2 * (e + n) * 1024
    assert counts.agg_bytes(passes[1]) == (
        (n + 1) * 4 + (e + n) * (4 + 4 * 4) + 2 * n * 4 * 4)


def test_dense_flops_by_hand():
    """Projections (the first one's input needs no gradient) and two score
    products a head, each a (width, 1) matmul whose input needs one."""
    n = 10
    mm = lambda k, m, passes: 2 * n * k * m * passes
    want = (mm(12, 16, 2) + 2 * 2 * mm(8, 1, 3)
            + mm(16, 16, 3) + 2 * 2 * mm(8, 1, 3)
            + mm(16, 15, 3) + 2 * 3 * mm(5, 1, 3))
    assert gat.dense_train_flops(SMALL, n, 12, 5) == want


def _readings(model=SMALL, window=True):
    ops = [I(0, 4e6, "group_aggregate_fwd.3"),
           I(4e6, 6e6, "group_aggregate_bwd.4"),
           I(6e6, 9e6, "group_edge_grad.1"),
           I(9e6, 10e6, "group_edge_grad.7"),
           I(10e6, 11e6, "fusion.2"),
           I(11e6, 14e6, "scatter.5")]
    cell = types.SimpleNamespace(config={
        "model": model, "graph": {"feat_dim": 12, "num_classes": 5}})
    return types.SimpleNamespace(
        window={"ops": {0: ops}} if window else None, trace_steps=2,
        cell=cell, nodes=60, edges=200, device={"kind": "TPU v5 lite"})


def test_new_readers_on_a_synthetic_trace():
    r = _readings()
    assert harness._reader("edge_grad_ms")(r) == pytest.approx(2.0)
    assert harness._reader("non_agg_ms")(r) == pytest.approx(2.0)
    peak = peak_for("TPU v5 lite")
    least = sum(counts.agg_least_s(p, peak.flops, peak.hbm_bw)
                for p in gat.train_edge_grad_passes(SMALL, 60, 200, 12, 5))
    assert harness._reader("edge_grad_roofline")(r) == pytest.approx(
        100 * least / 2e-3)
    # the kernels' own time and the rest's add up to the step's ops
    both = harness._reader("agg_kernel_ms")(r) + harness._reader(
        "non_agg_ms")(r)
    assert both == pytest.approx(7.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_read_nothing_without_a_trace(name):
    assert harness._reader(name)(_readings(window=False)) is None


def test_edge_grad_readers_without_edge_values_read_nothing():
    r = _readings(model={"arch": "gcn", "num_layers": 2, "hidden_dim": 16})
    r.window["ops"][0] = [iv for iv in r.window["ops"][0]
                          if not iv.name.startswith("group_edge_grad")]
    assert harness._reader("edge_grad_ms")(r) is None
    assert harness._reader("edge_grad_roofline")(r) is None
    r = _readings(model={"arch": "gcn", "num_layers": 2, "hidden_dim": 16})
    assert harness._reader("edge_grad_roofline")(r) is None


def test_manifest_lists_the_cell_with_its_files():
    w = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        "gat-ppi", "train_full_adam", 1)
    cfg = next(c for c in BENCH["configs"] if c["name"] == "gat-ppi")
    assert cfg["reduced"] == [] and cfg["file"] == \
        "chipbench/configs/gat-ppi.json"
    cell = harness.load_cell(CELL)
    assert cell.config["model"]["arch"] == "gat"
    assert cell.mix["loop"] == "train"
    assert cell.mix["optimizer"]["grad_clip"] is None
    assert set(cell.params["limits"]) == set(train.CHECKS)
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "train_step_ms"}
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW_METRICS) <= set(names)
    # every per-layer metric of the benchmark reads in the new cell
    assert len(names) == len(BENCH["per_layer"])
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert (ROOT / "chipbench" / "metrics"
                    / f"{m['name']}.py").exists()


def _tiny_cell():
    cell = harness.load_cell(CELL)
    cell.config = copy.deepcopy(cell.config)
    g = cell.config["graph"]
    g.update(num_nodes=256, num_edges=2560, community_size=64,
             num_communities=4, feat_dim=12, num_classes=5)
    cell.config["model"].update(heads=[2, 2, 3], head_dims=[8, 8, 5])
    cell.config["plan"]["tune_iters"] = 2
    return cell


def test_tiny_gat_cell_runs_correct_and_reads_its_layers(cpu_run):
    out = cpu_run(_tiny_cell(), seed=2 ** 33 + 5, trace=True)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert {"plan_s", "compile_s", "padded_slots_per_edge",
            "train_mfu"} <= set(m)
    # no chip planes in a CPU trace: the device readers find nothing
    assert not set(NEW_METRICS) & set(m)


def test_tiny_gat_cell_inputs_are_multi_label():
    cell = _tiny_cell()
    params, feat, labels = train.cell_inputs(cell, 7, 256)
    assert labels.shape == (256, 5) and labels.dtype == jnp.float32
    assert set(params) == set(gat.param_shapes(cell.config["model"], 12, 5))
    assert arch.task(cell.config["graph"]) == "multi"
    assert jax.tree.map(jnp.shape, params) == {
        k: tuple(s) for k, s in gat.param_shapes(
            cell.config["model"], 12, 5).items()}
