"""A new architecture joins the benchmark as new files only: a module
``lib/arch/<arch>.py`` and a configuration that names it.  Here the module
is a renamed copy of ``gcn.py`` that no file of the harness names, found
in a directory outside the repository.  Besides: an unknown architecture
fails when its cell is loaded; and what a new architecture's cell may need
of the shared files does what it says: the configuration's ``labels``, the
community generator's sizes, an aggregation differentiable in its edge
values, and a value per head in the work counts."""
import json
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cell

from chipbench.lib import arch, counts, graphs, harness, reference, train

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = "plainconv"


def _manifest(tmp_path, model_arch: str, **graph) -> dict:
    """BENCHMARK.json with `gcn-blogcatalog`'s configuration replaced by a
    copy whose model is ``model_arch`` and whose graph takes ``graph``."""
    cfg = json.loads((ROOT / "chipbench" / "configs"
                      / "gcn-blogcatalog.json").read_text())
    cfg["model"]["arch"] = model_arch
    cfg["graph"].update(graph)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(cfg))
    return {**BENCH, "configs": [
        {**c, "file": str(path)} if c["name"] == "gcn-blogcatalog" else c
        for c in BENCH["configs"]]}


@pytest.fixture
def new_arch(tmp_path, monkeypatch):
    """``gcn.py`` copied as ``plainconv.py`` into a directory that the
    architecture package searches besides its own."""
    d = tmp_path / "arch"
    d.mkdir()
    shutil.copy(ROOT / "chipbench" / "lib" / "arch" / "gcn.py",
                d / f"{NEW}.py")
    monkeypatch.setattr(arch, "__path__", [*arch.__path__, str(d)])
    yield NEW
    sys.modules.pop(f"{arch.__name__}.{NEW}", None)


def test_no_harness_file_names_the_new_arch():
    files = [ROOT / "BENCHMARK.json"] + [
        p for p in (ROOT / "chipbench").rglob("*")
        if p.suffix in (".py", ".json", ".md") and "tests" not in p.parts]
    assert not [p for p in files if NEW in p.read_text()]


@pytest.mark.parametrize("graph", [
    {}, {"generator": "community", "community_size": 60,
         "num_communities": 25, "num_nodes": 1500, "num_edges": 9000}],
    ids=["power_law", "sized_communities"])
def test_new_arch_module_runs_a_train_cell(cpu_run, new_arch, tmp_path,
                                           graph):
    cell = tiny_cell("gcn-blogcatalog.train",
                     _manifest(tmp_path, new_arch, **graph))
    assert cell.config["model"]["arch"] == new_arch
    out = cpu_run(cell, trace=True)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == set(train.CHECKS)
    assert "train_mfu" in out["metrics"]     # read through its work counts
    gcn = {**cell.config["model"], "arch": "gcn"}
    assert counts.train_step_flops(cell.config["model"], 100, 400, 128, 39) \
        == counts.train_step_flops(gcn, 100, 400, 128, 39)


def test_unknown_arch_fails_in_load_cell(tmp_path, monkeypatch):
    def no_graph(spec):
        raise AssertionError("a graph was built")

    monkeypatch.setattr(graphs, "make_graph", no_graph)
    with pytest.raises(LookupError, match=r"chipbench/lib/arch/nosuch\.py"):
        harness.load_cell("gcn-blogcatalog.train",
                          _manifest(tmp_path, "nosuch"))
    with pytest.raises(ValueError, match="labels"):
        harness.load_cell("gcn-blogcatalog.train",
                          _manifest(tmp_path, "gcn", labels="several"))


@pytest.mark.parametrize("name,extra", [("gcn", {}),
                                        ("gin", {"gin_eps": 0.25})])
def test_program_config_passes_every_key_the_reference_reads(name, extra):
    model = {"arch": name, "num_layers": 3, "hidden_dim": 8, **extra}
    graph = {"feat_dim": 5, "num_classes": 4}
    want = {"arch": name, "in_dim": 5, "num_classes": 4, "hidden_dim": 8,
            "num_layers": 3, "feat_dtype": "float32", **extra}
    mod = arch.load(name)
    assert mod.program_config(model, graph, "float32") == want
    multi = mod.program_config(model, {**graph, "labels": "multi"},
                               "float32")
    assert multi == {**want, "multi_label": True}


def test_multi_label_loss_is_mean_binary_cross_entropy():
    model = {"arch": "gcn", "num_layers": 1, "hidden_dim": 4}
    indptr, indices = np.array([0, 1, 3, 4]), np.array([1, 0, 2, 1])
    params, feat, labels = train.make_inputs(7, model, 3, 2, 5,
                                             labels="multi")
    single = train.make_inputs(7, model, 3, 2, 5)
    # the task changes the labels alone
    np.testing.assert_array_equal(feat, single[1])
    np.testing.assert_array_equal(params["w0"], single[0]["w0"])
    assert labels.shape == (3, 5) and labels.dtype == jnp.float32
    assert set(np.unique(labels)) <= {0.0, 1.0}

    g = reference.graph_arrays(indptr, indices, "gcn")
    num = reference.Numerics("highest")
    z = np.asarray(reference.logits(params, feat, g, model, num), np.float64)
    y = np.asarray(labels, np.float64)
    p = 1.0 / (1.0 + np.exp(-z))
    want = -np.mean(y * np.log(p) + (1.0 - y) * np.log(1.0 - p))
    got = reference.loss(params, feat, labels, g, model, num)
    assert float(got) == pytest.approx(want, rel=1e-6)
    grads = jax.grad(reference.loss)(params, feat, labels, g, model, num)
    assert np.isfinite(np.asarray(grads["w0"])).all()


def test_community_size_gives_the_ppi_sizing():
    p_intra = 818716 / (24 * 2373 * 2372 / 2)
    indptr, indices = graphs.community(24, 2373, p_intra=p_intra, seed=0)
    assert len(indptr) - 1 == 56952 and len(indices) == 1626762
    spec = {"generator": "community", "num_nodes": 56944,
            "num_edges": 818716, "community_size": 2373,
            "num_communities": 24, "seed": 0}
    ours = graphs.make_graph(spec)
    np.testing.assert_array_equal(ours[0], indptr)
    np.testing.assert_array_equal(ours[1], indices)
    assert np.diff(indptr).max() == 57


def test_aggregation_is_differentiable_in_edge_values():
    """What attention needs of the shared reference: the cotangent of each
    edge's value, at both precisions."""
    indptr, indices = graphs.make_graph(
        {"generator": "power_law", "num_nodes": 200, "num_edges": 1200,
         "exponent": 2.1, "seed": 4})
    rows, cols = (jnp.asarray(a) for a in reference.coo(indptr, indices))
    kx, kv, kc = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(kx, (200, 8))
    vals = jax.random.uniform(kv, rows.shape)
    ct = jax.random.normal(kc, (200, 8))

    def plain(x, vals):
        return jnp.sum(ct * jax.ops.segment_sum(vals[:, None] * x[cols], rows,
                                                num_segments=200))
    want = jax.grad(plain, argnums=(0, 1))(x, vals)
    for prec, rtol in (("highest", 1e-5), ("high", 1e-4)):
        num = reference.Numerics(prec)
        got = jax.grad(lambda x, v: jnp.sum(ct * num.aggregate(
            x, reference.Graph(rows, cols, v))), argnums=(0, 1))(x, vals)
        for w, g in zip(want, got):
            np.testing.assert_allclose(g, w, rtol=rtol,
                                       atol=rtol * np.abs(w).max())


def test_a_value_per_head_is_read_once_per_edge():
    one = counts.AggPass(nodes=10, edges=40, dim=24, weighted=True)
    six = counts.AggPass(nodes=10, edges=40, dim=24, weighted=True, heads=6)
    assert counts.agg_flops(six) == counts.agg_flops(one)
    assert counts.agg_bytes(six) - counts.agg_bytes(one) == 40 * 5 * 4
