"""The plain reference against the program's model at a tiny size on the
CPU: graphs, forward, loss, gradients and AdamW; and its control, the
three-pass products, differs from it by bfloat16-split rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.lib import graphs, reference

MODELS = {"gcn": {"arch": "gcn", "num_layers": 2, "hidden_dim": 16},
          "gin": {"arch": "gin", "num_layers": 5, "hidden_dim": 64,
                  "gin_eps": 0.0}}
DIMS = {"gcn": (32, 7), "gin": (24, 2)}


@pytest.mark.parametrize("dataset,gtype", [("soc-blogcatalog", "III"),
                                           ("dd", "II")])
def test_graph_copy_matches_the_program_generator(dataset, gtype):
    from repro.graphs.datasets import PAPER_DATASETS, make_dataset

    spec = PAPER_DATASETS[dataset]
    scale = 0.01
    g, _, _ = make_dataset(dataset, scale=scale, seed=3)
    generator = {"III": "power_law", "II": "community"}[gtype]
    ours = graphs.make_graph({"generator": generator, "seed": 3,
                              "exponent": 2.1,
                              "num_nodes": int(spec.num_nodes * scale),
                              "num_edges": spec.num_edges
                              * int(spec.num_nodes * scale)
                              / spec.num_nodes})
    np.testing.assert_array_equal(ours[0], g.indptr)
    np.testing.assert_array_equal(ours[1], g.indices)


def _program(arch, indptr, indices, in_dim, classes):
    from repro.graphs.csr import CSRGraph
    from repro.models.gnn import GNNConfig, build_gnn

    m = MODELS[arch]
    cfg = GNNConfig(arch=arch, in_dim=in_dim, hidden_dim=m["hidden_dim"],
                    num_classes=classes, num_layers=m["num_layers"],
                    backend="xla")
    return build_gnn(CSRGraph(indptr, indices), cfg, tune_iters=2)


def _inputs(arch, n):
    in_dim, classes = DIMS[arch]
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    params = reference.init_params(k1, MODELS[arch], in_dim, classes)
    feat = jax.random.normal(k2, (n, in_dim), jnp.float32)
    labels = jax.random.randint(k3, (n,), 0, classes)
    return params, feat, labels


def _plan_order(prog, x):
    perm = prog.plan.perm
    if perm is None:
        return x
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    return x[jnp.asarray(inv)]


@pytest.mark.parametrize("arch", ["gcn", "gin"])
def test_reference_matches_the_program(arch):
    indptr, indices = graphs.make_graph(
        {"generator": "power_law", "num_nodes": 300, "num_edges": 2400,
         "exponent": 2.1, "seed": 1})
    n = len(indptr) - 1
    in_dim, classes = DIMS[arch]
    params, feat, labels = _inputs(arch, n)
    prog = _program(arch, indptr, indices, in_dim, classes)
    g = reference.graph_arrays(indptr, indices, arch)
    num = reference.Numerics("highest")
    want = reference.logits(params, feat, g, MODELS[arch], num)
    got = prog.logits(params, _plan_order(prog, feat))
    got = np.asarray(got)[np.asarray(prog.plan.perm)] \
        if prog.plan.perm is not None else np.asarray(got)
    scale = np.abs(np.asarray(want)).max()
    np.testing.assert_allclose(got, want, atol=1e-5 * scale)

    ref_l, ref_g = jax.value_and_grad(reference.loss)(
        params, feat, labels, g, MODELS[arch], num)
    (prog_l, _), prog_g = jax.value_and_grad(prog.loss, has_aux=True)(
        params, _plan_order(prog, feat), _plan_order(prog, labels))
    assert float(prog_l) == pytest.approx(float(ref_l), rel=1e-5)
    for k in ref_g:
        s = np.abs(np.asarray(ref_g[k])).max()
        np.testing.assert_allclose(prog_g[k], ref_g[k], atol=1e-4 * s)


def test_adamw_matches_the_program_optimizer():
    from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

    opt = {"lr": 0.01, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
           "weight_decay": 0.1, "grad_clip": 1.0}
    params = {"w": jnp.arange(6.0).reshape(2, 3) / 7, "b": jnp.ones(3)}
    grads = {"w": jnp.full((2, 3), 0.9), "b": jnp.array([3.0, -1.0, 0.5])}
    p1, s1, _ = adamw_update(AdamWConfig(**opt), grads, adamw_init(params),
                             params)
    r1, rs1 = reference._adamw(opt, grads, reference.adamw_state(params),
                               params)
    for k in params:
        np.testing.assert_allclose(p1[k], r1[k], rtol=1e-6)
        np.testing.assert_allclose(s1.m[k], rs1["m"][k], rtol=1e-6)


def test_control_rounds_like_three_bf16_passes():
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 48))
    b = jax.random.normal(jax.random.PRNGKey(1), (48, 32))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    hi = reference.Numerics("highest").mm(a, b)
    lo = reference.Numerics("high").mm(a, b)
    err_hi = np.abs(np.asarray(hi) - exact).max()
    err_lo = np.abs(np.asarray(lo) - exact).max()
    assert err_lo > 10 * err_hi
    assert err_lo < 1e-3 * np.abs(exact).max()
    # gradients go through three passes too
    g = jax.grad(lambda a: reference.mm_high(a, b).sum())(a)
    np.testing.assert_allclose(g, jnp.ones((64, 32)) @ b.T, rtol=1e-3)
