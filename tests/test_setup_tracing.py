"""Set-up traced inside the program: planner spans and counters in the
process tracer, spans on the profiler's host plane, and JAX's compile
stages and cache lookups counted per function."""
from __future__ import annotations

import glob

import jax
import jax.numpy as jnp
import pytest

from repro.graphs.csr import random_power_law
from repro.models.gnn import GNNConfig, build_gnn, make_gnn_train_step
from repro.obs import (MetricsRegistry, SpanTracer, install_compile_listener,
                       process_tracer)
from repro.obs.compile import fun_label

PLAN_CHILDREN = ("analyze", "tune", "partition", "executor")


def _sums(reg, name, key) -> dict:
    return {m["labels"][key]: (m["count"], m["sum"])
            for m in reg.snapshot() if m["name"] == name}


def _delta(before: dict, after: dict, label) -> tuple:
    c0, s0 = before.get(label, (0, 0.0))
    c1, s1 = after.get(label, (0, 0.0))
    return c1 - c0, s1 - s0


def _value(reg, name, **labels) -> float:
    m = reg.get(name, labels)
    return 0.0 if m is None else m.value


@pytest.fixture(scope="module")
def small_graph():
    return random_power_law(400, 6.0, seed=3)


def _cfg(backend="pallas_interpret"):
    return GNNConfig(arch="gcn", in_dim=16, hidden_dim=16, num_classes=4,
                     num_layers=2, backend=backend)


def test_build_gnn_spans_plan_and_its_children(small_graph):
    reg = process_tracer().registry
    before = _sums(reg, "span_seconds", "span")
    build_gnn(small_graph, _cfg(), tune_iters=2)
    after = _sums(reg, "span_seconds", "span")
    n_plan, plan_s = _delta(before, after, "plan")
    assert n_plan == 1 and plan_s > 0
    children = {c: _delta(before, after, f"plan/{c}") for c in PLAN_CHILDREN}
    assert all(n == 1 for n, _ in children.values()), children
    covered = sum(s for _, s in children.values())
    assert 0.95 * plan_s <= covered <= plan_s


def test_build_gnn_counts_the_forward_plan(small_graph):
    reg = process_tracer().registry
    names = ("plan_tiles_total", "plan_padded_slots_total",
             "plan_edges_total")
    before = [_value(reg, n) for n in names]
    model = build_gnn(small_graph, _cfg("xla"), tune_iters=2)
    part = model.plan.partition
    got = [_value(reg, n) - b for n, b in zip(names, before)]
    assert got == [part.num_tiles, part.num_tiles * part.gpt * part.gs,
                   part.num_edges]


def test_plan_for_alone_nests_under_the_open_span(small_graph):
    from repro.core.advisor import plan_for
    from repro.core.model import AggConfig

    tr = SpanTracer(MetricsRegistry())
    with tr.span("serve_batch"):
        plan_for(small_graph, in_dim=16, tracer=tr, with_backward=True,
                 config=AggConfig(gs=8, gpt=8, ont=8, src_win=64, dt=16))
    paths = {r["span"] for r in tr.records()}
    # a given config skips the tuner; props are extracted on the way
    assert paths == {"serve_batch", "serve_batch/analyze",
                     "serve_batch/partition"}
    assert tr.registry.get("plan_tiles_total").value > 0


def test_serving_plan_cache_spans_nest_under_serve_batch(small_graph):
    import numpy as np

    from repro.serving import ServingConfig, ServingEngine

    cfg = GNNConfig(arch="gcn", in_dim=6, hidden_dim=6, num_classes=3,
                    num_layers=2, backend="xla")
    feat = np.ones((small_graph.num_nodes, 6), np.float32)
    eng = ServingEngine(small_graph, feat, cfg,
                        serving=ServingConfig(max_batch=4, tune_iters=2))
    process_before = _sums(process_tracer().registry, "span_seconds", "span")
    eng.serve_batch([1, 2, 3])               # a miss: tuned and partitioned
    eng.serve_batch([1, 2, 3])               # an exact hit: no planning
    got = _sums(eng.registry, "span_seconds", "span")
    for child in ("analyze", "tune", "partition"):
        assert got[f"serve_batch/plan/{child}"][0] == 1, got
    assert got["serve_batch/plan"][0] == 2
    assert eng.registry.get("plan_tiles_total").value > 0
    # the engine's planning stays out of the process tracer
    assert _sums(process_tracer().registry, "span_seconds",
                 "span") == process_before


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    tr = SpanTracer(MetricsRegistry())
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("outer"):
            with tr.span("probe"):
                jnp.arange(8.0).sum().block_until_ready()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events}
    assert {"repro/outer", "repro/outer/probe"} <= names
    # the tracer's own record is unchanged by the annotation
    assert [r["span"] for r in tr.records()] == ["outer/probe", "outer"]


@pytest.mark.parametrize("name, label", [
    ("jit(gnn_train_step)", "gnn_train_step"),
    ("gnn_train_step", "gnn_train_step"),
    ("pmap(step)", "step"),
    ("jit(vmap(f))", "vmap(f)"),
    (None, "unknown"),
])
def test_fun_label_strips_the_transform(name, label):
    assert fun_label(name) == label


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compilation cache in a fresh directory, every
    executable cached; JAX's settings restored afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_compile_cache_miss_then_hit_by_function(persistent_cache):
    reg = install_compile_listener().registry
    fun = "obs_cache_probe"

    def fresh():
        # a new function object each time: JAX's in-memory caches miss,
        # so the second compile reads the persistent cache
        def obs_cache_probe(x):
            return jnp.sin(x) * 3.0 + 0.25
        return jax.jit(obs_cache_probe)

    x = jnp.arange(7.0)
    counts = []
    for _ in range(2):
        fresh().lower(x).compile()
        counts.append((_value(reg, "compile_cache_misses_total", fun=fun),
                       _value(reg, "compile_cache_hits_total", fun=fun),
                       _value(reg, "jit_compiles_total", fun=fun)))
    assert counts == [(1.0, 0.0, 1.0), (1.0, 1.0, 2.0)]
    for stage in ("trace", "lower", "backend"):
        h = reg.get(f"jit_{stage}_seconds", {"fun": fun})
        assert h is not None and h.count == 2 and h.sum > 0
    recs = [r for r in process_tracer().records()
            if r.get("attrs", {}).get("fun") == fun]
    assert {r["span"] for r in recs} == {"jit/trace", "jit/lower",
                                         "jit/backend"}


def test_train_step_compiles_under_its_stable_name(small_graph):
    from repro.optim.adamw import AdamWConfig, adamw_init

    model = build_gnn(small_graph, _cfg("xla"), tune_iters=2)
    step = make_gnn_train_step(model, AdamWConfig())
    assert step.__name__ == "gnn_train_step"
    reg = install_compile_listener().registry
    n0 = _value(reg, "jit_compiles_total", fun="gnn_train_step")
    n = small_graph.num_nodes
    state = (model.params, adamw_init(model.params))
    batch = {"feat": jnp.ones((n, 16)), "labels": jnp.zeros(n, jnp.int32)}
    step.lower(state, batch).compile()
    assert _value(reg, "jit_compiles_total", fun="gnn_train_step") == n0 + 1
    for stage in ("trace", "lower", "backend"):
        assert reg.get(f"jit_{stage}_seconds", {"fun": "gnn_train_step"})


def test_train_driver_exports_setup_metrics(tmp_path):
    """`--metrics-out` of the GNN training driver carries the planner's
    spans and the train step's compile stages and cache lookups, and
    `--trace-out` the compile records."""
    import json
    import os
    import subprocess
    import sys

    metrics, trace = tmp_path / "metrics.json", tmp_path / "trace.json"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "PYTHONPATH": os.pathsep.join(
               [os.path.join(os.path.dirname(__file__), "..", "src")]
               + [p for p in [os.environ.get("PYTHONPATH")] if p])}
    subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "gcn",
         "--dataset", "cora", "--max-nodes", "300", "--steps", "2",
         "--hidden-dim", "8", "--backend", "xla",
         "--ckpt-dir", str(tmp_path / "ckpt"),
         "--metrics-out", str(metrics), "--trace-out", str(trace)],
        env=env, check=True, capture_output=True, timeout=240)
    rows = json.loads(metrics.read_text())["metrics"]
    have = {(m["name"], tuple(sorted(m["labels"].items()))) for m in rows}
    step = (("fun", "gnn_train_step"),)
    for name in ("jit_compiles_total", "compile_cache_misses_total",
                 "jit_trace_seconds", "jit_lower_seconds",
                 "jit_backend_seconds"):
        assert (name, step) in have, name
    for child in ("", "/analyze", "/tune", "/partition", "/executor"):
        assert ("span_seconds", (("span", "plan" + child),)) in have
    events = json.loads(trace.read_text())["traceEvents"]
    assert {"jit/trace", "jit/lower", "jit/backend"} <= {
        e["name"] for e in events}
