"""Full-graph training cells (mix ``train_full``).

Set-up builds one object, the compiled step of `make_gnn_train_step` with
its state, and drives it from the seed through the first three steps on
the whole graph; the window then goes on with that same object.  The
window runs whole steps, each synced on its loss as `Trainer` does, until
``--seconds`` is spent; ``train_step_ms`` is its elapsed time over its
steps.  After the window the program is freed and the plain reference
follows the same three steps.
"""
from __future__ import annotations

import gc
import time
import types

import numpy as np

from . import arch, compare, graphs, harness, reference
from .trace_window import TraceWindow

__all__ = ["run", "CHECKED_STEPS", "CHECKS"]

CHECKED_STEPS = 3
# the numbers `compare.train_numbers` gives `compare.judge`
CHECKS = ("loss_gap", "grad_norm_gap", "grad_diff", "update_norm_gap")


def make_inputs(seed: int, model: dict, nodes: int, in_dim: int,
                classes: int, labels: str = "single"):
    """Weights, features and labels from the seed, on the device, in one
    jitted call, in the original node order.  ``labels`` is the task:
    ``"single"``, a class a node, uniform; ``"multi"``, ``(nodes,
    classes)`` 0/1 targets, each 1 with probability one half."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        kp, kf, kl = jax.random.split(key, 3)
        params = reference.init_params(kp, model, in_dim, classes)
        feat = jax.random.normal(kf, (nodes, in_dim), jnp.float32)
        if labels == "multi":
            y = jax.random.bernoulli(kl, 0.5, (nodes, classes))
            return params, feat, y.astype(jnp.float32)
        y = jax.random.randint(kl, (nodes,), 0, classes, jnp.int32)
        return params, feat, y

    return draw(harness.jax_key(seed))


def cell_inputs(cell, seed: int, nodes: int):
    """`make_inputs` for the cell's model, graph widths and task."""
    g = cell.config["graph"]
    return make_inputs(seed, cell.config["model"], nodes, g["feat_dim"],
                       g["num_classes"], arch.task(g))


def _opt_config(mix: dict):
    from repro.optim.adamw import AdamWConfig

    o = mix["optimizer"]
    return AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                       weight_decay=o["weight_decay"],
                       grad_clip=o["grad_clip"])


def build(cell, hooks=None):
    """Graph, plan and the compiled step: the program side of the set-up,
    the same for every seed.  ``hooks`` (tests only) may wrap the step."""
    import jax
    from repro.graphs.csr import CSRGraph
    from repro.models.gnn import GNNConfig, build_gnn, make_gnn_train_step
    from repro.optim.adamw import adamw_init

    cfg, host = cell.config, {}
    model, gspec, plan_spec = cfg["model"], cfg["graph"], cfg["plan"]
    t = time.perf_counter()
    indptr, indices = graphs.make_graph(gspec)
    host["graph_s"] = time.perf_counter() - t
    n, e = len(indptr) - 1, len(indices)
    harness.log(f"graph {gspec['dataset']}: {n} nodes, {e} edges "
                f"({host['graph_s']:.2f}s)")
    gcfg = GNNConfig(**arch.load(model["arch"]).program_config(
        model, gspec, cfg["dtype"]))
    t = time.perf_counter()
    prog = build_gnn(CSRGraph(indptr, indices), gcfg,
                     reorder=plan_spec["reorder"],
                     tune_iters=plan_spec["tune_iters"],
                     seed=plan_spec["seed"])
    host["plan_s"] = time.perf_counter() - t
    part = prog.plan.partition
    plan = {"tiles": int(part.num_tiles), "gpt": int(part.gpt),
            "gs": int(part.gs), "edges": int(part.num_edges),
            "config": str(prog.plan.config)}
    harness.log(f"plan {host['plan_s']:.2f}s: {plan}")
    step_fn = make_gnn_train_step(prog, _opt_config(cell.mix))
    if hooks and "step" in hooks:
        step_fn = hooks["step"](step_fn)
    shapes = jax.eval_shape(lambda: cell_inputs(cell, 0, n))
    params = shapes[0]
    batch = {"feat": shapes[1], "labels": shapes[2]}
    state = (params, jax.eval_shape(adamw_init, params))
    t = time.perf_counter()
    step = step_fn.lower(state, batch).compile()
    host["compile_s"] = time.perf_counter() - t
    harness.log(f"compile {host['compile_s']:.2f}s")
    return types.SimpleNamespace(
        step=step, host=host, plan=plan, nodes=n, edges=e, indptr=indptr,
        indices=indices, perm=prog.plan.perm, prog=prog)


def feed(b, cell, seed: int):
    """The seed's weights, features and labels (original order), the
    optimiser state and the batch in the plan's node order."""
    import jax.numpy as jnp
    from repro.optim.adamw import adamw_init

    params, feat, labels = cell_inputs(cell, seed, b.nodes)
    if b.perm is None:
        batch = {"feat": feat, "labels": labels}
    else:
        inv = np.empty_like(b.perm)
        inv[b.perm] = np.arange(len(b.perm))
        inv = jnp.asarray(inv)
        batch = {"feat": feat[inv], "labels": labels[inv]}
    return (params, feat, labels), (params, adamw_init(params)), batch


def first_steps(step, state0, batch, b1: float):
    """The checked steps, through the window's own call and feed."""
    import jax

    state, losses, first_grad = state0, [], None
    for k in range(CHECKED_STEPS):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
        if k == 0:
            first_grad = jax.tree.map(lambda x: np.asarray(x) / (1 - b1),
                                      jax.device_get(state[1].m))
    return state, {"losses": losses, "first_grad": first_grad,
                   "params0": jax.device_get(state0[0]),
                   "params3": jax.device_get(state[0])}


def reference_steps(cell, indptr, indices, inputs,
                    precision: str = "highest") -> dict:
    """The plain reference's first steps from the same inputs."""
    import jax

    params, feat, labels = inputs
    g = reference.graph_arrays(indptr, indices, cell.config["model"]["arch"])
    with jax.default_matmul_precision(precision):
        losses, first_grad, params3 = reference.adamw_steps(
            params, feat, labels, g, cell.config["model"],
            cell.mix["optimizer"], reference.Numerics(precision),
            CHECKED_STEPS)
    return {"losses": losses, "first_grad": first_grad,
            "params0": jax.device_get(params), "params3": params3}


def run(cell, seed: int, seconds: float, trace: bool, t0: float,
        hooks=None) -> dict:
    import jax

    b = build(cell, hooks)
    inputs, state0, batch = feed(b, cell, seed)
    state, prog_out = first_steps(b.step, state0, batch,
                                  cell.mix["optimizer"]["b1"])
    harness.log(f"first steps: losses {prog_out['losses']}")
    tw = TraceWindow(trace)
    with tw, harness.CompileCounter() as counter:
        t_w0 = time.perf_counter()
        setup_s = t_w0 - t0
        steps = 0
        with tw.span("window"):
            while True:
                with tw.span("step"):
                    state, m = b.step(state, batch)
                    float(m["loss"])
                steps += 1
                if time.perf_counter() - t_w0 >= seconds:
                    break
        t_w1 = time.perf_counter()
    window_s = t_w1 - t_w0
    harness.log(f"window: {steps} steps in {window_s:.3f}s; "
                f"compiles in window: {counter.count} {counter.names}")
    mem = harness.peak_memory() if jax.default_backend() == "tpu" else 0
    indptr, indices = b.indptr, b.indices
    readings = types.SimpleNamespace(
        cell=cell, host={**b.host, "setup_s": setup_s,
                         "window_s": window_s, "steps": steps},
        plan=b.plan, nodes=b.nodes, edges=b.edges,
        trace=tw.data, trace_steps=steps)

    del state, state0, m, batch, b
    gc.collect()
    ref_out = reference_steps(cell, indptr, indices, inputs)
    numbers = compare.train_numbers(prog_out, ref_out)
    harness.log(f"detail: {compare.train_detail(prog_out, ref_out)}")
    return {"readings": readings, "numbers": numbers,
            "attempted": steps, "failed": 0, "memory_peak_bytes": mem,
            "end_to_end": {"setup_s": setup_s,
                           "train_step_ms": window_s / steps * 1e3}}
