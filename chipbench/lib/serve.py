"""Open-loop serving cells (mix ``serve_open``).

The program serves through `AsyncServingEngine` (one tenant, the mix's
SLO class, policy and batch cap) over `ServingEngine.serve_batch`.  The
window offers the cell's fixed rate as an open loop: each request is
submitted when it is due, whether or not earlier ones have finished, and
its latency runs from its due time to its result.  Requests due in the
window are waited for until a minute past its close; one that is refused
or never answered counts as missing, with the latency of the whole wait.

Set-up warms the shapes the cell's traffic uses: the window's own request
seeds, served synchronously in batches of random sizes up to the cap,
pass after pass until a pass builds no new executable; then the window's
first ``warm_s`` seconds of traffic through the async tier, so that its
batches take the window's shapes and the batcher's compute estimate is
filled.
"""
from __future__ import annotations

import gc
import math
import time
import types

import numpy as np

from . import arch, compare, graphs, harness, loadgen, reference
from .trace_window import TraceWindow

__all__ = ["run", "percentile", "DRAIN_S", "CHECKS"]

# the numbers `compare.serve_numbers` gives `compare.judge`
CHECKS = ("logit_gap", "unanswered")

DRAIN_S = 60.0            # wait past the window's close for answers
WARM_PASSES_MAX = 10
WARM_SEEDS = 4000         # the stream's first requests warm the shapes


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in (0, 100])."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


def make_inputs(seed: int, model: dict, nodes: int, in_dim: int,
                classes: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        kp, kf = jax.random.split(key)
        return (reference.init_params(kp, model, in_dim, classes),
                jax.random.normal(kf, (nodes, in_dim), jnp.float32))

    return draw(harness.jax_key(seed))


def _warm(serve_fn, seeds: np.ndarray, max_batch: int, counter,
          seed: int) -> int:
    """Serve the window's own seeds synchronously, cut into batches of
    sizes drawn uniformly from 1..max_batch, pass after pass until a pass
    builds no executable (at least three passes)."""
    rng = np.random.default_rng([seed, 0xA4A])
    for rnd in range(WARM_PASSES_MAX):
        before, pos = counter.count, 0
        while pos < len(seeds):
            size = int(rng.integers(1, max_batch + 1))
            serve_fn([int(s) for s in seeds[pos:pos + size]])
            pos += size
        if rnd >= 2 and counter.count == before:
            return rnd + 1
    return WARM_PASSES_MAX


def _offer(aeng, stream, t_open: float, tw):
    """Submit each request when due; returns the requests and send times."""
    reqs, sent = [], np.empty(len(stream.due))
    for i, (due, seed) in enumerate(zip(stream.due, stream.seeds)):
        wait = t_open + due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        with tw.span("submit"):
            sent[i] = time.perf_counter()
            reqs.append(aeng.submit(int(seed)))
    return reqs, sent


def _counters(engine) -> dict:
    reg = engine.registry
    out = {"batches": engine.stats.batches.value,
           "exact_hits": engine.cache.exact_hits,
           "config_hits": engine.cache.config_hits,
           "misses": engine.cache.misses}
    for part in ("extract", "plan", "compute"):
        h = reg.get("span_seconds", {"span": f"serve_batch/{part}"})
        out[f"{part}_s"] = h.sum if h is not None else 0.0
    return out


def build(cell, seed: int, tw, hooks=None):
    """The resident graph, the seed's weights and features, the serving
    engine and the serve function the async tier calls."""
    from repro.graphs.csr import CSRGraph
    from repro.models.gnn import GNNConfig
    from repro.serving import ServingConfig, ServingEngine

    cfg, mix = cell.config, cell.mix
    model, gspec = cfg["model"], cfg["graph"]
    t = time.perf_counter()
    indptr, indices = graphs.make_graph(gspec)
    n = len(indptr) - 1
    harness.log(f"graph {gspec['dataset']}: {n} nodes, {len(indices)} "
                f"edges ({time.perf_counter() - t:.2f}s)")
    gcfg = GNNConfig(**arch.load(model["arch"]).program_config(
        model, gspec, cfg["dtype"]))
    params, feat = make_inputs(seed, model, n, gspec["feat_dim"],
                               gspec["num_classes"])
    engine = ServingEngine(
        CSRGraph(indptr, indices), np.asarray(feat), gcfg, params=params,
        serving=ServingConfig(max_batch=mix["max_batch"],
                              tune_iters=cfg["plan"]["tune_iters"]))
    serve_fn = engine.serve_batch
    if hooks and "serve" in hooks:
        serve_fn = hooks["serve"](serve_fn)
    if tw.enabled:
        inner = serve_fn

        def serve_fn(seeds, _inner=inner):
            with tw.span("serve_batch"):
                return _inner(seeds)
    return types.SimpleNamespace(engine=engine, serve_fn=serve_fn,
                                 params=params, feat=feat, n=n,
                                 indptr=indptr, indices=indices)


def warm_shapes(b, cell, seeds: np.ndarray, seed: int) -> None:
    t = time.perf_counter()
    with harness.CompileCounter() as counter:
        passes = _warm(b.serve_fn, seeds, cell.mix["max_batch"], counter,
                       seed)
    harness.log(f"warm-up: {passes} passes, {counter.count} executables, "
                f"{time.perf_counter() - t:.2f}s")


def async_engine(cell, serve_fn):
    from repro.serving import AsyncServingEngine, SLOClass, TenantSpec

    mix = cell.mix
    return AsyncServingEngine(
        [TenantSpec("t0", serve_fn, max_batch=mix["max_batch"],
                    slo=SLOClass(mix["slo_class"], mix["slo_ms"] / 1e3))],
        policy=mix["policy"])


def window(aeng, engine, stream, seconds: float, tw, t0: float = None):
    """Offer the stream as an open loop for ``seconds``, then wait for its
    answers; latencies run from each request's due time."""
    before = _counters(engine)
    with tw, harness.CompileCounter() as counter:
        t_open = time.perf_counter()
        with tw.span("window"):
            reqs, sent = _offer(aeng, stream, t_open, tw)
            rest = t_open + seconds - time.perf_counter()
            if rest > 0:
                time.sleep(rest)
        window_s = time.perf_counter() - t_open
    aeng.drain(timeout=DRAIN_S)
    t_end = time.perf_counter()
    delta = {k: v - before[k] for k, v in _counters(engine).items()}
    harness.log(f"window: {len(reqs)} requests in {window_s:.3f}s; "
                f"compiles in window: {counter.count} {counter.names}")
    due = t_open + stream.due
    done = np.array([r.status == "done" for r in reqs])
    lat = np.where(done, [r.t_done for r in reqs], t_end) - due
    return types.SimpleNamespace(
        reqs=reqs, done=done, lat=lat, late=sent - due, window_s=window_s,
        setup_s=None if t0 is None else t_open - t0, counters=delta,
        compiles=counter.count)


def run(cell, seed: int, seconds: float, trace: bool, t0: float,
        hooks=None) -> dict:
    import jax

    tw = TraceWindow(trace)
    b = build(cell, seed, tw, hooks)
    gspec, model = cell.config["graph"], cell.config["model"]
    stream = loadgen.open_loop(b.n, cell.mix, float(cell.params["rate_rps"]),
                               seconds, seed)
    warm_shapes(b, cell, stream.seeds[:WARM_SEEDS], seed)
    aeng = async_engine(cell, b.serve_fn)
    try:
        head = stream.due < cell.mix["warm_s"]
        _offer(aeng, loadgen.Stream(stream.due[head], stream.seeds[head]),
               time.perf_counter(), tw)
        aeng.drain(timeout=DRAIN_S)
        w = window(aeng, b.engine, stream, seconds, tw, t0)
    finally:
        aeng.close(drain=False, timeout=DRAIN_S)
    mem = harness.peak_memory() if jax.default_backend() == "tpu" else 0
    done = w.done
    readings = types.SimpleNamespace(
        cell=cell, host={"setup_s": w.setup_s, "window_s": w.window_s},
        serve={**w.counters, "completed": int(done.sum()),
               "gen_late_s": w.late},
        trace=tw.data)

    served = np.stack([r.result for r, ok in zip(w.reqs, done) if ok]) \
        if done.any() else np.zeros((0, gspec["num_classes"]))
    answered = stream.seeds[done]
    params, feat, indptr, indices = b.params, b.feat, b.indptr, b.indices
    del aeng, b, w.reqs
    gc.collect()
    g = reference.graph_arrays(indptr, indices, model["arch"])
    with jax.default_matmul_precision("highest"):
        ref_all = jax.jit(lambda p, f, g: reference.logits(
            p, f, g, model, reference.Numerics("highest")))(params, feat, g)
    ref_rows = np.asarray(ref_all)[answered]
    numbers = compare.serve_numbers(served, ref_rows, int((~done).sum()))
    return {"readings": readings, "numbers": numbers,
            "attempted": len(done), "failed": int((~done).sum()),
            "memory_peak_bytes": mem,
            "end_to_end": {"setup_s": w.setup_s,
                           "serve_p50_ms": percentile(w.lat, 50) * 1e3,
                           "serve_p95_ms": percentile(w.lat, 95) * 1e3}}
