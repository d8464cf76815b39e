"""Serving benchmark: sync engine trace replay + async SLO-aware tier.

Two layers of measurement:

* the original synchronous `ServingEngine` rows (requests/s, latency
  percentiles, batch occupancy, plan-cache hit rate — the "one-time cost
  amortized over many kernel launches" claim, measured);
* the async tier comparison (the PR-7 tentpole): the deadline-aware
  continuous batcher vs the fixed-window `ClockBatcher` baseline, same
  deterministic Zipf schedule, same executor — open-loop phase for
  p50/p99/SLO-attainment + completed-throughput, burst phase
  (``rate_rps=inf``) for saturation throughput.  With ``--shards 2`` the
  same comparison additionally runs against the 2-way sharded
  halo-exchange executor: on a TPU in this process (a chip belongs to the
  one process that holds it), on CPU in a forced-device subprocess (the
  `bench_shard` pattern: device counts are fixed before jax initializes).

    PYTHONPATH=src python -m benchmarks.bench_serve [--smoke] \
        [--shards 2] [--json-out BENCH_serve.json]

CSV contract per line: name,us_per_call,derived (us_per_call = per
request, from completed-throughput).  ``--json-out`` writes the
machine-validated ``BENCH_serve.json`` document (schema
``repro.bench_serve/v1``; `tools.validate_metrics` checks it): run
context, one config row per (devices, policy) cell, and the
deadline-vs-clock comparison verdict CI asserts on.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys

SCHEMA = "repro.bench_serve/v1"
# sentinel for config rows crossing the forced-device subprocess boundary
_CFG_TAG = "@@serve_config@@"

CONFIG_KEYS = ("shards", "policy", "tenants", "requests", "rate_rps",
               "slo_ms", "completed", "rejected", "p50_ms", "p99_ms",
               "slo_attainment", "throughput_rps", "saturation_rps",
               "mean_batch")


def _profile(smoke: bool) -> dict:
    if smoke:
        return dict(num_nodes=1500, avg_degree=6.0, in_dim=16, hidden=16,
                    requests=96, rate_rps=500.0, slo_ms=400.0, max_batch=64,
                    tune_iters=2)
    return dict(num_nodes=20_000, avg_degree=8.0, in_dim=32, hidden=32,
                requests=512, rate_rps=1000.0, slo_ms=400.0, max_batch=64,
                tune_iters=4)


def _build_serve_fn(prof: dict, shards: int):
    """Resident graph + executor; warmed so measured batches replay cached
    plans/executables instead of paying plan build + XLA compile."""
    import numpy as np

    from repro.graphs.csr import random_power_law
    from repro.models.gnn import GNNConfig
    from repro.serving import (ServingConfig, ServingEngine,
                               make_sharded_serve_fn)

    g = random_power_law(prof["num_nodes"], prof["avg_degree"], seed=0)
    rng = np.random.default_rng(0)
    feat = rng.standard_normal((g.num_nodes, prof["in_dim"])
                               ).astype(np.float32)
    cfg = GNNConfig(arch="gcn", in_dim=prof["in_dim"],
                    hidden_dim=prof["hidden"], num_classes=4,
                    num_layers=2)
    if shards > 1:
        serve_fn = make_sharded_serve_fn(g, feat, cfg, num_shards=shards,
                                         tune_iters=prof["tune_iters"])
    else:
        sync = ServingEngine(
            g, feat, cfg,
            serving=ServingConfig(max_batch=prof["max_batch"],
                                  tune_iters=prof["tune_iters"]))
        serve_fn = sync.serve_batch
    b = 1
    while True:
        serve_fn(rng.integers(0, g.num_nodes, size=b).tolist())
        if b >= prof["max_batch"]:
            break
        b = min(2 * b, prof["max_batch"])
    return g, serve_fn


def _measure_policy(g, serve_fn, policy: str, prof: dict,
                    shards: int) -> dict:
    """One comparison cell: open-loop phase (latency/attainment +
    completed throughput over the same Zipf schedule both policies
    replay), then burst phase (saturation throughput)."""
    from benchmarks.common import emit
    from repro.serving import (AsyncServingEngine, LoadSpec, SLOClass,
                               TenantSpec, build_schedule, run_schedule)

    slo_s = prof["slo_ms"] / 1e3

    def fresh_engine():
        return AsyncServingEngine(
            [TenantSpec("default", serve_fn,
                        slo=SLOClass("gold", slo_s),
                        max_batch=prof["max_batch"])],
            policy=policy, window=slo_s / 2, margin=0.005, idle_gap=0.008)

    eng = fresh_engine()
    res = run_schedule(eng, build_schedule(g.num_nodes, LoadSpec(
        requests=prof["requests"], rate_rps=prof["rate_rps"], seed=0)))
    reqs = res["requests_detail"]
    done = [r for r in reqs if r.status == "done"]
    lat = sorted(r.latency for r in done)
    attain = (sum(l <= slo_s for l in lat) / len(lat)) if lat else 0.0
    summary = eng.summary()["default"]
    eng.close()

    eng = fresh_engine()
    burst = run_schedule(eng, build_schedule(g.num_nodes, LoadSpec(
        requests=prof["requests"], rate_rps=math.inf, seed=1)))
    eng.close()

    def pct(q):
        return lat[min(len(lat) - 1, int(q * len(lat)))] * 1e3 if lat else 0.0

    row = {
        "shards": shards,
        "policy": policy,
        "tenants": 1,
        "requests": prof["requests"],
        "rate_rps": prof["rate_rps"],
        "slo_ms": prof["slo_ms"],
        "completed": len(done),
        "rejected": sum(r.status == "rejected" for r in reqs),
        "p50_ms": pct(0.50),
        "p99_ms": pct(0.99),
        "slo_attainment": attain,
        "throughput_rps": res["throughput_rps"],
        "saturation_rps": burst["throughput_rps"],
        "mean_batch": summary["mean_batch"],
    }
    # p50/p90 request latency ride along as the row's noise estimate for
    # the baseline gate (repro.obs.baseline.row_tolerance)
    emit(f"serve_async/{policy}/p{shards}/n{prof['num_nodes']}",
         1e6 / max(row["throughput_rps"], 1e-9),
         f"p50_ms={row['p50_ms']:.1f};p99_ms={row['p99_ms']:.1f};"
         f"attain={attain:.3f};saturation_rps={row['saturation_rps']:.0f};"
         f"mean_batch={row['mean_batch']:.1f}",
         p50_us=row["p50_ms"] * 1e3, p90_us=pct(0.90) * 1e3)
    return row


def _async_configs(smoke: bool, shards: int) -> list:
    prof = _profile(smoke)
    g, serve_fn = _build_serve_fn(prof, shards)
    return [_measure_policy(g, serve_fn, policy, prof, shards)
            for policy in ("deadline", "clock")]


def _sync_rows(smoke: bool) -> None:
    """The original synchronous engine rows (perf-trajectory continuity)."""
    import numpy as np

    from benchmarks.common import emit
    from repro.graphs.csr import random_power_law
    from repro.launch.serve_gnn import build_trace
    from repro.models.gnn import GNNConfig
    from repro.serving import ServingConfig, ServingEngine

    if smoke:
        num_nodes, requests, batch = 1500, 24, 8
    else:
        num_nodes, requests, batch = 20_000, 256, 16

    g = random_power_law(num_nodes, 6.0, seed=0)
    rng = np.random.default_rng(0)
    for arch in ["gcn", "gin"]:
        cfg = GNNConfig(arch=arch, in_dim=16, hidden_dim=16, num_classes=4,
                        num_layers=2)
        feat = rng.standard_normal((g.num_nodes, 16)).astype(np.float32)
        eng = ServingEngine(g, feat, cfg,
                            serving=ServingConfig(max_batch=batch,
                                                  tune_iters=2 if smoke else 4))
        trace = build_trace(g.num_nodes, requests, seed=0)
        eng.run_trace(trace)
        s = eng.summary()
        c = s["cache"]
        # the summary exposes p50/p99; using p99 as the p90 bound
        # over-estimates the spread, which only widens the regression
        # tolerance (the safe direction for serving-path noise)
        emit(f"serve/{arch}/n{num_nodes}",
             1e6 / s["req_per_s"],
             f"p50_ms={s['p50_ms']:.1f};p99_ms={s['p99_ms']:.1f};"
             f"occupancy={s['batch_occupancy']:.2f};"
             f"cache_hit={c['hit_rate']:.2f};plans={c['plans']}",
             p50_us=s["p50_ms"] * 1e3, p90_us=s["p99_ms"] * 1e3)


def _worker(smoke: bool, shards: int) -> None:
    """Body of the forced-device subprocess: measure the sharded cells and
    print each config row behind the sentinel tag (stdout is the only
    channel back to the parent)."""
    for row in _async_configs(smoke, shards):
        print(f"{_CFG_TAG} {json.dumps(row)}")


def _spawn_sharded(smoke: bool, shards: int) -> list:
    """bench_shard pattern: forced host devices in a subprocess, CSV rows
    re-emitted through common.emit, config rows parsed off the sentinel."""
    from benchmarks.common import emit

    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={shards}",
        PYTHONPATH=os.pathsep.join(
            p for p in (os.path.join(os.path.dirname(__file__), "..", "src"),
                        os.path.dirname(os.path.dirname(__file__)),
                        os.environ.get("PYTHONPATH")) if p))
    cmd = [sys.executable, "-m", "benchmarks.bench_serve", "--worker",
           "--shards", str(shards)]
    if smoke:
        cmd.append("--smoke")
    r = subprocess.run(cmd, env=env, text=True, capture_output=True)
    configs = []
    for line in r.stdout.splitlines():
        if line.startswith(_CFG_TAG):
            configs.append(json.loads(line[len(_CFG_TAG):]))
            continue
        parts = line.split(",", 2)
        try:
            us = float(parts[1])
        except (IndexError, ValueError):
            print(line)
            continue
        emit(parts[0], us, parts[2] if len(parts) > 2 else "")
    if r.returncode != 0:
        sys.stderr.write(r.stderr)
        raise RuntimeError(f"bench_serve worker failed ({r.returncode})")
    return configs


def _comparison(configs: list) -> dict:
    """Deadline-vs-clock verdict on the 1-device cells: the deadline
    batcher must hold >= 99% SLO attainment at completed throughput
    strictly above the fixed-window baseline (same schedule)."""
    one = {c["policy"]: c for c in configs if c["shards"] == 1}
    dl, ck = one.get("deadline"), one.get("clock")
    if dl is None or ck is None:
        return {"pass": False, "reason": "missing 1-device cells"}
    ok = (dl["slo_attainment"] >= 0.99
          and dl["throughput_rps"] > ck["throughput_rps"])
    return {
        "baseline": "clock", "candidate": "deadline", "shards": 1,
        "deadline_attainment": dl["slo_attainment"],
        "clock_attainment": ck["slo_attainment"],
        "deadline_throughput_rps": dl["throughput_rps"],
        "clock_throughput_rps": ck["throughput_rps"],
        "throughput_ratio": dl["throughput_rps"]
        / max(ck["throughput_rps"], 1e-9),
        "pass": ok,
    }


def run(smoke: bool = True, *, shards: int = 1,
        json_out: str | None = None) -> None:
    import jax

    from repro.obs import run_context

    _sync_rows(smoke)
    configs = _async_configs(smoke, shards=1)
    if shards > 1:
        # the chip belongs to this process now: never spawn a child for it
        configs += (_async_configs(smoke, shards)
                    if jax.default_backend() == "tpu"
                    else _spawn_sharded(smoke, shards))
    comparison = _comparison(configs)
    doc = {"schema": SCHEMA, "smoke": smoke, "context": run_context(),
           "configs": configs, "comparison": comparison}
    print(f"# serve_async comparison: "
          f"deadline attain={comparison.get('deadline_attainment', 0):.3f} "
          f"throughput x{comparison.get('throughput_ratio', 0):.2f} "
          f"vs clock -> {'PASS' if comparison['pass'] else 'FAIL'}")
    if json_out:
        with open(json_out, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"# wrote {json_out}")
    if not comparison["pass"]:
        raise RuntimeError(f"serve_async comparison failed: {comparison}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="tiny graph + few requests (CI budget)")
    p.add_argument("--shards", type=int, default=1,
                   help="additionally measure the P-way sharded executor "
                        "cells (on CPU: in a forced-device subprocess)")
    p.add_argument("--json-out", default=None,
                   help="write the BENCH_serve.json document here")
    p.add_argument("--worker", action="store_true",
                   help="internal: run the sharded measurement in THIS "
                        "process (expects forced devices already set)")
    args = p.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.worker:
        _worker(smoke=args.smoke, shards=args.shards)
    else:
        run(smoke=args.smoke, shards=args.shards, json_out=args.json_out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
