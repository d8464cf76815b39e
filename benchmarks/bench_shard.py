"""Sharded execution benchmark: halo-exchange step time vs shard count.

Times one full-graph GCN optimizer step (fwd+bwd through the per-shard
group schedules, all-gather halo exchange, psum'd grads) at shard counts
{1, 2, 4} against the single-device step, and reports the shard splitter's
balance/halo metrics.  On a TPU host the measurement runs in this process
over the chips it holds (shard counts above the device count are
skipped).  On CPU, device counts are fixed per process before jax
initializes, so it runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

    PYTHONPATH=src python -m benchmarks.bench_shard [--smoke]

CSV contract per line: name,us_per_call,derived (us_per_call = per step).
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

SHARD_COUNTS = (1, 2, 4)


def _worker(smoke: bool) -> None:
    """Body that runs inside the forced-device subprocess."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit, time_fn
    from repro.distributed.graph_shard import make_sharded_train_step
    from repro.graphs.csr import random_power_law
    from repro.models.gnn import GNNConfig, build_gnn, make_gnn_train_step
    from repro.optim.adamw import AdamWConfig, adamw_init

    if smoke:
        num_nodes, in_dim, hidden, iters = 2000, 16, 16, 2
    else:
        num_nodes, in_dim, hidden, iters = 50_000, 64, 64, 5

    g = random_power_law(num_nodes, 8.0, seed=0)
    rng = np.random.default_rng(0)
    feat = rng.standard_normal((num_nodes, in_dim)).astype(np.float32)
    labels = rng.integers(0, 4, num_nodes).astype(np.int32)

    cfg = GNNConfig(arch="gcn", in_dim=in_dim, hidden_dim=hidden,
                    num_classes=4, num_layers=2)
    model = build_gnn(g, cfg, reorder="on", tune_iters=2 if smoke else 4,
                      with_backward=True)
    batch = {"feat": jnp.asarray(model.plan.renumber_features(feat)),
             "labels": jnp.asarray(model.plan.renumber_features(labels))}
    state = (model.params, adamw_init(model.params))
    opt = AdamWConfig(lr=1e-3)

    def timed(step_fn):
        return time_fn(lambda: step_fn(state, batch)[1]["loss"],
                       warmup=1, iters=iters)

    t1 = timed(make_gnn_train_step(model, opt))
    emit(f"shard_step/gcn/p1/n{num_nodes}", t1 * 1e6,
         f"tiles={model.plan.stats['tiles']}")

    for P in SHARD_COUNTS:
        if P == 1 or P > jax.device_count():
            continue
        shards = model.plan.shards(P)
        st = shards.stats()
        t = timed(make_sharded_train_step(cfg, shards, opt))
        halo = max(st["halo_frac"])
        emit(f"shard_step/gcn/p{P}/n{num_nodes}", t * 1e6,
             f"vs_1dev={t1 / t:.2f}x;edge_balance={st['edge_balance']:.2f};"
             f"max_halo_frac={halo:.2f};tiles={st['tiles_per_shard']}")

    # bf16 halo exchange: same schedule knobs, dtype policy flipped — the
    # all-gathered activation matrix halves its bytes.  Same-seed params,
    # so the loss is directly comparable to the f32 rows.
    import dataclasses

    P = 2
    if P > jax.device_count():
        return
    cfg16 = dataclasses.replace(cfg, feat_dtype="bfloat16")
    model16 = build_gnn(
        g, cfg16, reorder="on", tune_iters=2 if smoke else 4,
        with_backward=True,
        config=dataclasses.replace(model.plan.config,
                                   feat_dtype="bfloat16"))
    shards16 = model16.plan.shards(P)
    state16 = (model16.params, adamw_init(model16.params))
    step16 = make_sharded_train_step(cfg16, shards16, opt)
    t16 = time_fn(lambda: step16(state16, batch)[1]["loss"],
                  warmup=1, iters=iters)
    n_pad = shards16.spec.padded_nodes
    gathered_f32 = n_pad * hidden * 4
    gathered_bf16 = n_pad * hidden * 2
    emit(f"shard_step/gcn/p{P}/n{num_nodes}/bf16", t16 * 1e6,
         f"halo_gather_bytes={gathered_bf16};f32_bytes={gathered_f32};"
         f"exchange_ratio={gathered_bf16 / gathered_f32:.2f}x")


def run(smoke: bool = True) -> None:
    """Measure here on a TPU; on CPU spawn the forced-device subprocess and
    stream its CSV lines."""
    import jax

    if jax.default_backend() == "tpu":
        # a chip belongs to one process: this one holds it now
        _worker(smoke)
        return
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count="
                  f"{max(SHARD_COUNTS)}",
        PYTHONPATH=os.pathsep.join(
            p for p in (os.path.join(os.path.dirname(__file__), "..", "src"),
                        os.path.dirname(os.path.dirname(__file__)),
                        os.environ.get("PYTHONPATH")) if p))
    cmd = [sys.executable, "-m", "benchmarks.bench_shard", "--worker"]
    if smoke:
        cmd.append("--smoke")
    r = subprocess.run(cmd, env=env, text=True, capture_output=True)
    # re-emit the worker's CSV rows through common.emit so run.py's json
    # capture sees them (the subprocess's own capture dies with it)
    from benchmarks.common import emit
    for line in r.stdout.splitlines():
        parts = line.split(",", 2)
        try:
            us = float(parts[1])
        except (IndexError, ValueError):
            print(line)
            continue
        emit(parts[0], us, parts[2] if len(parts) > 2 else "")
    if r.returncode != 0:
        sys.stderr.write(r.stderr)
        raise RuntimeError(f"bench_shard worker failed ({r.returncode})")


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="small graph + few iters (CI budget)")
    p.add_argument("--worker", action="store_true",
                   help="internal: run the measurement in THIS process "
                        "(expects forced devices already set)")
    args = p.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.worker:
        _worker(smoke=args.smoke)
    else:
        run(smoke=args.smoke)
    return 0


if __name__ == "__main__":
    sys.exit(main())
