"""Find the highest rate a serve cell sustains.  Not part of a benchmark
run: it was run once, on the chip, to fix the cell's rate.

    python3 chipbench/sweep.py --workload gin-dd.serve --seed 1 \
        --seconds 8 --rates 200 400 800 1600

One process builds the engine once, warms the shapes of the highest
rate's traffic, then offers each rate as an open loop for ``--seconds``
and prints one JSON line per rate: requests completed per second, p50 and
p95 latency from the due time, and the median latency of the window's
last tenth over its first tenth (a growing backlog reads well over 1).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench.lib import harness, loadgen, serve  # noqa: E402
from chipbench.lib.trace_window import TraceWindow  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    import jax

    harness.enable_compile_cache()
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    tw = TraceWindow(False)
    b = serve.build(cell, args.seed, tw)
    streams = [loadgen.open_loop(b.n, cell.mix, r, args.seconds,
                                 args.seed + i)
               for i, r in enumerate(args.rates)]
    serve.warm_shapes(b, cell, np.concatenate([s.seeds for s in streams]),
                      args.seed)
    aeng = serve.async_engine(cell, b.serve_fn)
    try:
        for rate, stream in zip(args.rates, streams):
            w = serve.window(aeng, b.engine, stream, args.seconds, tw)
            tenth = max(1, len(w.lat) // 10)
            done_at = [r.t_done for r in w.reqs if r.status == "done"]
            span = (max(done_at) - min(r.t_submit for r in w.reqs)
                    if done_at else float("nan"))
            print(json.dumps({
                "rate_rps": rate, "requests": len(w.lat),
                "completed_per_s": int(w.done.sum()) / span,
                "p50_ms": serve.percentile(w.lat, 50) * 1e3,
                "p95_ms": serve.percentile(w.lat, 95) * 1e3,
                "tail_growth": float(np.median(w.lat[-tenth:])
                                     / np.median(w.lat[:tenth])),
                "batch": w.counters["batches"] and
                int(w.done.sum()) / w.counters["batches"],
                "gen_late_p95_ms": serve.percentile(w.late, 95) * 1e3,
                "compiles": w.compiles}), flush=True)
            time.sleep(1.0)
    finally:
        aeng.close(drain=False, timeout=serve.DRAIN_S)
    return 0


if __name__ == "__main__":
    sys.exit(main())
