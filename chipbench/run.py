"""Run one benchmark cell on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metric
readers are found by name from ``BENCHMARK.json`` (see chipbench/README.md).
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  Progress and the compared numbers go to
standard error, the numbers last; the last line of standard output is the
result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``, each
compared number with its limit.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()         # set-up is timed from here

import argparse                  # noqa: E402
import importlib                 # noqa: E402
import sys                       # noqa: E402
from pathlib import Path         # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench.lib import compare, harness, trace as trace_lib  # noqa: E402


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool,
             device: dict, hooks=None) -> str:
    """Everything after the look for a chip; returns the result line.
    ``hooks`` lets a test break the timed path underneath."""
    import jax

    harness.log(f"compile cache: {harness.enable_compile_cache()}")
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    loop = importlib.import_module(f"chipbench.lib.{cell.loop}")
    out = loop.run(cell, seed, seconds, trace, T0, hooks)
    ok, checks = compare.judge(out["numbers"], cell.params["limits"])
    device = {**device, "memory_peak_bytes": out["memory_peak_bytes"]}
    breakdown = None
    if trace:
        r = out["readings"]
        r.window = (None if r.trace is None
                    else trace_lib.window(r.trace, cell.chips))
        r.device = device
        metrics = harness.read_metrics(cell.per_layer, r)
        if r.window is not None:
            device.update(busy_s=r.window["busy_s"],
                          window_s=r.window["window_s"])
            breakdown = trace_lib.breakdown(r.window)
    else:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    for name, c in checks.items():
        harness.log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    return harness.result_line(
        correct=ok, attempted=out["attempted"], failed=out["failed"],
        metrics=metrics, device=device, checks=checks, breakdown=breakdown)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    device = harness.require_chips(cell.chips)
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), device)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
