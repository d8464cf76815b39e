"""Readings from which each cell's correctness limits are set.  Not part
of a benchmark run.

    python3 chipbench/controls.py program --workload <cell> --seeds 1 2 ...
    python3 chipbench/controls.py control --workload <cell> --seeds 1 2 ...

``program``: the program's numbers over many seeds in one process (the
lower readings).  A train cell plans and compiles once and feeds each seed
through the compiled step; a serve cell runs a short window per seed.

``control``: the reference computed with three-pass bfloat16 products
(``Precision.HIGH``) in the program's place, against the float32
reference (the upper readings).  It needs no program.

Each reading is one JSON line on standard output, with the verdict that
the cell's limits give it (``correct``) and each compared number beside
its limit (``checks``).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse                  # noqa: E402
import json                      # noqa: E402
import sys                       # noqa: E402
from pathlib import Path         # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench.lib import (compare, graphs, harness, loadgen,  # noqa: E402
                           reference, serve, train)

CONTROL = "high"          # three bfloat16 passes: below float32 at HIGHEST


def train_program(cell, seeds):
    b = train.build(cell)
    for seed in seeds:
        inputs, state0, batch = train.feed(b, cell, seed)
        _, prog = train.first_steps(b.step, state0, batch,
                                    cell.mix["optimizer"]["b1"])
        ref = train.reference_steps(cell, b.indptr, b.indices, inputs)
        yield seed, {**compare.train_numbers(prog, ref),
                     **compare.train_detail(prog, ref)}


def train_control(cell, seeds, precision):
    indptr, indices = graphs.make_graph(cell.config["graph"])
    n = len(indptr) - 1
    for seed in seeds:
        inputs = train.cell_inputs(cell, seed, n)
        ref = train.reference_steps(cell, indptr, indices, inputs)
        ctl = train.reference_steps(cell, indptr, indices, inputs,
                                    precision)
        yield seed, {**compare.train_numbers(ctl, ref),
                     **compare.train_detail(ctl, ref)}


def serve_program(cell, seeds, seconds):
    for seed in seeds:
        out = serve.run(cell, seed, seconds, False, time.perf_counter())
        yield seed, {**out["numbers"], **out["end_to_end"]}


def serve_control(cell, seeds, seconds, precision):
    import jax

    model, g = cell.config["model"], cell.config["graph"]
    indptr, indices = graphs.make_graph(g)
    n = len(indptr) - 1
    ga = reference.graph_arrays(indptr, indices, model["arch"])
    for seed in seeds:
        params, feat = serve.make_inputs(seed, model, n, g["feat_dim"],
                                         g["num_classes"])
        stream = loadgen.open_loop(n, cell.mix, cell.params["rate_rps"],
                                   seconds, seed)
        rows = {}
        for prec in ("highest", precision):
            with jax.default_matmul_precision(prec):
                rows[prec] = jax.device_get(jax.jit(
                    lambda p, f, ga, prec=prec: reference.logits(
                        p, f, ga, model, reference.Numerics(prec)))(
                            params, feat, ga))[stream.seeds]
        yield seed, compare.serve_numbers(rows[precision], rows["highest"], 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("program", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(args.workload, manifest)
    harness.require_chips(cell.chips)
    import jax

    harness.enable_compile_cache()
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    seconds = manifest["run_seconds"]
    if cell.loop == "train":
        it = (train_program(cell, args.seeds) if args.what == "program"
              else train_control(cell, args.seeds, CONTROL))
    elif args.what == "program":
        it = serve_program(cell, args.seeds, seconds)
    else:
        it = serve_control(cell, args.seeds, seconds, CONTROL)
    label = "program" if args.what == "program" else f"control-{CONTROL}"
    limits = cell.params["limits"]
    for seed, numbers in it:
        ok, checks = compare.judge({k: numbers[k] for k in limits}, limits)
        print(json.dumps({"workload": cell.name, "what": label,
                          "seed": seed, "correct": ok, "checks": checks,
                          "numbers": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
