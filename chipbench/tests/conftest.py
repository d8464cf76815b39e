"""Shared fixtures of the benchmark's tests: the repository root and the
program on the import path, and tiny copies of the cells for CPU runs."""
import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# graph sizes at which a whole run fits a CPU test, by generator
TINY_GRAPH = {"power_law": (1500, 1500 * 12), "community": (2000, 2000 * 5)}


# cells whose files are kept for a later PR but that BENCHMARK.json does not
# list yet: (config, traffic, their per-layer metric names)
UNLISTED = {
    "gin-dd.train": ("gin-dd", "train_full", ()),
    "gin-dd.serve": ("gin-dd", "serve_open", (
        "idle_share.serve", "extract_ms.serve", "compute_ms.serve",
        "plan_ms.serve", "plan_cache_hit_rate.serve", "batch_size.serve",
        "gen_late_ms.serve")),
}


def _unlisted_cell(name: str):
    import json

    from chipbench.lib import harness

    config, traffic, layers = UNLISTED[name]
    bench = ROOT / "chipbench"

    def load(path):
        return json.loads(path.read_text())
    e2e = ([{"name": "setup_s", "unit": "s"},
            {"name": "serve_p50_ms", "unit": "ms"},
            {"name": "serve_p95_ms", "unit": "ms"}] if traffic == "serve_open"
           else [{"name": "setup_s", "unit": "s"},
                 {"name": "train_step_ms", "unit": "ms"}])
    return harness.Cell(
        name=name, chips=1,
        config=load(bench / "configs" / f"{config}.json"),
        mix=load(bench / "mixes" / f"{traffic}.json"),
        params=load(bench / "workloads" / f"{name}.json"),
        end_to_end=e2e,
        per_layer=[{"name": m, "unit": "-"} for m in layers])


def tiny_cell(name: str, manifest=None):
    """The cell with its graph cut to a CPU size; ``manifest`` stands in
    for BENCHMARK.json."""
    from chipbench.lib import harness

    cell = (_unlisted_cell(name) if name in UNLISTED
            else harness.load_cell(name, manifest))
    cell.config = copy.deepcopy(cell.config)
    g = cell.config["graph"]
    if "community_size" in g or "num_communities" in g:
        # one community of the configured size and density
        k = g.get("num_communities") or g["num_nodes"] // g["community_size"]
        g["num_nodes"] = g.get("community_size") or g["num_nodes"] // k
        g["num_edges"] /= k
        g["num_communities"] = 1
    else:
        g["num_nodes"], g["num_edges"] = TINY_GRAPH[g["generator"]]
    cell.config["plan"]["tune_iters"] = 2
    if "rate_rps" in cell.params:
        cell.params = {**cell.params, "rate_rps": 40}
        cell.mix = {**cell.mix, "warm_s": 0.25}
    return cell


@pytest.fixture
def cpu_run(monkeypatch):
    """Run a cell past the look for a chip, on the CPU, leaving JAX's
    process-wide settings as it found them."""
    import jax

    from chipbench import run as runner
    from chipbench.lib import harness

    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    saved = jax.config.jax_default_matmul_precision
    device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}

    def go(cell, seed=12345, seconds=0.5, trace=False, hooks=None):
        import json
        line = runner.run_cell(cell, seed, seconds, trace, device, hooks)
        return json.loads(line)

    try:
        yield go
    finally:
        jax.config.update("jax_default_matmul_precision", saved)
