"""What every cell shares: the manifest, the files found by name, the chip
check, the compile cache, compile counting, metric readers and the result
line.

A cell ``<config>.<traffic>`` in ``BENCHMARK.json`` is run from four kinds
of files, each found by its name:

* ``configs/<config>.json``: the model, its graph and its numerics; the
  model's ``arch`` names its module, ``lib/arch/<arch>.py``;
* ``mixes/<traffic>.json``: the traffic's parameters and the ``loop``
  (``lib/<loop>.py``) that runs that kind of traffic;
* ``workloads/<cell>.json``: the cell's own parameters (rates, lengths)
  and the limits of its correctness checks;
* ``metrics/<metric>.py``: one reader per per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
import threading
from pathlib import Path
from typing import Optional

from . import arch

__all__ = ["ROOT", "BENCH_DIR", "Cell", "load_cell", "require_chips",
           "enable_compile_cache", "CompileCounter", "read_metrics",
           "jax_key", "log", "result_line", "peak_memory"]

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout (listed in .gitignore): part of each entry's key
DEFAULT_CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of the manifest with its files loaded."""

    name: str
    chips: int
    config: dict
    mix: dict
    params: dict          # workloads/<cell>.json
    end_to_end: list      # manifest entries this cell reports
    per_layer: list

    @property
    def loop(self) -> str:
        return self.mix["loop"]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, manifest: Optional[dict] = None) -> Cell:
    if manifest is None:
        manifest = _load_json(ROOT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(by_name)}")
    w = by_name[name]
    cfg = next(c for c in manifest["configs"] if c["name"] == w["config"])
    config = _load_json(ROOT / cfg["file"])
    # an architecture or a task the benchmark lacks fails here, before any
    # graph is built
    arch.load(config["model"]["arch"])
    arch.task(config["graph"])
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        mix=_load_json(BENCH_DIR / "mixes" / f"{w['traffic']}.json"),
        params=_load_json(BENCH_DIR / "workloads" / f"{name}.json"),
        end_to_end=[m for m in manifest["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _reports(m, name)])


def require_chips(chips: int) -> dict:
    """The device stamp; exits non-zero, printing no result, unless JAX's
    devices are TPUs and there are at least ``chips`` of them."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": chips}
    if dev["platform"] != "tpu":
        log(f"no TPU: JAX's first device is {dev['platform']!r}")
        raise SystemExit(2)
    if len(devs) < chips:
        log(f"the cell needs {chips} chips, JAX sees {len(devs)}")
        raise SystemExit(2)
    return dev


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else at a fixed path in the checkout.  Every executable is
    cached, however quickly it compiled."""
    import jax

    path = os.environ.get(CACHE_ENV) or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts executables built (compiled or loaded from the persistent
    cache) inside its ``with`` block; a window that builds one is not
    steady."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.names: list = []
        self._lock = threading.Lock()

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            with self._lock:
                self.count += 1
                self.names.append(str(kw.get("fun_name")))

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


def jax_key(seed: int):
    """A JAX key from any non-negative seed, 64 bits and more."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def peak_memory() -> int:
    """Peak bytes in use on the fullest local chip."""
    import jax

    return max(int(d.memory_stats()["peak_bytes_in_use"])
               for d in jax.local_devices())


def _reader(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries: list, readings) -> dict:
    """Run each entry's reader on the run's readings; a reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for m in entries:
        value = _reader(m["name"])(readings)
        if value is None:
            log(f"metric {m['name']}: nothing to read in this run")
            continue
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown: Optional[dict] = None) -> str:
    """The last line of standard output; the compared numbers, each with
    its limit, come last under ``checks``."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)
