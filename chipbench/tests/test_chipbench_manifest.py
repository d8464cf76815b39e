"""BENCHMARK.json keeps to the benchmark's contract, and every cell finds
its files by name."""
import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def _reported(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_texts():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    cfg = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert cfg["file"].startswith("chipbench/")
    config = json.loads((ROOT / cfg["file"]).read_text())
    assert config["name"] == cfg["name"]
    assert config["reduced"] == cfg["reduced"]
    mix = json.loads((ROOT / "chipbench" / "mixes"
                      / f"{w['traffic']}.json").read_text())
    assert (ROOT / "chipbench" / "lib" / f"{mix['loop']}.py").exists()
    assert (ROOT / "chipbench" / "lib" / "arch"
            / f"{config['model']['arch']}.py").exists()
    params = json.loads((ROOT / "chipbench" / "workloads"
                         / f"{cell}.json").read_text())
    loop = importlib.import_module(f"chipbench.lib.{mix['loop']}")
    assert set(params["limits"]) == set(loop.CHECKS)
    for m in BENCH["per_layer"]:
        if _reported(m, cell):
            assert (ROOT / "chipbench" / "metrics"
                    / f"{m['name']}.py").exists(), m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = [m["name"] for m in BENCH["end_to_end"] if _reported(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(_reported(m, cell) for m in BENCH["per_layer"])


def test_moves_is_reported_in_every_cell_of_the_metric():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert _reported(target, cell), (m["name"], cell)


def test_configs_are_used_and_files_distinct():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
