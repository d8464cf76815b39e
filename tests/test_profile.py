"""Profiling harness, Chrome-trace export, perf baselines + CI gate.

Covers the observability tentpole: `measure` calibration and stats,
the Chrome/Perfetto exporter's event structure,
`repro.obs.baseline` verdicts, and the `tools/bench_compare.py` CLI
(clean / regressed / missing-row / schema-mismatch exits).
"""
from __future__ import annotations

import importlib.util
import json
import os
import time

import numpy as np
import pytest

from repro.obs import (MetricsRegistry, SpanTracer, chrome_trace_doc,
                       compare_rows, make_baseline, measure, row_tolerance,
                       save_baseline, validate_baseline, write_chrome_trace)
from repro.obs.profile import Measurement


def _load_bench_compare():
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "bench_compare.py")
    spec = importlib.util.spec_from_file_location("bench_compare", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- measure

def test_measure_basic_stats():
    m = measure(lambda x: x + 1, 2.0, warmup=1, iters=6)
    assert m.count == 6 and m.warmup == 1
    assert m.min <= m.p50 <= m.p90 <= m.max
    row = m.to_row()
    assert set(row) == {"p50_us", "p90_us", "min_us", "mean_us", "iters"}
    assert row["iters"] == 6


def test_measure_quantiles_match_numpy():
    samples = (0.5, 0.1, 0.9, 0.3, 0.7, 0.2)
    m = Measurement(samples=samples, warmup=0)
    assert m.p50 == pytest.approx(np.median(samples))
    assert m.p90 == pytest.approx(np.quantile(samples, 0.9))
    assert m.min == min(samples)


def test_measure_trimmed_mean_drops_outliers():
    # one huge outlier among ten samples must not move the trimmed mean
    samples = (1.0,) * 9 + (100.0,)
    m = Measurement(samples=samples, warmup=0)
    assert m.trimmed_mean == pytest.approx(1.0)
    assert m.mean > 10.0


def test_measure_calibrated_warmup_absorbs_slow_first_call():
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] == 1:
            time.sleep(0.05)       # stands in for jit compilation

    m = measure(fn, iters=3)       # warmup=None -> calibrated
    # the slow first call cannot be a timed sample: warmup ran past it
    assert m.warmup >= 2
    assert m.p50 < 0.05
    # fixed warmup is honored exactly
    calls["n"] = 0
    m2 = measure(fn, warmup=3, iters=2)
    assert m2.warmup == 3 and m2.count == 2


def test_measure_rejects_zero_iters():
    with pytest.raises(ValueError):
        measure(lambda: None, iters=0)


# ----------------------------------------------------------- chrome trace

def test_chrome_trace_doc_nesting_and_metadata():
    reg = MetricsRegistry()
    tr = SpanTracer(reg)
    with tr.span("outer", k=1):
        with tr.span("inner"):
            time.sleep(0.001)
    doc = chrome_trace_doc(tr, context={"git_sha": "abc"})
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    by_name = {e["name"]: e for e in evs}
    assert set(by_name) == {"outer", "outer/inner"}
    outer, inner = by_name["outer"], by_name["outer/inner"]
    # Perfetto nests by time containment: inner inside [outer, outer+dur]
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"k": 1}
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    assert any(e["name"] == "thread_name" for e in meta)
    assert doc["otherData"]["git_sha"] == "abc"


def test_write_chrome_trace_round_trip(tmp_path):
    reg = MetricsRegistry()
    tr = SpanTracer(reg)
    with tr.span("a"):
        pass
    path = tmp_path / "trace.json"
    write_chrome_trace(str(path), tr)
    doc = json.load(open(path))
    assert any(e["name"] == "a" for e in doc["traceEvents"])
    assert doc["displayTimeUnit"] == "ms"


def test_trainer_emits_nested_train_spans(tmp_path):
    from repro.runtime.trainer import Trainer, TrainerConfig

    reg = MetricsRegistry()
    tr = SpanTracer(reg)
    trainer = Trainer(
        TrainerConfig(ckpt_dir=str(tmp_path / "ck"), ckpt_every=2,
                      log_every=100),
        lambda state, batch: (state + 1, {"loss": float(state)}),
        lambda step: step, 0, tracer=tr)
    trainer.run(4)
    trainer.close()
    paths = {r["span"] for r in tr.records()}
    assert "train" in paths
    assert "train/step" in paths
    assert "train/step/batch" in paths
    assert "train/checkpoint" in paths        # ckpt_every=2, 4 steps
    # the same structure survives the Chrome-trace export
    doc = chrome_trace_doc(tr)
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert any(n.startswith("train/") for n in names)


# -------------------------------------------------------------- baselines

def _rows(us, spread=0.05):
    return [{"name": "agg/x/group", "us_per_call": us,
             "p50_us": us, "p90_us": us * (1 + spread)}]


def test_baseline_make_validate_round_trip(tmp_path):
    doc = make_baseline("bench_x", _rows(100.0),
                        context={"git_sha": "abc"})
    assert validate_baseline(doc) == []
    path = tmp_path / "bench_x.json"
    save_baseline(doc, str(path))
    assert validate_baseline(json.load(open(path))) == []


@pytest.mark.parametrize("mutate, frag", [
    (lambda d: d.update(schema="nope"), "schema"),
    (lambda d: d.update(rows=[]), "rows"),
    (lambda d: d["rows"][0].pop("us_per_call"), "us_per_call"),
    (lambda d: d["rows"][0].pop("name"), "name"),
    (lambda d: d.update(history="not-a-list"), "history"),
])
def test_baseline_validate_negatives(mutate, frag):
    doc = make_baseline("bench_x", _rows(100.0),
                        context={"git_sha": "abc"})
    mutate(doc)
    problems = validate_baseline(doc)
    assert problems and any(frag in p for p in problems)


def test_row_tolerance_noise_aware():
    # no recorded spread -> generous fallback
    assert row_tolerance({"us_per_call": 10.0}) == pytest.approx(0.25)
    # recorded 5% spread, noise_factor 3 -> 15%
    b = _rows(100.0, spread=0.05)[0]
    assert row_tolerance(b) == pytest.approx(0.15)
    # the floor wins over a tiny spread
    tight = _rows(100.0, spread=0.01)[0]
    assert row_tolerance(tight, rel_floor=0.10) == pytest.approx(0.10)
    # the larger (noisier) of base/current governs
    noisy_cur = _rows(100.0, spread=0.20)[0]
    assert row_tolerance(b, noisy_cur) == pytest.approx(0.60)


def test_compare_rows_verdicts():
    base = _rows(100.0) + [{"name": "gone", "us_per_call": 5.0}]
    cur = _rows(100.0) + [{"name": "fresh", "us_per_call": 1.0}]
    v = {r["name"]: r["verdict"] for r in compare_rows(base, cur)}
    assert v == {"agg/x/group": "flat", "gone": "missing", "fresh": "new"}
    # 2x slower on a 15% tolerance -> regress; 2x faster -> improve
    slow = [{**_rows(200.0)[0]}]
    fast = [{**_rows(50.0)[0]}]
    assert compare_rows(_rows(100.0), slow)[0]["verdict"] == "regress"
    assert compare_rows(_rows(100.0), fast)[0]["verdict"] == "improve"


def test_compare_rows_spread_widens_tolerance():
    # +40% would regress on the default tolerance, but a recorded 20%
    # spread (x3 noise factor = 60% tolerance) absorbs it
    base, cur = _rows(100.0, spread=0.20), _rows(140.0, spread=0.20)
    assert compare_rows(base, cur)[0]["verdict"] == "flat"
    assert compare_rows(_rows(100.0), _rows(140.0))[0]["verdict"] == \
        "regress"


def test_append_history_bounded():
    from repro.obs import append_history
    doc = make_baseline("s", _rows(1.0))
    for i in range(60):
        append_history(doc, _rows(float(i + 1)),
                       context={"git_sha": f"sha{i}"}, max_history=50)
    assert len(doc["history"]) == 50
    assert doc["history"][-1]["git_sha"] == "sha59"
    assert doc["rows"][0]["us_per_call"] == 60.0


# -------------------------------------------------- bench_compare CLI gate

def _bench_doc(rows, ok=True):
    return {"schema": "repro.bench/v1", "section": "t", "module": "m",
            "ok": ok, "wall_s": 1.0, "context": {"git_sha": "abc"},
            "rows": rows}


def _write_pair(tmp_path, base_rows, cur_rows, section="bench_t"):
    bench_dir = tmp_path / "bench"
    base_dir = tmp_path / "baselines"
    bench_dir.mkdir(exist_ok=True)
    base_dir.mkdir(exist_ok=True)
    with open(bench_dir / f"BENCH_{section}.json", "w") as f:
        json.dump(_bench_doc(cur_rows), f)
    doc = make_baseline(section, base_rows, context={"git_sha": "abc"})
    save_baseline(doc, str(base_dir / f"{section}.json"))
    return str(bench_dir), str(base_dir)


def test_bench_compare_clean_exit_zero(tmp_path, capsys):
    bc = _load_bench_compare()
    bench_dir, base_dir = _write_pair(tmp_path, _rows(100.0), _rows(102.0))
    rc = bc.main(["--bench-dir", bench_dir, "--baseline-dir", base_dir])
    out = capsys.readouterr().out
    assert rc == 0
    assert "flat" in out and "agg/x/group" in out


def test_bench_compare_regression_exits_nonzero_naming_metric(tmp_path,
                                                              capsys):
    bc = _load_bench_compare()
    # synthetically slowed row: 3x the baseline, far past any tolerance
    bench_dir, base_dir = _write_pair(tmp_path, _rows(100.0), _rows(300.0))
    rc = bc.main(["--bench-dir", bench_dir, "--baseline-dir", base_dir])
    out = capsys.readouterr().out
    assert rc == 1
    assert "regress" in out and "agg/x/group" in out
    # --warn-only downgrades the perf failure, not the report
    rc = bc.main(["--bench-dir", bench_dir, "--baseline-dir", base_dir,
                  "--warn-only"])
    assert rc == 0
    assert "regress" in capsys.readouterr().out


def test_bench_compare_missing_row_fails(tmp_path, capsys):
    bc = _load_bench_compare()
    base = _rows(100.0) + [{"name": "dropped", "us_per_call": 5.0}]
    bench_dir, base_dir = _write_pair(tmp_path, base, _rows(100.0))
    rc = bc.main(["--bench-dir", bench_dir, "--baseline-dir", base_dir])
    out = capsys.readouterr().out
    assert rc == 1 and "MISSING" in out and "dropped" in out


def test_bench_compare_schema_mismatch_exits_two(tmp_path, capsys):
    bc = _load_bench_compare()
    bench_dir, base_dir = _write_pair(tmp_path, _rows(100.0), _rows(100.0))
    # corrupt the baseline schema: hard failure even under --warn-only
    bad = json.load(open(os.path.join(base_dir, "bench_t.json")))
    bad["schema"] = "wrong/v0"
    with open(os.path.join(base_dir, "bench_t.json"), "w") as f:
        json.dump(bad, f)
    rc = bc.main(["--bench-dir", bench_dir, "--baseline-dir", base_dir,
                  "--warn-only"])
    out = capsys.readouterr().out
    assert rc == 2 and "SCHEMA PROBLEM" in out


def test_bench_compare_failed_section_exits_two(tmp_path, capsys):
    bc = _load_bench_compare()
    bench_dir = tmp_path / "bench"
    bench_dir.mkdir()
    with open(bench_dir / "BENCH_t.json", "w") as f:
        json.dump(_bench_doc(_rows(1.0), ok=False), f)
    rc = bc.main(["--bench-dir", str(bench_dir),
                  "--baseline-dir", str(tmp_path / "baselines")])
    assert rc == 2
    assert "ok: false" in capsys.readouterr().out


def test_bench_compare_update_baselines(tmp_path, capsys):
    bc = _load_bench_compare()
    bench_dir = tmp_path / "bench"
    base_dir = tmp_path / "baselines"
    bench_dir.mkdir()
    with open(bench_dir / "BENCH_new.json", "w") as f:
        json.dump(_bench_doc(_rows(100.0)), f)
    # first run seeds the baseline ...
    rc = bc.main(["--bench-dir", str(bench_dir), "--baseline-dir",
                  str(base_dir), "--update-baselines"])
    assert rc == 0
    doc = json.load(open(base_dir / "new.json"))
    assert validate_baseline(doc) == [] and len(doc["history"]) == 1
    # ... a later update installs new rows and appends history, and a
    # would-be regression does not fail an update run
    with open(bench_dir / "BENCH_new.json", "w") as f:
        json.dump(_bench_doc(_rows(500.0)), f)
    rc = bc.main(["--bench-dir", str(bench_dir), "--baseline-dir",
                  str(base_dir), "--update-baselines"])
    assert rc == 0
    doc = json.load(open(base_dir / "new.json"))
    assert doc["rows"][0]["us_per_call"] == 500.0
    assert len(doc["history"]) == 2


def test_committed_baselines_are_valid():
    """The baselines shipped in-repo must satisfy their own schema."""
    base_dir = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                            "baselines")
    files = [f for f in os.listdir(base_dir) if f.endswith(".json")]
    assert files, "no committed baselines found"
    for f in files:
        doc = json.load(open(os.path.join(base_dir, f)))
        assert validate_baseline(doc, f) == []
