"""The readers of what the program records about itself (planner spans,
compile stages and cache counters from its process registry) and of the
aggregation launches by direction, on synthetic readings: each returns its
value, or None when the run has nothing to read."""
import types

import pytest

from chipbench.lib import harness
from chipbench.lib import trace as T

I = T.Interval
STEP = {"fun": "gnn_train_step"}


def _registry():
    from repro.obs import MetricsRegistry

    reg = MetricsRegistry()
    for path, s in (("plan/analyze", 2.0), ("plan/partition", 5.0),
                    ("plan/executor", 1.0)):
        reg.histogram("span_seconds", labels={"span": path}).observe(s)
    reg.histogram("jit_trace_seconds", labels=STEP).observe(3.0)
    reg.histogram("jit_lower_seconds", labels=STEP).observe(4.0)
    reg.histogram("jit_backend_seconds", labels=STEP).observe(18.0)
    reg.histogram("jit_backend_seconds",
                  labels={"fun": "draw"}).observe(9.0)
    reg.counter("compile_cache_misses_total", labels=STEP).inc()
    return reg


def _window():
    ops = [I(0, 3e6, "group_aggregate_fwd.3"),
           I(3e6, 4e6, "group_aggregate_fwd.7"),
           I(4e6, 9e6, "group_aggregate_bwd.4"),
           I(9e6, 10e6, "group_edge_grad.1"),
           I(10e6, 11e6, "fusion.2")]
    return {"ops": {0: ops}}


PROGRAM = {"plan_analyze_s": 2.0, "plan_partition_s": 5.0,
           "plan_executor_s": 1.0, "compile_trace_s": 7.0,
           "compile_backend_s": 18.0, "compile_cache_misses": 1.0}
DEVICE = {"agg_fwd_ms": 2.0, "agg_bwd_ms": 3.0}


@pytest.mark.parametrize("name", sorted(PROGRAM))
def test_program_reader_reads_the_registry(name):
    r = types.SimpleNamespace(registry=_registry())
    assert harness._reader(name)(r) == pytest.approx(PROGRAM[name])


@pytest.mark.parametrize("name", sorted(PROGRAM))
def test_program_reader_without_a_record_reads_nothing(name):
    from repro.obs import MetricsRegistry

    r = types.SimpleNamespace(registry=MetricsRegistry())
    assert harness._reader(name)(r) is None


@pytest.mark.parametrize("name", sorted(DEVICE))
def test_direction_reader_splits_kernel_time(name):
    r = types.SimpleNamespace(window=_window(), trace_steps=2)
    assert harness._reader(name)(r) == pytest.approx(DEVICE[name])
    both = sum(harness._reader(n)(r) for n in DEVICE)
    assert both == pytest.approx(harness._reader("agg_kernel_ms")(r))


@pytest.mark.parametrize("name", sorted(DEVICE))
def test_direction_reader_on_unnamed_launches_reads_nothing(name):
    # a program whose launches carry no direction (all `group_aggregate`)
    win = {"ops": {0: [I(0, 5e6, "group_aggregate.48")]}}
    assert harness._reader(name)(types.SimpleNamespace(
        window=win, trace_steps=2)) is None
    assert harness._reader(name)(types.SimpleNamespace(
        window=None, trace_steps=2)) is None


def test_traced_run_reports_the_program_set_up(cpu_run):
    from conftest import tiny_cell

    out = cpu_run(tiny_cell("gcn-blogcatalog.train"), trace=True)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert set(PROGRAM) <= set(m)
    assert all(m[n]["value"] > 0 for n in PROGRAM
               if n != "compile_cache_misses")
    # no chip planes in a CPU trace: the direction readers find nothing
    assert not set(DEVICE) & set(m)
