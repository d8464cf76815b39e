"""The trace reduction: busy union, kernel time by name, idle gaps, on
synthetic intervals and on a trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

from chipbench.lib import trace as T

I = T.Interval
RECORDED = Path(__file__).parent / "data" / "gin_train_small.xplane.pb"


def test_union_merges_overlaps_and_clips():
    ivs = [I(0, 10, "a"), I(5, 15, "b"), I(20, 30, "a"), I(29, 31, "c")]
    assert T.union_ns(ivs) == 15 + 11
    assert T.union_ns(ivs, lo=8, hi=25) == 7 + 5
    assert T.union_ns([]) == 0


def test_gaps_longest_first_and_at_the_edges():
    ivs = [I(10, 20, "a"), I(15, 25, "b"), I(40, 45, "a")]
    assert T.gaps(ivs, 0, 50) == [(25, 40), (0, 10), (45, 50)]
    assert T.gaps([], 0, 5) == [(0, 5)]
    assert T.gaps([I(0, 100, "a")], 10, 20) == []


def test_kernel_time_by_name_prefix():
    ivs = [I(0, 4, "group_aggregate.3"), I(4, 6, "fusion.1"),
           I(6, 9, "group_edge_grad"), I(9, 10, "group_aggregate")]
    assert T.sum_by_name(ivs, ("group_aggregate", "group_edge_grad")) == 8
    top = T.top_by_name(ivs, 2)
    assert [name for name, _ in top] == ["group_aggregate.3",
                                         "group_edge_grad"]
    assert [s for _, s in top] == pytest.approx([4e-9, 3e-9])


def test_gap_labels_pick_the_span_that_covers_most():
    spans = [I(0, 12, "chipbench/step"), I(12, 40, "chipbench/submit")]
    labels = T.label_gaps([(10, 30), (45, 50)], spans)
    assert [name for name, _ in labels] == ["chipbench/submit", "none"]
    assert [s for _, s in labels] == pytest.approx([20e-9, 5e-9])


def test_window_reads_busy_share_from_its_span():
    data = T.TraceData(
        device_ops={0: [I(0, 50, "x"), I(100, 150, "y"), I(190, 300, "z")]},
        host_spans=[I(100, 200, "chipbench/window"),
                    I(100, 150, "chipbench/step")])
    win = T.window(data)
    assert win["window_s"] == pytest.approx(100e-9)
    assert win["busy_s"] == pytest.approx(60e-9)
    assert [iv.name for iv in win["ops"][0]] == ["y", "z"]
    [[label, idle]] = T.breakdown(win)["idle_gaps"]
    assert label == "none" and idle == pytest.approx(40e-9)
    assert T.window(T.TraceData({0: []}, [])) is None


def test_op_names_from_hlo_text():
    assert T.op_name("%group_aggregate.9 = f32[8,96]{1,0} custom-call("
                     "s32[4]{0} %a)") == "group_aggregate.9"
    assert T.op_name("jit_step_fn(163)") == "jit_step_fn(163)"


def test_recorded_chip_trace():
    # three steps of a 4,000-node GIN train step, traced on a TPU v5e
    data = T.load(str(RECORDED))
    assert set(data.device_ops) == {0}
    win = T.window(data)
    assert win is not None and 0 < win["busy_s"] < win["window_s"]
    steps = [s for s in data.host_spans if s.name == "chipbench/step"]
    assert len(steps) == 3
    ops = win["ops"][0]
    kernels = [iv for iv in ops if iv.name.startswith("group_aggregate")]
    # five forward aggregations and four backward ones per step
    assert len(kernels) == 3 * 9
    kernel_ns = T.sum_by_name(ops, ("group_aggregate", "group_edge_grad"))
    assert 0 < kernel_ns <= T.union_ns(ops)
    bd = T.breakdown(win)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert all(s > 0 for _, s in bd["idle_gaps"])
