"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps.

The JAX profiler writes one ``.xplane.pb`` per traced session.  Each chip
is a plane named ``/device:TPU:<i>``; its ``XLA Ops`` line holds one event
per operation the chip ran, named by the HLO instruction's text
(``%group_aggregate.9 = f32[...] custom-call(...)`` for a Pallas kernel
named ``group_aggregate``), with start and duration in nanoseconds on the
host's clock.  Events are kept under the instruction's name
(``group_aggregate.9``).  Host threads are lines of the ``/host:CPU``
plane; the harness's own `jax.profiler.TraceAnnotation` spans appear there
under their names.

Everything below works on plain ``(start_ns, end_ns, name)`` intervals, so
it is tested on synthetic intervals as well as on a recorded trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Iterable, Sequence

__all__ = ["Interval", "load", "union_ns", "gaps", "sum_by_name",
           "top_by_name", "label_gaps", "TraceData", "op_name", "window",
           "breakdown"]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass(frozen=True)
class Interval:
    start: float          # ns
    end: float            # ns
    name: str


@dataclasses.dataclass
class TraceData:
    """Device operations per chip and the harness's host spans."""

    device_ops: dict      # device index -> [Interval]
    host_spans: list      # [Interval] whose name starts with the prefix


def union_ns(ivs: Iterable[Interval], lo: float = -float("inf"),
             hi: float = float("inf")) -> float:
    """Length of the union of the intervals, clipped to ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for iv in sorted(ivs, key=lambda x: x.start):
        s, e = max(iv.start, lo), min(iv.end, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(ivs: Iterable[Interval], lo: float, hi: float) -> list:
    """Stretches of ``[lo, hi]`` that no interval covers, as
    ``(start, end)``, longest first."""
    out, t = [], lo
    for iv in sorted(ivs, key=lambda x: x.start):
        if iv.end <= t:
            continue
        if iv.start > t:
            out.append((t, min(iv.start, hi)))
        t = max(t, iv.end)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    out = [(s, e) for s, e in out if e > s]
    return sorted(out, key=lambda g: g[0] - g[1])


def sum_by_name(ivs: Iterable[Interval], names: Sequence[str]) -> float:
    """Summed duration (ns) of the intervals whose name starts with one of
    ``names``."""
    names = tuple(names)
    return sum(iv.end - iv.start for iv in ivs if iv.name.startswith(names))


def top_by_name(ivs: Iterable[Interval], k: int = 10) -> list:
    """``[[name, seconds], ...]`` of the ``k`` names with most summed time."""
    acc: dict = {}
    for iv in ivs:
        acc[iv.name] = acc.get(iv.name, 0.0) + (iv.end - iv.start)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in top]


def label_gaps(gap_list: Sequence, spans: Sequence[Interval],
               k: int = 10) -> list:
    """``[[label, seconds], ...]`` of the ``k`` longest gaps, each labelled
    by the host span that overlaps it most (``"none"`` when none does)."""
    out = []
    for s, e in gap_list[:k]:
        best, best_ov = "none", 0.0
        for sp in spans:
            ov = min(e, sp.end) - max(s, sp.start)
            if ov > best_ov:
                best, best_ov = sp.name, ov
        out.append([best, (e - s) * 1e-9])
    return out


def op_name(text: str) -> str:
    """``%name.3 = f32[...] op(...)`` -> ``name.3``; other names as they
    are."""
    if text.startswith("%"):
        return text[1:].split(" = ", 1)[0]
    return text


def latest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str, span_prefix: str = "chipbench/") -> TraceData:
    """Read an ``.xplane.pb`` into device operations and harness spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):])
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Interval(ev.start_ns, ev.end_ns,
                                        op_name(ev.name))
                               for ev in line.events)
            device_ops[idx] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(Interval(ev.start_ns, ev.end_ns, ev.name)
                             for ev in line.events
                             if ev.name.startswith(span_prefix))
    return TraceData(device_ops=device_ops, host_spans=spans)


def window(data: TraceData, chips: int = 1) -> dict:
    """The traced window, from the harness's ``chipbench/window`` span:
    its bounds, the device operations of the chips used inside it, and
    the union of their intervals averaged over those chips."""
    spans = [s for s in data.host_spans if s.name == "chipbench/window"]
    if not spans:
        return None
    lo, hi = spans[0].start, spans[0].end
    ops = {d: [iv for iv in data.device_ops.get(d, [])
               if iv.end > lo and iv.start < hi] for d in range(chips)}
    busy = sum(union_ns(v, lo, hi) for v in ops.values()) / chips
    return {"lo": lo, "hi": hi, "window_s": (hi - lo) * 1e-9,
            "busy_s": busy * 1e-9, "ops": ops,
            "spans": [s for s in data.host_spans
                      if s.name != "chipbench/window"
                      and s.end > lo and s.start < hi]}


def breakdown(win: dict, k: int = 10) -> dict:
    """The top device operations and the longest idle gaps of chip 0,
    each gap labelled by the harness span it fell in."""
    ops = win["ops"][0]
    return {"device_ops": top_by_name(ops, k),
            "idle_gaps": label_gaps(gaps(ops, win["lo"], win["hi"]),
                                    win["spans"], k)}
