"""Jit'd public wrappers around the Pallas kernels.

`aggregate(...)` is the user-facing entry point: it takes raw node features
plus a `GroupPartition` schedule, handles all padding, and dispatches to the
Pallas kernel (TPU) or its pure-XLA fallback.

Backend dispatch rules
----------------------
``backend`` selects how the group schedule is executed:

  * ``"xla"`` — a pure gather + segment-sum lowering over the schedule's
    real edges (`repro.kernels.ref.schedule_edges`; without edge members,
    over every slot with `repro.kernels.ref.group_aggregate_ref`).  Runs
    anywhere, is the semantic ground truth, and is natively
    differentiable (every op has an XLA AD rule).  This is the
    reference both the tests and `benchmarks/bench_train.py` compare
    against.
  * ``"pallas"`` — `group_aggregate_pallas` compiled for the local TPU.
    Fastest path; requires a TPU backend.
  * ``"pallas_interpret"`` — the same Pallas kernel executed by the Pallas
    interpreter (`interpret=True`).  The kernel's semantics on CPU; a test
    device only, never chosen by default.

``backend=None`` (the default everywhere) resolves by platform through
`resolve_backend`: ``"pallas"`` when JAX's default backend is a TPU,
``"xla"`` otherwise.

Differentiation: the Pallas backends have no built-in AD rule, so
``aggregate`` installs a `jax.custom_vjp` whenever a *backward schedule* is
supplied (``sched_bwd=``, a `DeviceSchedule` built from the TRANSPOSED
graph's partition — see `core.partition.transpose_graph`).  The backward
pass is then itself a group-aggregate kernel launch over the transposed
schedule (cotangent w.r.t. ``feat``) plus, for the dynamic edge-value path,
a `group_edge_grad_pallas` launch over the forward schedule (cotangent
w.r.t. ``edge_values``).  The custom VJP applies to EVERY backend once
``sched_bwd`` is passed — handing it to ``backend="xla"`` exercises the
transposed schedule through the reference lowering (numerically equivalent
to native AD).  Without ``sched_bwd``, the XLA backend differentiates
natively and the Pallas backends are forward-only (``jax.grad`` raises).

Dtype rules
-----------
``feat`` may be any float dtype (float32, bfloat16, float16); the dtype of
the feature operand is the dtype the kernel's window DMAs move, so a bf16
``feat`` halves the dominant memory-bound term.  Accumulation is ALWAYS
float32 regardless of input dtype: every matmul inside the kernels (and
the XLA references) runs with ``preferred_element_type=float32``, so group
sums never accumulate in reduced precision.

``out_dtype`` selects the dtype of the RESULT, applied as the final cast
after f32 accumulation.  ``None`` (the default) means float32 — the
historical contract.  The end-to-end bf16 policy passes the feature dtype
here so activations stay bf16 between layers (`AggConfig.feat_dtype`,
threaded through `Plan.jit_statics` / `PlanExecutor`).

Backward: the output cotangent is cast to the FORWARD feature dtype before
the transposed-schedule launch (the backward window DMAs enjoy the same
bf16 halving), accumulated in f32, and the returned cotangents match the
primals' dtypes (``feat.dtype`` and ``edge_values.dtype``).  Static edge
values stay float32 inside schedules; dynamic edge values keep their own
dtype through `_layout_edge_values`.
"""
from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp
import numpy as np

from typing import TYPE_CHECKING

from repro.hw import LANES
from repro.kernels import ref as _ref
from repro.kernels.group_aggregate import (group_aggregate_pallas,
                                           group_edge_grad_pallas)

if TYPE_CHECKING:                      # avoid core<->kernels import cycle
    from repro.core.partition import GroupPartition

__all__ = ["aggregate", "DeviceSchedule", "dim_tile", "head_columns",
           "schedule_to_device", "SchedView", "sched_arrays", "sched_static",
           "sched_statics", "sched_statics_for", "BACKENDS", "resolve_backend"]

Backend = Literal["pallas", "pallas_interpret", "xla"]
BACKENDS: tuple = ("pallas", "pallas_interpret", "xla")


def resolve_backend(backend: Optional[str] = None) -> str:
    """The aggregation backend to run: ``backend`` when given, else the
    compiled kernel (``"pallas"``) on a TPU and the XLA reference
    elsewhere.  ``"pallas_interpret"`` is only ever run when asked for."""
    if backend is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    return backend


def dim_tile(dt: int, d: int, dtype) -> int:
    """Effective dim-tile width for a D-wide feature operand.

    The kernel pads D up to a multiple of the tile and launches D/dt_eff
    dim steps.  A TPU block's minor dim must be a multiple of 128 lanes or
    span the whole padded array, so D is first rounded up to the dtype's
    alignment unit (8 for 32-bit, 16 for 16-bit); when ``dt`` covers that,
    one tile spans it, otherwise ``dt`` is rounded up to a multiple of 128.
    """
    # policy dtypes take their alignment from the model layer's single
    # source of truth (what config_infeasibility enforces); dtypes outside
    # the policy vocabulary (f64 under x64) fall back to the packing rule:
    # 8 rows for 32-bit-and-wider, 16 for 16-bit
    dtype = np.dtype(dtype)
    try:
        from repro.core.model import feat_dtype_align
        unit = feat_dtype_align(dtype.name)
    except ValueError:
        unit = max(8, 8 * 4 // max(dtype.itemsize, 1))
    d_aligned = -(-max(d, 1) // unit) * unit
    return min(-(-max(dt, 1) // LANES) * LANES, d_aligned)


class DeviceSchedule:
    """Device-resident copy of a GroupPartition's arrays + static config.

    Array members (T = tiles): ``nbrs``/``edge_val`` (T, gpt, gs),
    ``local_node`` (T, gpt), ``tile_node_block``/``tile_window`` (T,), and
    ``edge_slot``/``edge_pos`` (E,).  Static ints mirror the partition's
    config (`gs`, `gpt`, `ont`, `src_win`) and padding geometry
    (`padded_src_rows`, `padded_out_rows`).

    When a schedule is built from a TRANSPOSED partition to serve as a
    backward schedule, ``edge_perm`` maps its CSR edge order back to the
    forward graph's edge order (``ev_bwd = ev_fwd[edge_perm]``); it is
    ``None`` for ordinary forward schedules.

    ``slot_edge`` ((T*gpt*gs,)) is the CSR edge each slot holds, E for a
    padded one: dynamic edge values are laid out by a gather through it.
    """

    def __init__(self, p: "GroupPartition",
                 edge_perm: Optional[np.ndarray] = None):
        self.nbrs = jnp.asarray(p.nbrs)
        self.edge_val = jnp.asarray(p.edge_val)
        self.local_node = jnp.asarray(p.local_node)
        self.tile_node_block = jnp.asarray(p.tile_node_block)
        self.tile_window = jnp.asarray(p.tile_window)
        self.edge_slot = jnp.asarray(p.edge_slot)
        self.edge_pos = jnp.asarray(p.edge_pos)
        self.edge_perm = None if edge_perm is None else jnp.asarray(edge_perm)
        self.slot_edge = jnp.asarray(_slot_edges(p))
        self.gs, self.gpt, self.ont, self.src_win = p.gs, p.gpt, p.ont, p.src_win
        self.num_nodes = p.num_nodes
        self.num_edges = p.num_edges
        self.padded_src_rows = p.padded_src_rows
        self.padded_out_rows = p.padded_out_rows
        self.num_tiles = p.num_tiles


def _slot_edges(p: "GroupPartition") -> np.ndarray:
    """(T*gpt*gs,) int32: the CSR edge each of the partition's slots holds,
    E (one past the last edge) for a padded slot."""
    e = len(p.edge_slot)
    slots = np.full(np.size(p.nbrs), e, np.int32)
    slots[np.asarray(p.edge_slot, np.int64) * p.gs
          + np.asarray(p.edge_pos)] = np.arange(e, dtype=np.int32)
    return slots


def schedule_to_device(p: "GroupPartition") -> DeviceSchedule:
    return DeviceSchedule(p)


# --- schedule (arrays, statics) split -------------------------------------
#
# The custom VJP below must work when the schedule tensors are jit ARGUMENTS
# (tracers), not closure constants: serving's shared forwards, the sampled
# trainer's per-bucket steps, and the sharded per-device bodies all compile
# ONE executable per shape bucket and feed each schedule in as data.
# `jax.custom_vjp` forbids tracers in nondiff_argnums, so a schedule is
# split into a pytree of arrays (traced) and a hashable tuple of static
# ints (nondiff) and rebuilt inside via `SchedView`.  The Plan IR wraps
# this split as its one jit-argument convention — prefer
# `repro.core.plan.Plan.jit_args()/jit_statics()/executor_from_args` at
# call sites over using these helpers directly.

_SCHED_ARRAY_FIELDS = ("nbrs", "edge_val", "local_node", "tile_node_block",
                       "tile_window", "edge_slot", "edge_pos", "edge_perm",
                       "slot_edge")
# the first N fields are tile-shaped (uniform after tile padding) — the
# (E,)-sized edge members, and the slots' edge index that only dynamic
# edge values read, sit after this split point so callers can drop them
# (Plan.jit_args) or pad them to one edge count (graph_shard stacking)
N_TILE_FIELDS = 5
# num_edges deliberately NOT part of the static signature: raw edge counts
# are unbucketed and nothing in the compute path reads them — including
# them would defeat shape bucketing (one retrace per distinct edge count).
_SCHED_STATIC_FIELDS = ("gs", "gpt", "ont", "src_win", "num_nodes",
                        "padded_src_rows", "padded_out_rows")


def sched_arrays(s) -> tuple:
    """The schedule's array members as a pytree (missing members -> None)."""
    return tuple(getattr(s, f, None) for f in _SCHED_ARRAY_FIELDS)


def sched_statics(s) -> tuple:
    """The schedule's static ints as a hashable tuple."""
    return tuple(int(getattr(s, f)) for f in _SCHED_STATIC_FIELDS)


def sched_static(statics: tuple, field: str) -> int:
    """Read one field of a `sched_statics` tuple BY NAME — callers that
    hold only the tuple (host-side uniformization in the sharded sampled
    trainer) stay correct if `_SCHED_STATIC_FIELDS` is ever reordered."""
    return statics[_SCHED_STATIC_FIELDS.index(field)]


def sched_statics_for(*, gs: int, gpt: int, ont: int, src_win: int,
                      num_nodes: int) -> tuple:
    """A `sched_statics` tuple from bare knobs + a node count.

    For callers that OVERRIDE a schedule's node geometry (the sharded
    sampled trainer uniformizes per-layer node buckets across devices)
    without having a schedule object carrying the new count.  Keeping the
    constructor here pins the field order and the padded-rows math to
    `_SCHED_STATIC_FIELDS`' single point of truth.
    """
    return (gs, gpt, ont, src_win, num_nodes,
            -(-num_nodes // src_win) * src_win,     # padded_src_rows
            -(-num_nodes // ont) * ont)             # padded_out_rows


class SchedView:
    """Duck-typed DeviceSchedule rebuilt from (arrays, statics).

    Arrays may be jax tracers — this is how schedule tensors flow through a
    shared jitted function as arguments (serving's shared forwards, the
    sampled trainer's per-bucket step executables)."""

    def __init__(self, arrays: tuple, statics: tuple):
        for f, a in zip(_SCHED_ARRAY_FIELDS, arrays):
            setattr(self, f, a)
        for f, v in zip(_SCHED_STATIC_FIELDS, statics):
            setattr(self, f, v)
        self.num_tiles = int(self.nbrs.shape[0])


def _zero_cotangents(arrs: tuple):
    """Zero cotangents for a schedule-array pytree: float0 for integer
    arrays (jax's tangent type for int primals), real zeros for floats."""
    return jax.tree_util.tree_map(
        lambda x: (jnp.zeros_like(x)
                   if jnp.issubdtype(x.dtype, jnp.floating)
                   else np.zeros(x.shape, jax.dtypes.float0)),
        arrs)


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    r, c = x.shape
    return jnp.pad(x, ((0, rows - r), (0, cols - c)))


def _head_tile(dt: int, d: int, heads: int, dtype) -> int:
    """Dim-tile width for D columns that are ``heads`` equal head blocks:
    `dim_tile` of one head's width.  With several heads a tile never spans
    the whole array, so a head narrower than a lane tile is padded to one
    (a block's minor dim is a multiple of 128 lanes or the array's)."""
    dt_eff = dim_tile(dt, d // heads, dtype)
    if heads > 1 and dt_eff % LANES:
        dt_eff = LANES
    return dt_eff


def head_columns(dt: int, d: int, heads: int, dtype) -> int:
    """Columns each of ``heads`` equal head blocks of a D-wide operand
    takes in the kernel: one head's width rounded up to its dim tile."""
    tile = _head_tile(dt, d, heads, dtype)
    return -(-(d // heads) // tile) * tile


def _pad_heads(x: jax.Array, rows: int, heads: int,
               width: int) -> jax.Array:
    """(r, H*w) -> (rows, H*width): rows and each head block zero-padded.
    One head stays 2-D: an (r, 1, w) view makes XLA lay out the arrays
    around the kernel anew (copies the one-head step would pay for)."""
    if heads == 1:
        return _pad_to(x, rows, width)
    r, d = x.shape
    x = jnp.pad(x.reshape(r, heads, d // heads),
                ((0, rows - r), (0, 0), (0, width - d // heads)))
    return x.reshape(rows, heads * width)


def _unpad_heads(x: jax.Array, n: int, heads: int, w: int) -> jax.Array:
    """The inverse of `_pad_heads`: (rows, H*width) -> (n, H*w)."""
    if heads == 1:
        return x[:n, :w]
    return x[:n].reshape(n, heads, -1)[:, :, :w].reshape(n, heads * w)


def _layout_edge_values(sched: DeviceSchedule,
                         edge_values: jax.Array) -> jax.Array:
    """Lay per-edge values (original CSR order, (E, H)) out in schedule
    layout, (H, T, gpt, gs): a gather through ``slot_edge``, padded slots
    reading an appended 0.

    The layout keeps the edge values' own (float) dtype — under the bf16
    policy a bf16 edge-value tensor stays bf16 through the layout
    transform; the kernels up-cast to f32 at the accumulating matmul."""
    T, gpt, gs = sched.edge_val.shape
    heads = edge_values.shape[1]
    ev_dtype = (edge_values.dtype
                if jnp.issubdtype(edge_values.dtype, jnp.floating)
                else jnp.float32)
    ev = jnp.pad(edge_values.astype(ev_dtype).T, ((0, 0), (0, 1)))
    return jnp.take(ev, sched.slot_edge, axis=1).reshape(heads, T, gpt, gs)


def _aggregate_impl(feat: jax.Array, sched: DeviceSchedule, *,
                    dt: int, backend: Backend,
                    edge_values: Optional[jax.Array] = None,
                    out_dtype=None,
                    kernel_name: str = "group_aggregate_fwd") -> jax.Array:
    """Forward-only aggregation (no AD rule on the Pallas paths).

    ``edge_values`` is None (the schedule's static values) or (E, H): head
    h's values weight the h-th of H equal column blocks of ``feat``.
    Accumulates in f32; the result is cast to ``out_dtype`` (None =
    float32) as the final step — see the module docstring's dtype rules.
    ``kernel_name`` names the Pallas launches in the device trace: the
    backward pass over the transposed schedule passes
    ``group_aggregate_bwd``."""
    n, d = feat.shape
    heads = 1 if edge_values is None else edge_values.shape[1]
    assert d % heads == 0, (d, heads)
    out_dtype = jnp.float32 if out_dtype is None else out_dtype
    assert n == sched.num_nodes, (n, sched.num_nodes)
    if sched.num_tiles == 0:
        return jnp.zeros((n, d), out_dtype)
    if backend == "xla" and sched.edge_slot is not None:
        # E-sized, not slot-sized: padded slots can outnumber edges many
        # times over, and their (slots, D) gather outgrows HBM first
        src, dst, flat = _ref.schedule_edges(
            sched.nbrs, sched.local_node, sched.tile_node_block,
            sched.edge_slot, sched.edge_pos, sched.ont)
        vals = (sched.edge_val.reshape(-1)[flat] if edge_values is None
                else edge_values)
        out = _ref.segment_aggregate_ref(feat, src, dst, vals,
                                         sched.padded_out_rows)
        return out[:n].astype(out_dtype)
    if edge_values is not None:
        ev = _layout_edge_values(sched, edge_values)
    else:
        ev = sched.edge_val[None]
    if backend == "xla":
        # edge-less schedules (the tile-only jit args `Plan.jit_args` passes
        # for shape bucketing) have no edge list to sum over: gather every
        # slot instead, padded slots contributing 0
        feat_p = _pad_to(feat, sched.padded_src_rows, d)
        w = d // heads
        out = jnp.concatenate([_ref.group_aggregate_ref(
            feat_p[:, h * w:(h + 1) * w], sched.nbrs, ev[h],
            sched.local_node, sched.tile_node_block, sched.ont,
            sched.padded_out_rows) for h in range(heads)], axis=1)
        return out[:n].astype(out_dtype)
    dt_eff = _head_tile(dt, d, heads, feat.dtype)
    w = d // heads
    w_pad = -(-w // dt_eff) * dt_eff
    out = group_aggregate_pallas(
        _pad_heads(feat, sched.padded_src_rows, heads, w_pad),
        sched.nbrs, ev, sched.local_node,
        sched.tile_node_block, sched.tile_window,
        gs=sched.gs, gpt=sched.gpt, ont=sched.ont, src_win=sched.src_win,
        dt=dt_eff, out_rows=sched.padded_out_rows,
        interpret=(backend == "pallas_interpret"), name=kernel_name,
    )
    # node blocks no tile names (bipartite sampled blocks: edge-less rows
    # past num_dst) keep the zeros the kernel's accumulator starts from
    return _unpad_heads(out, n, heads, w).astype(out_dtype)


def _edge_cotangent(g_out: jax.Array, feat: jax.Array,
                    sched: DeviceSchedule, *, dt: int, backend: Backend,
                    heads: int) -> jax.Array:
    """Cotangent w.r.t. per-edge values (original CSR order, (E, H)): the
    per-edge gather-dot <g_out[dst], feat[src]> over each head's columns,
    via the forward schedule."""
    n, d = feat.shape
    w = d // heads
    T, gpt, gs = sched.edge_val.shape
    if backend == "xla":
        src, dst, _ = _ref.schedule_edges(
            sched.nbrs, sched.local_node, sched.tile_node_block,
            sched.edge_slot, sched.edge_pos, sched.ont)
        prod = (_pad_to(g_out, sched.padded_out_rows, d)[dst]
                .astype(jnp.float32) * feat[src].astype(jnp.float32))
        return prod.reshape(-1, heads, w).sum(axis=-1)
    dt_eff = _head_tile(dt, d, heads, feat.dtype)
    w_pad = -(-w // dt_eff) * dt_eff
    per_slot = group_edge_grad_pallas(
        _pad_heads(g_out, sched.padded_out_rows, heads, w_pad),
        _pad_heads(feat, sched.padded_src_rows, heads, w_pad),
        sched.nbrs, sched.local_node,
        sched.tile_node_block, sched.tile_window,
        gs=sched.gs, gpt=sched.gpt, ont=sched.ont,
        src_win=sched.src_win, dt=dt_eff, heads=heads,
        interpret=(backend == "pallas_interpret"))
    return per_slot.reshape(heads, T * gpt, gs)[
        :, sched.edge_slot, sched.edge_pos].T


# --- the differentiable wrapper: forward over the CSR schedule, backward
# --- over the transposed (CSC) schedule — "the transpose of aggregation is
# --- aggregation over the transposed graph".  Schedule ARRAYS are primal
# --- args (they may be tracers inside a shared jitted step); only the
# --- static ints + dispatch options ride in nondiff_argnums.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _aggregate_diff(statics, statics_bwd, opts, feat, edge_values, arrs,
                    arrs_bwd):
    dt, backend, out_dtype = opts
    return _aggregate_impl(feat, SchedView(arrs, statics), dt=dt,
                           backend=backend, edge_values=edge_values,
                           out_dtype=jnp.dtype(out_dtype))


def _aggregate_diff_fwd(statics, statics_bwd, opts, feat, edge_values, arrs,
                        arrs_bwd):
    dt, backend, out_dtype = opts
    out = _aggregate_impl(feat, SchedView(arrs, statics), dt=dt,
                          backend=backend, edge_values=edge_values,
                          out_dtype=jnp.dtype(out_dtype))
    return out, (feat, edge_values, arrs, arrs_bwd)


def _aggregate_diff_bwd(statics, statics_bwd, opts, res, g_out):
    feat, edge_values, arrs, arrs_bwd = res
    dt, backend, _ = opts
    sched = SchedView(arrs, statics)
    sched_bwd = SchedView(arrs_bwd, statics_bwd)
    # run the backward aggregation in the FORWARD feature dtype (bf16
    # cotangents move bf16 window bytes); accumulation stays f32 inside
    g_out = g_out.astype(feat.dtype)
    if edge_values is None:
        ev_bwd = None            # sched_bwd.edge_val holds the transposed vals
        ev_bar = None
    else:
        ev_bwd = edge_values[sched_bwd.edge_perm]
        ev_bar = _edge_cotangent(g_out, feat, sched, dt=dt, backend=backend,
                                 heads=edge_values.shape[1]
                                 ).astype(edge_values.dtype)
    feat_bar = _aggregate_impl(g_out, sched_bwd, dt=dt, backend=backend,
                               edge_values=ev_bwd,
                               kernel_name="group_aggregate_bwd")
    return (feat_bar.astype(feat.dtype), ev_bar,
            _zero_cotangents(arrs), _zero_cotangents(arrs_bwd))


_aggregate_diff.defvjp(_aggregate_diff_fwd, _aggregate_diff_bwd)


def aggregate(feat: jax.Array, sched: DeviceSchedule, *,
              dt: int = 128, backend: Optional[Backend] = None,
              edge_values: Optional[jax.Array] = None,
              sched_bwd: Optional[DeviceSchedule] = None,
              out_dtype=None) -> jax.Array:
    """out[v] = sum over v's neighbor groups of edge_val * feat[nbr].

    feat: (N, D) node features in the schedule's node order, any float
    dtype (accumulation is always float32).  Returns (num_nodes, D) in
    ``out_dtype`` (None = float32 — see the module docstring's dtype
    rules; the bf16 policy passes the feature dtype to keep activations
    16-bit between layers).

    backend: None resolves by platform (`resolve_backend`).

    edge_values: optional (E,) or (E, H) per-edge weights in ORIGINAL CSR
    edge order, overriding the schedule's static values — the
    dynamic-edge-value path attention needs (weights recomputed every
    forward).  With H heads, ``feat``'s D columns are H equal head blocks
    and head h's values weight the h-th; (E,) is the one-head case.  Their
    cotangent has their shape: per edge and head, a dot over that head's
    columns.

    sched_bwd: optional `DeviceSchedule` over the TRANSPOSED graph (same
    config), making the call differentiable w.r.t. ``feat`` and
    ``edge_values`` on every backend (see the module docstring).  Must carry
    ``edge_perm`` when ``edge_values`` is used.  `core.advisor.plan_for`
    builds the pair with ``with_backward=True``.
    """
    backend = resolve_backend(backend)
    if edge_values is not None and edge_values.ndim == 1:
        edge_values = edge_values[:, None]
    if sched_bwd is None:
        return _aggregate_impl(feat, sched, dt=dt, backend=backend,
                               edge_values=edge_values, out_dtype=out_dtype)
    if edge_values is not None and sched_bwd.edge_perm is None:
        raise ValueError(
            "dynamic edge_values need a backward schedule with edge_perm "
            "(build it via transpose_graph / plan_for(with_backward=True))")
    # out_dtype rides in nondiff opts as a canonical NAME (hashable)
    out_name = jnp.dtype(jnp.float32 if out_dtype is None else out_dtype).name
    return _aggregate_diff(sched_statics(sched), sched_statics(sched_bwd),
                           (dt, backend, out_name), feat,
                           edge_values,
                           sched_arrays(sched), sched_arrays(sched_bwd))
