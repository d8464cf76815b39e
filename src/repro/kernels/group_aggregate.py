"""Group-based neighbor aggregation — the GNNAdvisor kernel, TPU-native.

One `pl.pallas_call` realizes the paper's §5 workload-management stack:

  C1 group partitioning   — operands come pre-grouped from `core.partition`
                            (fixed (gpt, gs) work tiles, window-homogeneous);
  C2 leader-node scheme   — consecutive tiles of one node block accumulate
                            into the same VMEM-resident output block and flush
                            to HBM exactly once (grid-revisit accumulation:
                            single writer, no atomics by construction);
  C3 block-based mapping  — `gpt` groups per grid step; the VMEM working set
                            (feature window + output block) is the shared-
                            memory analogue, sized by Eq. 4 re-derived for
                            16 MiB VMEM;
  C4 dimension sharing    — the `dt`-wide lane dimension of every block; the
                            paper's coalesced thread→dim mapping (Fig. 6b) is
                            lane order on TPU.

The gather is ``folded``: edge weights and the intra-group sum are folded
INTO the gather matrix (W[g, r] = Σ_s ev[g,s]·1[nbr=r]), so each grid step
runs one (gpt, src_win) @ (src_win, dt) MXU matmul against the VMEM-resident
feature window.  The fold itself runs on the VPU: one compare-select of a
(gpt, src_win) block per slot position s, summed over the gs positions, so
the MXU sees only the gather and the node scatter.  (The paper-faithful
one-hot-row-per-slot mapping and a dynamic-slice ``direct`` gather were
dropped: neither passes the Mosaic compiler — `docs/performance.md`.  A
(gpt, gpt*gs) group one-hot matmul over per-slot rows, which summed the
same exact values, took about 45% of a grid step's MXU weight pushes.)

Grid = (D/dt, T) with tiles innermost so output/feature block revisits are
consecutive.  Scalar-prefetched per-tile metadata (`tile_node_block`,
`tile_window`) drives the BlockSpec index maps — the kernel body never does
a dynamic HBM load.

Block layouts obey the TPU rule that a block's last two dims are multiples
of (8, 128) or span the whole array, and keep HBM lane-dense: a (T, gpt,
gs) operand would be padded to 128 lanes in its minor gs dim (32x at
gs=4).  So per-tile metadata runs as (T, gpt*gs) rows read in (8, gpt*gs)
blocks — 8 tiles per block — and ``local_node`` as (8, gpt) blocks of
(T, gpt), T padded to a multiple of 8.  Each grid step takes its tile's
row out of the block in-kernel (a transposed block gives the slots as a
column).  The aggregation kernel's rows are slot-major, (T, gs, gpt)
flattened, so slot position s of every group is one contiguous run of
gpt rows of that column; the edge-gradient kernel's stay group-major.

Edge values may carry a head axis (attention's): ``(H, T, gpt, gs)``, one
value per slot and head, laid out as (H, T, gs*gpt) rows.  The feature
columns are then H equal head blocks, each a whole number of dim tiles,
and dim tile j weights its window with head ``j // (J / H)``'s values, so
each grid step is the one-head kernel; H = 1 is the plain aggregation.

The scalar-prefetched (T,) metadata lives in SMEM (1 MiB on v5e), so a
schedule longer than `MAX_TILES_PER_CALL` tiles runs as several launches
over consecutive tile ranges.  Each launch accumulates into the previous
one's output (aliased in place): a node block whose tiles straddle two
launches starts its second run from the first one's partial sums.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["group_aggregate_pallas", "group_edge_grad_pallas"]

# tiles per metadata block: the sublane tile (see module docstring)
_ROWS = 8
# tiles per pallas_call: two (T,) int32 prefetch arrays must fit SMEM
# (a multiple of _ROWS, so every launch starts on a metadata block)
MAX_TILES_PER_CALL = 1 << 16
# in-kernel f32 matmuls that select or sum exact values (one-hot operands)
# must not round them to bf16 on the MXU
_EXACT = jax.lax.Precision.HIGHEST


def _launches(T: int) -> list:
    """(first tile, tile count) of each launch covering T tiles."""
    return [(s, min(MAX_TILES_PER_CALL, T - s))
            for s in range(0, T, MAX_TILES_PER_CALL)]


def _rows(x: jax.Array) -> jax.Array:
    """(T, ...) -> (T rounded up to 8, prod(...)); pad rows are never read."""
    x = x.reshape(x.shape[0], -1)
    return jnp.pad(x, ((0, -x.shape[0] % _ROWS), (0, 0)))


def _head_rows(x: jax.Array) -> jax.Array:
    """(H, T, ...) -> (H, T rounded up to 8, prod(...)): `_rows` per head."""
    x = x.reshape(x.shape[0], x.shape[1], -1)
    return jnp.pad(x, ((0, 0), (0, -x.shape[1] % _ROWS), (0, 0)))


def _tile_row(ref, t) -> jax.Array:
    """This tile's (1, n) row out of its (8, n) block."""
    return ref[pl.ds(jax.lax.rem(t, _ROWS), 1), :]


def _tile_column(ref, t) -> jax.Array:
    """This tile's row of an (8, n) block, as an (n, 1) column."""
    blk = ref[...].T                                        # (n, 8)
    lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    return jnp.sum(jnp.where(lane == jax.lax.rem(t, _ROWS), blk, 0),
                   axis=1, keepdims=True)


def _group_onehot(gpt: int, gs: int) -> jax.Array:
    """(gpt, gpt*gs) f32: 1 where slot k belongs to group g (k // gs == g)."""
    shape = (gpt, gpt * gs)
    k = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    g0 = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * gs
    return jnp.logical_and(k >= g0, k < g0 + gs).astype(jnp.float32)


def _node_scatter(lnode_ref, t, ont: int, gpt: int) -> jax.Array:
    """(ont, gpt) f32 one-hot: group g lands on row local_node[g] of the
    tile's node block."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (ont, gpt), 0)
    return (rows == _tile_row(lnode_ref, t)).astype(jnp.float32)


def _kernel(nb_ref, tw_ref,                       # scalar prefetch (SMEM)
            feat_ref, nbrs_ref, eval_ref, lnode_ref,  # VMEM inputs
            acc_ref,                               # prior sums (aliased)
            out_ref,                               # VMEM output block
            *, gs: int, gpt: int, ont: int, src_win: int):
    t = pl.program_id(1)

    # --- leader-node flush boundary: load the block's prior sums (zero
    # --- unless an earlier launch visited it) on first visit ---
    prev = nb_ref[jnp.maximum(t - 1, 0)]
    first_visit = jnp.logical_or(t == 0, nb_ref[t] != prev)

    @pl.when(first_visit)
    def _load():
        out_ref[...] = acc_ref[...]

    # slot-major columns: rows s*gpt .. (s+1)*gpt are slot s of every group
    local = _tile_column(nbrs_ref, t) - tw_ref[t] * src_win  # (gs*gpt, 1)
    evals = _tile_column(eval_ref, t).astype(jnp.float32)   # 0 => padding
    feat = feat_ref[...]                                     # (src_win, dt)

    # W[g, r] = sum_s evals[s, g] * 1[local[s, g] == r]: the intra-group
    # reduction folds into the gather matrix on the VPU, one compare-select
    # per slot position, so one (gpt, src_win) @ (src_win, dt) matmul
    # gathers and sums every group of the tile
    cols = jax.lax.broadcasted_iota(jnp.int32, (gpt, src_win), 1)
    slot = lambda s: jnp.where(local[s * gpt:(s + 1) * gpt] == cols,
                               evals[s * gpt:(s + 1) * gpt], 0.0)
    w = functools.reduce(jnp.add, map(slot, range(gs)))      # (gpt, W)
    # (bf16 windows multiply bf16 operands: exact products, f32 sums)
    per_group = jnp.dot(w.astype(feat.dtype), feat,
                        precision=_EXACT if feat.dtype == jnp.float32
                        else None,
                        preferred_element_type=jnp.float32)  # (gpt, dt)

    # --- inter-group scatter within the node block: one-hot matmul on MXU ---
    # padded groups carry all-zero evals => per_group row is 0: safe to land on row 0
    out_ref[...] += jnp.dot(_node_scatter(lnode_ref, t, ont, gpt), per_group,
                            precision=_EXACT,
                            preferred_element_type=jnp.float32)


def _edge_grad_kernel(nb_ref, tw_ref,                 # scalar prefetch (SMEM)
                      grad_ref, feat_ref, nbrs_ref, lnode_ref,  # VMEM inputs
                      prior_ref,                       # aliased, never read
                      out_ref,                         # (8, gpt*gs) block
                      *, gs: int, gpt: int, ont: int, src_win: int):
    del prior_ref
    t = pl.program_id(1)
    j = pl.program_id(2)
    row = pl.ds(jax.lax.rem(t, _ROWS), 1)

    # a head's dim tiles are innermost here (grid (H, T, J/H)), so every
    # j-step revisits the tile's output row of that head: zero on the
    # first, accumulate after.
    @pl.when(j == 0)
    def _zero():
        out_ref[row, :] = jnp.zeros((1, gpt * gs), jnp.float32)

    local = _tile_column(nbrs_ref, t) - tw_ref[t] * src_win  # (gpt*gs, 1)
    feat = feat_ref[...].astype(jnp.float32)                 # (src_win, dt)
    grad = grad_ref[...].astype(jnp.float32)                 # (ont, dt)

    # each group's output-row cotangent: scatter^T @ grad -> (gpt, dt)
    gsel = jax.lax.dot_general(_node_scatter(lnode_ref, t, ont, gpt), grad,
                               (((0,), (0,)), ((), ())), precision=_EXACT,
                               preferred_element_type=jnp.float32)
    # p[g, r] = <grad[v_g], feat[r]> for every window row r, spread to the
    # group's slots; each slot then keeps its own row: out[k] = p[g_k, local_k]
    p = jax.lax.dot_general(gsel, feat, (((1,), (1,)), ((), ())),
                            precision=_EXACT,
                            preferred_element_type=jnp.float32)      # (gpt, W)
    p_slot = jax.lax.dot_general(_group_onehot(gpt, gs), p,
                                 (((0,), (0,)), ((), ())), precision=_EXACT,
                                 preferred_element_type=jnp.float32)  # (K, W)
    cols = jax.lax.broadcasted_iota(jnp.int32, p_slot.shape, 1)
    val = jnp.sum(jnp.where(local == cols, p_slot, 0.0), axis=1,
                  keepdims=True)                                      # (K, 1)
    # padded slots produce values the caller never reads (only
    # (edge_slot, edge_pos) entries are gathered back out)
    out_ref[row, :] += jnp.broadcast_to(val, (gpt * gs, _ROWS)).T[0:1, :]


@functools.partial(
    jax.jit,
    static_argnames=("gs", "gpt", "ont", "src_win", "dt", "heads",
                     "interpret"),
)
def group_edge_grad_pallas(grad_padded: jax.Array, feat_padded: jax.Array,
                           nbrs: jax.Array, local_node: jax.Array,
                           tile_node_block: jax.Array, tile_window: jax.Array,
                           *, gs: int, gpt: int, ont: int, src_win: int,
                           dt: int, heads: int = 1,
                           interpret: bool = False) -> jax.Array:
    """Per-slot edge-value cotangent: the backward of aggregation w.r.t. the
    (H, T, gpt, gs) edge-value tensor.

    For slot (t, g, s) holding edge (v <- u) and head h:
    out[h, t, g, s] = <grad[v], feat[u]> over head h's columns — each
    group's output-row cotangent is selected from the VMEM-resident node
    block (one-hot matmul), dotted against the whole feature window (one
    MXU matmul), and each slot keeps its own window row (same schedule
    metadata, same scalar-prefetch-driven BlockSpecs as the forward kernel).

    grad_padded: (out_rows, D_pad) output cotangent, out_rows % ont == 0.
    feat_padded: (N_src_pad, D_pad), N_src_pad % src_win == 0; D_pad is
    ``heads`` equal head blocks, each a multiple of dt.
    Returns (H, T, gpt, gs) float32.  Padded slots hold garbage; callers
    gather only real (edge_slot, edge_pos) entries.
    """
    out_rows, d_pad = grad_padded.shape
    n_src, d_pad2 = feat_padded.shape
    assert d_pad == d_pad2 and d_pad % (dt * heads) == 0, (d_pad, d_pad2, dt)
    assert n_src % src_win == 0 and out_rows % ont == 0
    T = nbrs.shape[0]
    assert nbrs.shape == (T, gpt, gs) and local_node.shape == (T, gpt)
    jh = d_pad // dt // heads                    # dim tiles per head
    kernel = functools.partial(_edge_grad_kernel, gs=gs, gpt=gpt, ont=ont,
                               src_win=src_win)
    K = gpt * gs
    nbrs_rows, ln = _rows(nbrs), _rows(local_node)
    out = jnp.zeros((heads,) + nbrs_rows.shape, jnp.float32)
    for t0, nt in _launches(T):
        meta = lambda h, t, j, nb, tw, t0=t0: ((t0 + t) // _ROWS, 0)
        col = lambda h, j: h * jh + j
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(heads, nt, jh),
            in_specs=[
                pl.BlockSpec((ont, dt),
                             lambda h, t, j, nb, tw: (nb[t], col(h, j))),
                pl.BlockSpec((src_win, dt),
                             lambda h, t, j, nb, tw: (tw[t], col(h, j))),
                pl.BlockSpec((_ROWS, K), meta),
                pl.BlockSpec((_ROWS, gpt), meta),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (None, _ROWS, K),
                lambda h, t, j, nb, tw, t0=t0: (h, (t0 + t) // _ROWS, 0)),
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(out.shape, jnp.float32),
            input_output_aliases={6: 0},
            interpret=interpret,
            name="group_edge_grad",
        )(tile_node_block[t0:t0 + nt], tile_window[t0:t0 + nt],
          grad_padded, feat_padded, nbrs_rows, ln, out)
    return out[:, :T].reshape(heads, T, gpt, gs)


@functools.partial(
    jax.jit,
    static_argnames=("gs", "gpt", "ont", "src_win", "dt", "out_rows",
                     "interpret", "name"),
)
def group_aggregate_pallas(feat_padded: jax.Array,
                           nbrs: jax.Array, edge_val: jax.Array,
                           local_node: jax.Array,
                           tile_node_block: jax.Array, tile_window: jax.Array,
                           *, gs: int, gpt: int, ont: int, src_win: int,
                           dt: int, out_rows: int,
                           interpret: bool = False,
                           name: str = "group_aggregate") -> jax.Array:
    """Run the group-aggregation kernel (one `pl.pallas_call`).

    Arguments (T = number of tiles; all arrays device-resident)
    ---------
    feat_padded : (N_src_pad, D_pad) float — source features;
        N_src_pad % src_win == 0 and D_pad % dt == 0 (caller pads; see
        `repro.kernels.ops.aggregate` for the padding/unpadding wrapper).
        ``dt`` is a multiple of 128 unless it spans all of D_pad
        (`repro.kernels.ops.dim_tile`).
    nbrs : (T, gpt, gs) int32 — global source ids per slot.  Padded slots
        point at their tile's window base so local ids stay in range.
    edge_val : (T, gpt, gs) or (H, T, gpt, gs) float32 — per-edge weights,
        one per head; exactly 0 marks a padded slot.  With H heads the
        D_pad columns are H equal head blocks, each a multiple of ``dt``,
        and head h's values weight head h's columns.
    local_node : (T, gpt) int32 — target row within the output node block.
    tile_node_block / tile_window : (T,) int32 — scalar-prefetched per-tile
        output-block / feature-window indices driving the BlockSpec index
        maps.
    gs, gpt, ont, src_win, dt, out_rows : static ints; out_rows % ont == 0.
    interpret : run under the Pallas interpreter (CPU).
    name : the kernel's name, which its launches carry in HLO and the
        device trace (`repro.kernels.ops` names the forward and backward
        passes ``group_aggregate_fwd`` / ``group_aggregate_bwd``).

    Returns (out_rows, D_pad) float32: out[v] = Σ_slots ev · feat[nbr]
    (0 on node blocks no tile names).

    This entry point is forward-only; `repro.kernels.ops.aggregate` adds the
    custom VJP (backward = this kernel over the transposed schedule).

    Example (schedule from `core.partition.partition_graph`):

    >>> p = partition_graph(g, gs=8, gpt=16, ont=8, src_win=512)
    >>> out = group_aggregate_pallas(
    ...     feat_padded, jnp.asarray(p.nbrs), jnp.asarray(p.edge_val),
    ...     jnp.asarray(p.local_node), jnp.asarray(p.tile_node_block),
    ...     jnp.asarray(p.tile_window), gs=p.gs, gpt=p.gpt, ont=p.ont,
    ...     src_win=p.src_win, dt=128, out_rows=p.padded_out_rows)
    """
    n_src, d_pad = feat_padded.shape
    T = nbrs.shape[0]
    if edge_val.ndim == 3:
        edge_val = edge_val[None]
    heads = edge_val.shape[0]
    assert n_src % src_win == 0 and d_pad % (dt * heads) == 0, (
        n_src, d_pad, src_win, dt, heads)
    assert out_rows % ont == 0
    assert nbrs.shape == (T, gpt, gs) and edge_val.shape[1:] == (T, gpt, gs)
    assert local_node.shape == (T, gpt)
    J = d_pad // dt
    jh = J // heads                              # dim tiles per head
    kernel = functools.partial(_kernel, gs=gs, gpt=gpt, ont=ont,
                               src_win=src_win)
    K = gpt * gs
    # slot-major metadata rows (see `_kernel`)
    nbrs_rows = _rows(jnp.swapaxes(nbrs, 1, 2))
    ev_rows = _head_rows(jnp.swapaxes(edge_val, 2, 3))
    ln = _rows(local_node)
    out = jnp.zeros((out_rows, d_pad), jnp.float32)
    for t0, nt in _launches(T):
        meta = lambda j, t, nb, tw, t0=t0: ((t0 + t) // _ROWS, 0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(J, nt),
            in_specs=[
                pl.BlockSpec((src_win, dt), lambda j, t, nb, tw: (tw[t], j)),
                pl.BlockSpec((_ROWS, K), meta),
                pl.BlockSpec((None, _ROWS, K),
                             lambda j, t, nb, tw, t0=t0: (
                                 j // jh, (t0 + t) // _ROWS, 0)),
                pl.BlockSpec((_ROWS, gpt), meta),
                pl.BlockSpec((ont, dt), lambda j, t, nb, tw: (nb[t], j)),
            ],
            out_specs=pl.BlockSpec((ont, dt), lambda j, t, nb, tw: (nb[t], j)),
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((out_rows, d_pad), jnp.float32),
            input_output_aliases={6: 0},
            interpret=interpret,
            name=name,
        )(tile_node_block[t0:t0 + nt], tile_window[t0:t0 + nt],
          feat_padded, nbrs_rows, ev_rows, ln, out)
    return out
