"""Pure-jnp oracles for every kernel in this package.

These are the semantic ground truth: small, obviously-correct, and used by
the test suite to validate each Pallas kernel across shape/dtype sweeps.
They are also the "DGL-analogue" XLA execution path used as a baseline in
benchmarks (gather + segment-sum is what a cuSPARSE-backed SpMM does).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "segment_aggregate_ref",
    "group_aggregate_ref",
    "schedule_edges",
    "edge_centric_aggregate_ref",
    "node_centric_aggregate_ref",
    "selective_scan_ref",
]


def selective_scan_ref(xc, dt_raw, b, c, a_log, dt_bias, d_skip):
    """Pure-jnp oracle for the fused selective-scan kernel: the literal
    per-token Mamba-1 recurrence h_t = exp(dt_t A) h_{t-1} + dt_t xc_t B_t,
    y_t = C_t·h_t + D xc_t.  Shapes as selective_scan_pallas."""
    Bb, S, di = xc.shape
    N = b.shape[-1]
    A = -jnp.exp(a_log.astype(jnp.float32))
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias[None, None, :])
    a = jnp.exp(dt[..., None] * A[None, None])                 # (B,S,di,N)
    bb = (dt * xc.astype(jnp.float32))[..., None] * b[:, :, None, :].astype(jnp.float32)

    def step(h, ab):
        ai, bi = ab
        h = ai * h + bi
        return h, h

    _, hs = jax.lax.scan(step, jnp.zeros((Bb, di, N), jnp.float32),
                         (a.transpose(1, 0, 2, 3), bb.transpose(1, 0, 2, 3)))
    hs = hs.transpose(1, 0, 2, 3)                              # (B,S,di,N)
    y = jnp.einsum("bsdn,bsn->bsd", hs, c.astype(jnp.float32))
    return y + d_skip[None, None, :] * xc.astype(jnp.float32)


def segment_aggregate_ref(feat: jax.Array, src: jax.Array, dst: jax.Array,
                          edge_val: jax.Array, num_nodes: int) -> jax.Array:
    """out[v] = sum_{e: dst_e = v} edge_val_e * feat[src_e]   (float32 accum).

    ``edge_val`` (E,) or (E, H): with H heads, feat's columns are H equal
    head blocks and head h's values weight head h's columns."""
    gathered = jnp.take(feat, src, axis=0).astype(jnp.float32)
    ev = edge_val.astype(jnp.float32)
    if ev.ndim == 1:
        gathered = gathered * ev[:, None]
    else:
        e, d = gathered.shape
        gathered = (gathered.reshape(e, ev.shape[1], -1)
                    * ev[:, :, None]).reshape(e, d)
    return jax.ops.segment_sum(gathered, dst, num_segments=num_nodes)


def group_aggregate_ref(feat: jax.Array, nbrs: jax.Array, edge_val: jax.Array,
                        local_node: jax.Array, tile_node_block: jax.Array,
                        ont: int, out_rows: int) -> jax.Array:
    """Oracle consuming the *group schedule* (same operands as the kernel).

    feat:            (N_src_pad, D)
    nbrs, edge_val:  (T, gpt, gs)
    local_node:      (T, gpt)
    tile_node_block: (T,)
    Returns (out_rows, D) float32.
    """
    T, gpt, gs = nbrs.shape
    gathered = jnp.take(feat, nbrs.reshape(-1), axis=0).astype(jnp.float32)
    gathered = gathered.reshape(T * gpt * gs, -1) * edge_val.reshape(-1, 1).astype(jnp.float32)
    per_group = gathered.reshape(T, gpt, gs, -1).sum(axis=2)          # (T, gpt, D)
    rows = tile_node_block[:, None] * ont + local_node                 # (T, gpt)
    return jax.ops.segment_sum(
        per_group.reshape(T * gpt, -1), rows.reshape(-1), num_segments=out_rows
    )


def schedule_edges(nbrs: jax.Array, local_node: jax.Array,
                   tile_node_block: jax.Array, edge_slot: jax.Array,
                   edge_pos: jax.Array, ont: int):
    """``(src, dst, flat_slot)`` of every real edge a group schedule holds,
    in original CSR edge order (``edge_slot``/``edge_pos`` name each edge's
    group and position): the E-sized view of the schedule, which skips
    the padded slots the (T, gpt, gs) arrays carry."""
    _, gpt, gs = nbrs.shape
    flat = edge_slot * gs + edge_pos
    src = nbrs.reshape(-1)[flat]
    dst = (tile_node_block[edge_slot // gpt] * ont
           + local_node.reshape(-1)[edge_slot])
    return src, dst, flat


def edge_centric_aggregate_ref(feat, src, dst, edge_val, num_nodes):
    """Edge-centric baseline (PyG torch-scatter analogue): one unit per edge.

    Semantically identical to segment_aggregate_ref; kept separate so the
    benchmark can lower it without the gather/scale fusion (scatter-add of
    pre-scaled messages materialized per edge — the §5.1 'edge-centric'
    strawman, Fig. 4c).
    """
    messages = feat[src] * edge_val[:, None]
    out = jnp.zeros((num_nodes, feat.shape[1]), jnp.float32)
    return out.at[dst].add(messages.astype(jnp.float32))


def node_centric_aggregate_ref(feat, indptr_padded_nbrs, indptr_mask, edge_val_padded,
                               num_nodes):
    """Node-centric baseline (Fig. 4b): one unit per node, padded to max degree.

    indptr_padded_nbrs: (N, max_deg) neighbor ids (padded 0)
    indptr_mask:        (N, max_deg) 1.0 valid / 0.0 pad
    edge_val_padded:    (N, max_deg)
    The padding to max degree is exactly the workload imbalance the paper's
    Fig. 2b illustrates — wasted lanes on low-degree nodes.
    """
    gathered = feat[indptr_padded_nbrs]                      # (N, max_deg, D)
    w = (indptr_mask * edge_val_padded)[..., None]
    return (gathered * w).sum(axis=1).astype(jnp.float32)
