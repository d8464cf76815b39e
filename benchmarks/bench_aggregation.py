"""Paper Fig. 4 / §8.2: group-based aggregation vs node-centric vs
edge-centric vs gather+segment-sum (the DGL-analogue XLA path).

Wall-clock is CPU (this container); the paper's GPU ordering is reproduced
by the relative speedups — group-based avoids both max-degree padding waste
(node-centric) and per-edge scatter overhead (edge-centric).  The TPU
projection for the same schedules comes from the white-box KernelModel and
is reported as the derived column.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, load_replica, measure_fn
from repro.core.extractor import extract_graph_props
from repro.core.model import AggConfig, KernelModel
from repro.core.partition import partition_graph, partition_stats
from repro.kernels import ref
from repro.kernels.ops import DeviceSchedule, aggregate

DATASETS = ["cora", "pubmed", "proteins_full", "artist", "com-amazon"]
DIM = 64

def run():
    import jax
    km = KernelModel()
    for name in DATASETS:
        g, spec, _ = load_replica(name, max_nodes=3000)
        rng = np.random.default_rng(0)
        feat = jnp.asarray(rng.standard_normal((g.num_nodes, DIM)),
                           jnp.float32)
        ev = jnp.ones(g.num_edges, jnp.float32)
        rows, cols = g.to_coo()
        rows_j, cols_j = jnp.asarray(rows), jnp.asarray(cols)

        seg = jax.jit(lambda f: ref.segment_aggregate_ref(
            f, cols_j, rows_j, ev, g.num_nodes))
        m_seg = measure_fn(seg, feat)
        t_seg = m_seg.p50

        edge = jax.jit(lambda f: ref.edge_centric_aggregate_ref(
            f, cols_j, rows_j, ev, g.num_nodes))
        m_edge = measure_fn(edge, feat)
        t_edge = m_edge.p50

        degs = g.degrees
        md = max(int(degs.max()), 1)
        nbrs = np.zeros((g.num_nodes, md), np.int32)
        mask = np.zeros((g.num_nodes, md), np.float32)
        for v in range(g.num_nodes):
            d = int(degs[v])
            nbrs[v, :d] = g.indices[g.indptr[v]:g.indptr[v + 1]]
            mask[v, :d] = 1.0
        nbrs_j, mask_j = jnp.asarray(nbrs), jnp.asarray(mask)
        node = jax.jit(lambda f: ref.node_centric_aggregate_ref(
            f, nbrs_j, mask_j, mask_j, g.num_nodes))
        m_node = measure_fn(node, feat)
        t_node = m_node.p50

        p = partition_graph(g, gs=16, gpt=16, ont=8, src_win=256)
        sched = DeviceSchedule(p)
        grp = jax.jit(lambda f: aggregate(f, sched, backend="xla"))
        m_grp = measure_fn(grp, feat)
        t_grp = m_grp.p50

        props = extract_graph_props(g, detect_communities=False)
        cfg = AggConfig(gs=16, gpt=16, ont=8, src_win=256)
        tpu = km.latency(props, DIM, cfg, tiles=p.num_tiles)
        stats = partition_stats(p)
        emit(f"agg/{name}/group", t_grp * 1e6,
             f"speedup_vs_edge={t_edge / t_grp:.2f}x "
             f"vs_node={t_node / t_grp:.2f}x vs_segsum={t_seg / t_grp:.2f}x "
             f"tpu_model_us={tpu * 1e6:.1f} occ={stats['slot_occupancy']:.2f}",
             stats=m_grp)
        emit(f"agg/{name}/segsum_dgl_analogue", t_seg * 1e6, "",
             stats=m_seg)
        emit(f"agg/{name}/edge_centric_pyg_analogue", t_edge * 1e6, "",
             stats=m_edge)
        emit(f"agg/{name}/node_centric", t_node * 1e6,
             f"max_deg_pad={md}", stats=m_node)

        # bf16 vs f32 on the SAME schedule: measured latency plus modeled
        # DMA bytes — the memory-bound term halves with bytes_feat=2
        import dataclasses
        cfg16 = dataclasses.replace(cfg, feat_dtype="bfloat16")
        feat16 = feat.astype(jnp.bfloat16)
        grp16 = jax.jit(lambda f: aggregate(f, sched, backend="xla",
                                            out_dtype=jnp.bfloat16))
        m_grp16 = measure_fn(grp16, feat16)
        t_grp16 = m_grp16.p50
        term32 = km.terms(props, DIM, cfg, tiles=p.num_tiles)
        term16 = km.terms(props, DIM, cfg16, tiles=p.num_tiles)
        tpu16 = term16["latency"]
        emit(f"agg/{name}/group_bf16", t_grp16 * 1e6,
             f"vs_f32={t_grp / t_grp16:.2f}x "
             f"model_bytes_f32={term32['bytes']:.0f} "
             f"model_bytes_bf16={term16['bytes']:.0f} "
             f"bytes_ratio={term16['bytes'] / term32['bytes']:.2f} "
             f"tpu_model_us_bf16={tpu16 * 1e6:.1f} "
             f"tpu_model_speedup={tpu / tpu16:.2f}x", stats=m_grp16)


if __name__ == "__main__":
    run()
