"""Operations and bytes that the work needs, counted from its shapes.

These counts are the yardstick's and do not depend on how the program
implements the work: aggregation is counted on the real edges and nodes
(the edge list read once, the features read once, the output written
once), never on padded slots or tiles.
"""
from __future__ import annotations

import dataclasses

__all__ = ["AggPass", "train_agg_passes", "agg_flops", "agg_bytes",
           "agg_least_s", "dense_train_flops", "train_step_flops"]

F32 = 4          # bytes of a float32 feature or edge value
IDX = 4          # bytes of an int32 node id


@dataclasses.dataclass(frozen=True)
class AggPass:
    """One aggregation over ``edges`` (real, self-loops included) of a
    ``(nodes, dim)`` feature matrix; ``weighted`` when each edge carries a
    value (GCN's normalisation)."""

    nodes: int
    edges: int
    dim: int
    weighted: bool


def agg_flops(p: AggPass) -> int:
    """One multiply and one add per edge and feature."""
    return 2 * p.edges * p.dim


def agg_bytes(p: AggPass) -> int:
    """Row pointers and source ids (and values) once, the input features
    once, the output once."""
    edge_bytes = p.edges * (IDX + (F32 if p.weighted else 0))
    return ((p.nodes + 1) * IDX + edge_bytes
            + 2 * p.nodes * p.dim * F32)


def agg_least_s(p: AggPass, peak_flops: float, peak_bw: float) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(agg_flops(p) / peak_flops, agg_bytes(p) / peak_bw)


def train_agg_passes(model: dict, nodes: int, edges: int, in_dim: int,
                     num_classes: int) -> list[AggPass]:
    """The aggregations of one full-graph training step, forward then
    backward.

    ``edges`` are the graph's edges without self-loops; GCN aggregates
    ``A + I`` (weighted), GIN the plain ``A`` (its self term is an
    elementwise sum).  GCN aggregates the projected width (``X W``), GIN
    the layer's input width.  The backward aggregates the output cotangent
    over the transposed graph (same edge count) for every layer whose input
    needs a gradient; the input features need none, so GIN's first layer
    has no backward aggregation, while GCN's first aggregation does (its
    input is ``X W0``).
    """
    L, h = model["num_layers"], model["hidden_dim"]
    if model["arch"] == "gcn":
        out_dims = [h] * (L - 1) + [num_classes]
        e = edges + nodes
        fwd = [AggPass(nodes, e, d, True) for d in out_dims]
        return fwd + fwd[::-1]
    if model["arch"] == "gin":
        in_dims = [in_dim] + [h] * (L - 1)
        fwd = [AggPass(nodes, edges, d, False) for d in in_dims]
        return fwd + fwd[:0:-1]
    raise ValueError(f"no counts for arch {model['arch']!r}")


def dense_train_flops(model: dict, nodes: int, in_dim: int,
                      num_classes: int) -> int:
    """Dense matmul FLOPs of one training step: the forward, plus the
    weight gradient of every matmul and the input gradient of every matmul
    whose input needs one (not the input features')."""
    L, h = model["num_layers"], model["hidden_dim"]
    dims = [in_dim] + [h] * (L - 1) + [num_classes]
    mats = []                       # (k, n, input needs a gradient)
    for i in range(L):
        if model["arch"] == "gcn":
            mats.append((dims[i], dims[i + 1], i > 0))
        else:
            mats.append((dims[i], h, i > 0))
            mats.append((h, dims[i + 1], True))
    total = 0
    for k, n, needs_dx in mats:
        mm = 2 * nodes * k * n
        total += mm * (3 if needs_dx else 2)
    return total


def train_step_flops(model: dict, nodes: int, edges: int, in_dim: int,
                     num_classes: int) -> int:
    """Model FLOPs of one full-graph training step: dense matmuls and
    aggregation multiply-adds on the real edges, forward and backward."""
    agg = sum(agg_flops(p) for p in train_agg_passes(
        model, nodes, edges, in_dim, num_classes))
    return agg + dense_train_flops(model, nodes, in_dim, num_classes)
