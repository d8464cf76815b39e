"""Operations and bytes that the work needs, counted from its shapes.

These counts are the yardstick's and do not depend on how the program
implements the work: aggregation is counted on the real edges and nodes
(the edge list read once, the features read once, the output written
once), never on padded slots or tiles.  Which aggregations and matmuls a
step runs is the architecture's to say (``lib/arch/<arch>.py``).
"""
from __future__ import annotations

import dataclasses

from .arch import load as load_arch

__all__ = ["AggPass", "train_agg_passes", "agg_flops", "agg_bytes",
           "agg_least_s", "matmul_train_flops", "dense_train_flops",
           "train_step_flops"]

F32 = 4          # bytes of a float32 feature or edge value
IDX = 4          # bytes of an int32 node id


@dataclasses.dataclass(frozen=True)
class AggPass:
    """One aggregation over ``edges`` (real, self-loops included) of a
    ``(nodes, dim)`` feature matrix; ``weighted`` when each edge carries a
    value (GCN's normalisation), ``heads`` values an edge when each head
    has its own (attention's; ``dim`` then spans all heads)."""

    nodes: int
    edges: int
    dim: int
    weighted: bool
    heads: int = 1


def agg_flops(p: AggPass) -> int:
    """One multiply and one add per edge and feature."""
    return 2 * p.edges * p.dim


def agg_bytes(p: AggPass) -> int:
    """Row pointers and source ids (and each head's values) once, the
    input features once, the output once."""
    edge_bytes = p.edges * (IDX + (F32 * p.heads if p.weighted else 0))
    return ((p.nodes + 1) * IDX + edge_bytes
            + 2 * p.nodes * p.dim * F32)


def agg_least_s(p: AggPass, peak_flops: float, peak_bw: float) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(agg_flops(p) / peak_flops, agg_bytes(p) / peak_bw)


def train_agg_passes(model: dict, nodes: int, edges: int, in_dim: int,
                     num_classes: int) -> list[AggPass]:
    """The aggregations of one full-graph training step, forward then
    backward, as the model's architecture gives them.

    ``edges`` are the graph's edges without self-loops.  The backward
    aggregates the output cotangent over the transposed graph (same edge
    count) for every layer whose input needs a gradient; the input
    features need none.
    """
    return load_arch(model["arch"]).train_agg_passes(
        model, nodes, edges, in_dim, num_classes)


def matmul_train_flops(nodes: int, mats) -> int:
    """FLOPs of dense matmuls ``(k, n, input needs a gradient)`` over
    ``nodes`` rows in one training step: the forward, the weight gradient
    of each, and the input gradient of each whose input needs one."""
    total = 0
    for k, n, needs_dx in mats:
        mm = 2 * nodes * k * n
        total += mm * (3 if needs_dx else 2)
    return total


def dense_train_flops(model: dict, nodes: int, in_dim: int,
                      num_classes: int) -> int:
    """Dense matmul FLOPs of one training step, as the model's
    architecture gives them (`matmul_train_flops` of its matmuls)."""
    return load_arch(model["arch"]).dense_train_flops(
        model, nodes, in_dim, num_classes)


def train_step_flops(model: dict, nodes: int, edges: int, in_dim: int,
                     num_classes: int) -> int:
    """Model FLOPs of one full-graph training step: dense matmuls and
    aggregation multiply-adds on the real edges, forward and backward."""
    agg = sum(agg_flops(p) for p in train_agg_passes(
        model, nodes, edges, in_dim, num_classes))
    return agg + dense_train_flops(model, nodes, in_dim, num_classes)
