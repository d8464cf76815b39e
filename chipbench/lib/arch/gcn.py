"""GCN (Kipf & Welling, arXiv:1609.02907), as GNNAdvisor runs it.

``A_hat = D^-1/2 (A + I) D^-1/2`` with ``D`` the degrees of ``A + I``; each
layer is ``A_hat (X W)``, ReLU between layers (the projection before the
aggregation, as GNNAdvisor places it; the product is the same).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import counts, reference
from . import common_config


def program_config(model: dict, graph: dict, dtype: str) -> dict:
    return {**common_config(graph, dtype), "arch": "gcn",
            "hidden_dim": model["hidden_dim"],
            "num_layers": model["num_layers"]}


def _dims(model: dict, in_dim: int, num_classes: int) -> list:
    L, h = model["num_layers"], model["hidden_dim"]
    return [in_dim] + [h] * (L - 1) + [num_classes]


def param_shapes(model: dict, in_dim: int, num_classes: int) -> dict:
    """``w{i}``, one projection a layer."""
    dims = _dims(model, in_dim, num_classes)
    return {f"w{i}": (dims[i], dims[i + 1])
            for i in range(model["num_layers"])}


def graph_arrays(indptr: np.ndarray, indices: np.ndarray) -> reference.Graph:
    """A self-loop on every node and the symmetric normalisation."""
    n = len(indptr) - 1
    deg = np.diff(indptr)
    rows, cols = reference.coo(indptr, indices)
    loops = np.arange(n, dtype=np.int32)
    rows = np.concatenate([rows, loops])
    cols = np.concatenate([cols, loops])
    order = np.argsort(rows, kind="stable")
    rows, cols = rows[order], cols[order]
    inv_sqrt = 1.0 / np.sqrt((deg + 1).astype(np.float64))
    vals = (inv_sqrt[rows] * inv_sqrt[cols]).astype(np.float32)
    return reference.Graph(jnp.asarray(rows), jnp.asarray(cols),
                           jnp.asarray(vals))


def logits(params, feat, g: reference.Graph, model: dict,
           num: reference.Numerics):
    x = feat
    L = model["num_layers"]
    for i in range(L):
        x = num.aggregate(num.mm(x, params[f"w{i}"]), g)
        if i < L - 1:
            x = jax.nn.relu(x)
    return x


def train_agg_passes(model: dict, nodes: int, edges: int, in_dim: int,
                     num_classes: int) -> list:
    """``A + I`` (weighted) at each layer's projected width, forward; the
    backward over the same widths in reverse, the first layer's included
    (its input is ``X W0``)."""
    out_dims = _dims(model, in_dim, num_classes)[1:]
    e = edges + nodes
    fwd = [counts.AggPass(nodes, e, d, True) for d in out_dims]
    return fwd + fwd[::-1]


def dense_train_flops(model: dict, nodes: int, in_dim: int,
                      num_classes: int) -> int:
    """One matmul a layer; the first one's input needs no gradient."""
    dims = _dims(model, in_dim, num_classes)
    return counts.matmul_train_flops(
        nodes, [(dims[i], dims[i + 1], i > 0)
                for i in range(model["num_layers"])])
