"""GIN (Xu et al., arXiv:1810.00826), as GNNAdvisor runs it.

Each layer is ``MLP((1 + eps) x + sum of neighbours)`` with the two-layer
MLP ``relu(h W) Wb``.  As in the program, there is no ReLU or
normalisation between GIN layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import counts, reference
from . import common_config


def program_config(model: dict, graph: dict, dtype: str) -> dict:
    return {**common_config(graph, dtype), "arch": "gin",
            "hidden_dim": model["hidden_dim"],
            "num_layers": model["num_layers"], "gin_eps": model["gin_eps"]}


def _dims(model: dict, in_dim: int, num_classes: int) -> list:
    L, h = model["num_layers"], model["hidden_dim"]
    return [in_dim] + [h] * (L - 1) + [num_classes]


def param_shapes(model: dict, in_dim: int, num_classes: int) -> dict:
    """``w{i}`` and ``w{i}b``, the two matmuls of each layer's MLP."""
    h = model["hidden_dim"]
    dims = _dims(model, in_dim, num_classes)
    shapes = {}
    for i in range(model["num_layers"]):
        shapes[f"w{i}"] = (dims[i], h)
        shapes[f"w{i}b"] = (h, dims[i + 1])
    return shapes


def graph_arrays(indptr: np.ndarray, indices: np.ndarray) -> reference.Graph:
    """The plain adjacency, unweighted: the self term is a sum apart."""
    rows, cols = reference.coo(indptr, indices)
    return reference.Graph(jnp.asarray(rows), jnp.asarray(cols), None)


def logits(params, feat, g: reference.Graph, model: dict,
           num: reference.Numerics):
    x = feat
    for i in range(model["num_layers"]):
        h = (1.0 + model["gin_eps"]) * x + num.aggregate(x, g)
        x = num.mm(jax.nn.relu(num.mm(h, params[f"w{i}"])),
                   params[f"w{i}b"])
    return x


def train_agg_passes(model: dict, nodes: int, edges: int, in_dim: int,
                     num_classes: int) -> list:
    """``A`` (unweighted) at each layer's input width, forward; the
    backward for every layer but the first, whose input, the features,
    needs no gradient."""
    in_dims = _dims(model, in_dim, num_classes)[:-1]
    fwd = [counts.AggPass(nodes, edges, d, False) for d in in_dims]
    return fwd + fwd[:0:-1]


def dense_train_flops(model: dict, nodes: int, in_dim: int,
                      num_classes: int) -> int:
    """Two matmuls a layer; only the first layer's first one has an input
    that needs no gradient."""
    h = model["hidden_dim"]
    dims = _dims(model, in_dim, num_classes)
    mats = []
    for i in range(model["num_layers"]):
        mats.append((dims[i], h, i > 0))
        mats.append((h, dims[i + 1], True))
    return counts.matmul_train_flops(nodes, mats)
