"""GAT (Velickovic et al., arXiv:1710.10903), its inductive PPI model.

Layer ``i`` has ``heads[i]`` attention heads of ``head_dims[i]`` features.
Each head projects ``z = x W`` (the heads side by side in ``w{i}``),
scores every edge of ``A + I`` as ``e_vu = LeakyReLU(a_d . z_v + a_s . z_u)``
(``a{i}d`` and ``a{i}s`` hold one column per head), and takes the softmax
of the scores over each destination's edges, shifted by that
destination's own maximum (a constant of the softmax: no gradient), as
the weights of its sum of ``z_u``.  Heads are concatenated, then ELU, on
every layer but the last, whose heads are averaged.
An identity skip adds a layer's input to its output (after the ELU) on
each layer in ``skip_layers`` (0-based).

Departures from the paper, which the configuration's ``assumed`` repeats:
no bias; the identity skip only across the listed layers, as the paper's
text has it for PPI's middle layer (the authors' code adds residuals on
every layer); the whole graph in one step with every node labelled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import counts, reference
from . import common_config


def program_config(model: dict, graph: dict, dtype: str) -> dict:
    return {**common_config(graph, dtype), "arch": "gat",
            "num_layers": model["num_layers"],
            "heads": tuple(model["heads"]),
            "head_dims": tuple(model["head_dims"]),
            "skip_layers": tuple(model["skip_layers"]),
            "gat_slope": model["gat_slope"]}


def _in_dims(model: dict, in_dim: int) -> list:
    """Columns into each layer: the features, then the previous layer's
    heads side by side."""
    return [in_dim] + [h * d for h, d in zip(model["heads"][:-1],
                                             model["head_dims"][:-1])]


def param_shapes(model: dict, in_dim: int, num_classes: int) -> dict:
    """``w{i}`` (columns in, heads x head width); ``a{i}s``, ``a{i}d``
    (head width, heads), so each weight's fan-in is its first axis."""
    shapes = {}
    for i, k in enumerate(_in_dims(model, in_dim)):
        h, d = model["heads"][i], model["head_dims"][i]
        shapes[f"w{i}"] = (k, h * d)
        shapes[f"a{i}s"] = (d, h)
        shapes[f"a{i}d"] = (d, h)
    return shapes


def graph_arrays(indptr: np.ndarray, indices: np.ndarray) -> reference.Graph:
    """``A + I``, unweighted: attention makes the values."""
    n = len(indptr) - 1
    rows, cols = reference.coo(indptr, indices)
    loops = np.arange(n, dtype=np.int32)
    rows = np.concatenate([rows, loops])
    cols = np.concatenate([cols, loops])
    order = np.argsort(rows, kind="stable")
    return reference.Graph(jnp.asarray(rows[order]),
                           jnp.asarray(cols[order]), None)


def _head(z, a_d, a_s, g: reference.Graph, slope: float,
          num: reference.Numerics):
    """One head: ``z`` (N, F), ``a_d`` and ``a_s`` (F,) -> (N, F)."""
    n = z.shape[0]
    s_d = num.mm(z, a_d[:, None])[:, 0]
    s_s = num.mm(z, a_s[:, None])[:, 0]
    e = jax.nn.leaky_relu(s_d[g.rows] + s_s[g.cols], negative_slope=slope)
    e_max = jax.lax.stop_gradient(jax.ops.segment_max(
        e, g.rows, num_segments=n, indices_are_sorted=True))
    p = jnp.exp(e - e_max[g.rows])
    den = jax.ops.segment_sum(p, g.rows, num_segments=n,
                              indices_are_sorted=True)
    out = num.aggregate(z, reference.Graph(g.rows, g.cols, p))
    return out / den[:, None]


def logits(params, feat, g: reference.Graph, model: dict,
           num: reference.Numerics):
    x = feat
    L = model["num_layers"]
    for i in range(L):
        H, F = model["heads"][i], model["head_dims"][i]
        n = x.shape[0]
        z = num.mm(x, params[f"w{i}"]).reshape(n, H, F)
        # each head rematerialised in the backward pass: otherwise a
        # head's (E, F) edge messages, the reference's largest arrays, are
        # kept from the forward pass for every head at once
        head = jax.checkpoint(lambda zh, a_d, a_s: _head(
            zh, a_d, a_s, g, model["gat_slope"], num))
        outs = jnp.stack([head(z[:, h], params[f"a{i}d"][:, h],
                               params[f"a{i}s"][:, h])
                          for h in range(H)])                 # (H, N, F)
        if i == L - 1:
            y = jnp.mean(outs, axis=0)
        else:
            y = outs.transpose(1, 0, 2).reshape(n, H * F)
        if i < L - 1:
            y = jax.nn.elu(y)
        if i in model["skip_layers"]:
            y = y + x
        x = y
    return x


def _layer_passes(model: dict, nodes: int, edges: int) -> list:
    """Per layer, ``(numerator, normaliser)`` over ``A + I``: the heads'
    projections, and a column of ones per head (each head's softmax
    sum), each with a value per edge and head."""
    e = edges + nodes
    return [(counts.AggPass(nodes, e, h * d, True, h),
             counts.AggPass(nodes, e, h, True, h))
            for h, d in zip(model["heads"], model["head_dims"])]


def train_edge_grad_passes(model: dict, nodes: int, edges: int,
                           in_dim: int, num_classes: int) -> list:
    """The edge-value cotangents of a step: per layer, each edge and
    head's dot of its destination's output cotangent with its source's
    row, over the numerator's and over the normaliser's columns."""
    return [p for pair in _layer_passes(model, nodes, edges) for p in pair]


def train_agg_passes(model: dict, nodes: int, edges: int, in_dim: int,
                     num_classes: int) -> list:
    """Forward, the numerator and the normaliser at every layer; backward,
    the numerator's cotangent over the transposed graph at every layer,
    the first included (its input is ``X W0``), and the edge-value
    cotangents (`train_edge_grad_passes`).  The normaliser's input, a
    constant, needs no transposed pass."""
    layers = _layer_passes(model, nodes, edges)
    fwd = [p for pair in layers for p in pair]
    transposed = [num for num, _ in layers[::-1]]
    return fwd + transposed + train_edge_grad_passes(
        model, nodes, edges, in_dim, num_classes)


def dense_train_flops(model: dict, nodes: int, in_dim: int,
                      num_classes: int) -> int:
    """Per layer the projection (the first one's input needs no gradient)
    and the two score products of each head, ``z_h a``."""
    mats = []
    for i, k in enumerate(_in_dims(model, in_dim)):
        h, d = model["heads"][i], model["head_dims"][i]
        mats.append((k, h * d, i > 0))
        mats += [(d, 1, True)] * (2 * h)
    return counts.matmul_train_flops(nodes, mats)
