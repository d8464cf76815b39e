"""Plain float32 GCN and GIN, their loss, and AdamW: the benchmark's
reference.

It imports nothing of the program.  It works on the original CSR (no
renumbering, no tiles, no schedule): aggregation is a gather of the source
rows and a sorted `segment_sum` into the destination rows.  Dense matmuls
run at ``Precision.HIGHEST``.

GCN (Kipf & Welling, arXiv:1609.02907): ``A_hat = D^-1/2 (A + I) D^-1/2``
with ``D`` the degrees of ``A + I``; each layer is ``A_hat (X W)``, ReLU
between layers (the projection before the aggregation, as GNNAdvisor
places it; the product is the same).

GIN (Xu et al., arXiv:1810.00826): each layer is
``MLP((1 + eps) x + sum of neighbours)`` with the two-layer MLP
``relu(h W) Wb``.  As in the program, there is no ReLU or normalisation
between GIN layers.

``Numerics("high")`` is the control: every product a three-pass bfloat16
product, ``a_hi b_hi + a_hi b_lo + a_lo b_hi``, as the MXU computes at
``Precision.HIGH``.  It is applied to the dense matmuls and to the edge
products of the aggregation, forward and backward, the way a kernel run at
that precision would compute them.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Numerics", "Graph", "graph_arrays", "init_params", "logits",
           "loss", "adamw_steps", "adamw_state"]

HIGHEST = jax.lax.Precision.HIGHEST


def _split(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _mm3(a, b):
    (ah, al), (bh, bl) = _split(a), _split(b)
    dot = partial(jnp.dot, preferred_element_type=jnp.float32)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


@jax.custom_vjp
def mm_high(a, b):
    return _mm3(a, b)


def _mm_high_fwd(a, b):
    return _mm3(a, b), (a, b)


def _mm_high_bwd(res, g):
    a, b = res
    return _mm3(g, b.T), _mm3(a.T, g)


mm_high.defvjp(_mm_high_fwd, _mm_high_bwd)


def _prod3(v, x):
    (vh, vl), (xh, xl) = _split(v), _split(x)
    f = jnp.float32
    return (vh.astype(f) * xh.astype(f) + vh.astype(f) * xl.astype(f)
            + vl.astype(f) * xh.astype(f))


def _mul(v, x):
    return v * x


@dataclasses.dataclass(frozen=True)
class Numerics:
    """How the reference multiplies: ``"highest"`` (float32) or ``"high"``
    (three bfloat16 passes, the control)."""

    precision: str = "highest"

    def __post_init__(self):
        if self.precision not in ("highest", "high"):
            raise ValueError(f"unknown precision {self.precision!r}")

    def mm(self, a, b):
        if self.precision == "high":
            return mm_high(a, b)
        return jnp.dot(a, b, precision=HIGHEST)

    def aggregate(self, x, g: "Graph"):
        """``out[v] = sum_e vals[e] * x[cols[e]]`` over v's row."""
        prod = _prod3 if self.precision == "high" else _mul
        n = x.shape[0]

        @jax.custom_vjp
        def agg(x):
            return jax.ops.segment_sum(
                _edge_msg(x[g.cols], g.vals, prod), g.rows, num_segments=n,
                indices_are_sorted=True)

        def fwd(x):
            return agg(x), None

        def bwd(_, ct):
            # the transpose: each source gathers its destinations' cotangent
            return (jax.ops.segment_sum(
                _edge_msg(ct[g.rows], g.vals, prod), g.cols,
                num_segments=n),)

        agg.defvjp(fwd, bwd)
        return agg(x)


def _edge_msg(msg, vals, prod):
    if vals is None:
        return msg if prod is _mul else prod(jnp.ones_like(msg), msg)
    return prod(vals[:, None], msg)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """Device COO of the aggregation graph: destination ``rows`` (sorted),
    source ``cols``, and per-edge ``vals`` (None: all ones)."""

    rows: jax.Array
    cols: jax.Array
    vals: jax.Array | None


def graph_arrays(indptr: np.ndarray, indices: np.ndarray, arch: str) -> Graph:
    """Original CSR -> the aggregation graph of ``arch`` on the device.
    GCN adds a self-loop on every node and the symmetric normalisation."""
    n = len(indptr) - 1
    deg = np.diff(indptr)
    rows = np.repeat(np.arange(n, dtype=np.int32), deg)
    cols = np.asarray(indices, np.int32)
    if arch == "gin":
        return Graph(jnp.asarray(rows), jnp.asarray(cols), None)
    if arch != "gcn":
        raise ValueError(f"no reference for arch {arch!r}")
    loops = np.arange(n, dtype=np.int32)
    rows = np.concatenate([rows, loops])
    cols = np.concatenate([cols, loops])
    order = np.argsort(rows, kind="stable")
    rows, cols = rows[order], cols[order]
    inv_sqrt = 1.0 / np.sqrt((deg + 1).astype(np.float64))
    vals = (inv_sqrt[rows] * inv_sqrt[cols]).astype(np.float32)
    return Graph(jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(vals))


def param_shapes(model: dict, in_dim: int, num_classes: int) -> dict:
    """Parameter names and shapes: ``w{i}`` (and GIN's ``w{i}b``)."""
    L, h = model["num_layers"], model["hidden_dim"]
    dims = [in_dim] + [h] * (L - 1) + [num_classes]
    shapes = {}
    for i in range(L):
        if model["arch"] == "gcn":
            shapes[f"w{i}"] = (dims[i], dims[i + 1])
        else:
            shapes[f"w{i}"] = (dims[i], h)
            shapes[f"w{i}b"] = (h, dims[i + 1])
    return shapes


def init_params(key, model: dict, in_dim: int, num_classes: int) -> dict:
    """Glorot-scaled normal weights, ``N(0, 1 / fan_in)``; call under jit."""
    shapes = param_shapes(model, in_dim, num_classes)
    keys = jax.random.split(key, len(shapes))
    return {name: jax.random.normal(k, s, jnp.float32) / np.sqrt(s[0])
            for k, (name, s) in zip(keys, sorted(shapes.items()))}


def logits(params, feat, g: Graph, model: dict, num: Numerics):
    x = feat
    L = model["num_layers"]
    for i in range(L):
        if model["arch"] == "gcn":
            x = num.aggregate(num.mm(x, params[f"w{i}"]), g)
            if i < L - 1:
                x = jax.nn.relu(x)
        else:
            h = (1.0 + model["gin_eps"]) * x + num.aggregate(x, g)
            x = num.mm(jax.nn.relu(num.mm(h, params[f"w{i}"])),
                       params[f"w{i}b"])
    return x


def loss(params, feat, labels, g: Graph, model: dict, num: Numerics):
    """Mean softmax cross-entropy over every node."""
    logp = jax.nn.log_softmax(logits(params, feat, g, model, num), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def adamw_state(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"step": 0, "m": zeros, "v": jax.tree.map(jnp.zeros_like, zeros)}


def _adamw(opt: dict, grads, state, params):
    """One decoupled-weight-decay Adam step with global-norm clipping
    (Loshchilov & Hutter, arXiv:1711.05101), decay on matrices only."""
    leaves = jax.tree_util.tree_leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
    if opt["grad_clip"] is not None:
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gn, 1e-9))
        grads = jax.tree.map(lambda x: x * scale, grads)
    step = state["step"] + 1
    b1, b2 = opt["b1"], opt["b2"]
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"],
                     grads)

    def upd(p, m, v):
        d = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        if p.ndim >= 2:
            d = d + opt["weight_decay"] * p
        return p - opt["lr"] * d

    new_p = jax.tree.map(upd, params, m, v)
    return new_p, {"step": step, "m": m, "v": v}


def adamw_steps(params, feat, labels, g: Graph, model: dict, opt: dict,
                num: Numerics, steps: int):
    """``steps`` full-graph AdamW steps from ``params``.

    Returns the loss at each step (taken before its update), the clipped
    gradient of the first step as the optimiser receives it, and the
    parameters after the last step.
    """
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, f, y, g: loss(p, f, y, g, model, num)))
    state = adamw_state(params)
    losses, first_grad = [], None
    for _ in range(steps):
        value, grads = grad_fn(params, feat, labels, g)
        params, state = _adamw(opt, grads, state, params)
        if first_grad is None:
            first_grad = jax.tree.map(lambda m: m / (1 - opt["b1"]),
                                      state["m"])
        losses.append(float(value))
    return losses, jax.device_get(first_grad), jax.device_get(params)
