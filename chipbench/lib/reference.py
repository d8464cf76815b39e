"""The benchmark's reference: a plain float32 GNN, its loss, and AdamW.

It imports nothing of the program.  It works on the original CSR (no
renumbering, no tiles, no schedule): aggregation is a gather of the source
rows and a sorted `segment_sum` into the destination rows.  Dense matmuls
run at ``Precision.HIGHEST``.  Each architecture's graph, parameters and
forward live in its module, ``lib/arch/<arch>.py``, found by the model's
``arch``; this module holds what they share.

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

from .arch import load as load_arch

__all__ = ["Numerics", "Graph", "coo", "graph_arrays", "param_shapes",
           "init_params", "logits", "loss", "adamw_steps", "adamw_state"]

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
        """``out[v] = sum_e vals[e] * x[cols[e]]`` over v's row;
        differentiable in ``x`` and in ``g.vals`` (edge values computed from
        the parameters, as attention's are)."""
        prod = _prod3 if self.precision == "high" else _mul
        n = x.shape[0]

        @jax.custom_vjp
        def agg(x, vals):
            return jax.ops.segment_sum(
                _edge_msg(x[g.cols], vals, prod), g.rows, num_segments=n,
                indices_are_sorted=True)

        def fwd(x, vals):
            return agg(x, vals), (x, vals)

        def bwd(res, ct):
            x, vals = res
            # the transpose: each source gathers its destinations' cotangent
            dx = jax.ops.segment_sum(_edge_msg(ct[g.rows], vals, prod),
                                     g.cols, num_segments=n)
            if vals is None:
                return dx, None
            # each edge's value: its destination's cotangent . its source
            return dx, jnp.sum(prod(ct[g.rows], x[g.cols]), axis=1)

        agg.defvjp(fwd, bwd)
        return agg(x, g.vals)


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


def coo(indptr: np.ndarray, indices: np.ndarray):
    """The original CSR as destination ``rows`` (sorted) and source
    ``cols``, int32 on the host."""
    n = len(indptr) - 1
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
    return rows, np.asarray(indices, np.int32)


def graph_arrays(indptr: np.ndarray, indices: np.ndarray, arch: str) -> Graph:
    """Original CSR -> the aggregation graph of ``arch`` on the device."""
    return load_arch(arch).graph_arrays(indptr, indices)


def param_shapes(model: dict, in_dim: int, num_classes: int) -> dict:
    """Parameter names and shapes, as the model's architecture gives them."""
    return load_arch(model["arch"]).param_shapes(model, in_dim, num_classes)


def init_params(key, model: dict, in_dim: int, num_classes: int) -> dict:
    """Glorot-scaled normal weights, ``N(0, 1 / fan_in)``; call under jit."""
    shapes = param_shapes(model, in_dim, num_classes)
    keys = jax.random.split(key, len(shapes))
    return {name: jax.random.normal(k, s, jnp.float32) / np.sqrt(s[0])
            for k, (name, s) in zip(keys, sorted(shapes.items()))}


def logits(params, feat, g: Graph, model: dict, num: Numerics):
    return load_arch(model["arch"]).logits(params, feat, g, model, num)


def loss(params, feat, labels, g: Graph, model: dict, num: Numerics):
    """Class ids ``(N,)``: mean softmax cross-entropy over every node.
    Multi-label 0/1 targets ``(N, C)``: mean sigmoid binary cross-entropy
    over every node and class, ``log(1 + e^z) - y z``."""
    z = logits(params, feat, g, model, num)
    if labels.ndim == 2:
        return jnp.mean(jax.nn.softplus(z) - labels * z)
    logp = jax.nn.log_softmax(z, axis=-1)
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
