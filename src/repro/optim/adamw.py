"""AdamW + learning-rate schedules + global-norm clipping.

Hand-rolled (no optax in the container) but matching optax semantics so the
update rule is unsurprising.  Optimizer state mirrors the parameter pytree, so
its sharding specs are the parameter specs — m/v shards follow FSDP/TP
automatically when passed through `jax.jit` in/out shardings.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

Pytree = Any

__all__ = ["AdamWConfig", "OptState", "adamw_init", "adamw_update",
           "bias_correction",
           "global_norm", "clip_by_global_norm", "cosine_schedule",
           "linear_warmup"]


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: Optional[float] = 1.0
    schedule: Optional[Callable[[jax.Array], jax.Array]] = None


class OptState(NamedTuple):
    step: jax.Array      # () int32
    m: Pytree            # first moment (f32)
    v: Pytree            # second moment (f32)


def adamw_init(params: Pytree) -> OptState:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return OptState(step=jnp.zeros((), jnp.int32), m=zeros,
                    v=jax.tree.map(jnp.copy, zeros))


def global_norm(tree: Pytree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def clip_by_global_norm(grads: Pytree, max_norm: float):
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), gn


def bias_correction(beta: float, step: jax.Array) -> jax.Array:
    """``1 - beta ** step`` in float32, to float32's precision: as
    ``-expm1(step * log(beta))`` with the log taken in double precision.
    Formed directly, the difference cancels for ``beta`` near 1 (at 0.999
    the first steps' corrections come out about 1e-5 to 1e-4 off, and
    every update with them)."""
    if beta == 0.0:
        return jnp.float32(1.0)
    return -jnp.expm1(step.astype(jnp.float32) * math.log(beta))


def adamw_update(cfg: AdamWConfig, grads: Pytree, state: OptState,
                 params: Pytree):
    """One AdamW step. Returns (new_params, new_state, metrics)."""
    metrics = {}
    g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    if cfg.grad_clip is not None:
        g32, gn = clip_by_global_norm(g32, cfg.grad_clip)
    else:
        gn = global_norm(g32)
    metrics["grad_norm"] = gn
    step = state.step + 1
    lr = cfg.lr * (cfg.schedule(step) if cfg.schedule is not None else 1.0)
    metrics["lr"] = lr
    c1 = bias_correction(cfg.b1, step)
    c2 = bias_correction(cfg.b2, step)

    def upd(p, g, m, v):
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * g * g
        mh, vh = m / c1, v / c2
        delta = mh / (jnp.sqrt(vh) + cfg.eps)
        # decoupled weight decay on matrices only (ndim >= 2)
        if p.ndim >= 2:
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(g32)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)
    new = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([t[0] for t in new])
    new_m = treedef.unflatten([t[1] for t in new])
    new_v = treedef.unflatten([t[2] for t in new])
    return new_p, OptState(step=step, m=new_m, v=new_v), metrics


def linear_warmup(warmup: int) -> Callable:
    def f(step):
        return jnp.minimum(step.astype(jnp.float32) / max(warmup, 1), 1.0)
    return f


def cosine_schedule(warmup: int, total: int, final_frac: float = 0.1) -> Callable:
    def f(step):
        s = step.astype(jnp.float32)
        warm = s / max(warmup, 1)
        prog = jnp.clip((s - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = final_frac + (1 - final_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return jnp.where(s < warmup, warm, cos)
    return f
