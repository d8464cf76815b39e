"""Mixture-of-Experts with expert parallelism (EP) over the `model` axis.

Distribution scheme (the GNNAdvisor C1/C2 analogy is deliberate — see
DESIGN.md §5: token->expert dispatch is a sparse segment workload with
skewed "degrees", and we regularize it into fixed-capacity bins exactly the
way the group partitioner regularizes neighbor lists):

* Activations are replicated over `model` between blocks (Megatron
  convention), so every model rank computes routing identically and
  gathers ONLY its local experts' tokens from its local token shard —
  no all-to-all is needed; the combine is a single psum over `model`
  (same wire cost as a Megatron MLP).
* Expert weights are sharded (E over `model`, d over `data` ZeRO-style);
  inside the shard_map we explicitly all-gather the `data`-sharded dim —
  the manual FSDP unshard.
* Fixed per-rank capacity C = ceil(T_local * topk * cf / E): overflow
  tokens are dropped (counted in metrics) — the Switch/GShard contract.

The same code runs without a mesh (mesh=None) for 1-device smoke tests:
identical math, no collectives.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.nn.layers import Initializer

__all__ = ["MoEParams", "moe_init", "moe_apply"]


@dataclasses.dataclass(frozen=True)
class MoEParams:
    n_experts: int
    topk: int
    d_ff: int
    capacity_factor: float = 1.25
    router_norm_topk: bool = True   # renormalize selected probs to sum to 1


def moe_init(init: Initializer, d_model: int, mp: MoEParams):
    p, s = {}, {}
    p["router"], s["router"] = init.weight((d_model, mp.n_experts),
                                           ("embed", None), dtype=jnp.float32)
    p["wi"], s["wi"] = init.weight((mp.n_experts, d_model, 2, mp.d_ff),
                                   ("experts", "expert_mlp", None, "mlp"))
    p["wo"], s["wo"] = init.weight((mp.n_experts, mp.d_ff, d_model),
                                   ("experts", "mlp", "expert_mlp"))
    return p, s


def _route(router_w, x2d, mp: MoEParams):
    """x2d (T, d) -> (top_idx (T,k), top_w (T,k) f32, aux_loss, probs)."""
    logits = (x2d.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, mp.topk)
    if mp.router_norm_topk:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss ingredients
    T = x2d.shape[0]
    frac = jnp.zeros(mp.n_experts, jnp.float32).at[top_idx.reshape(-1)].add(1.0)
    frac = frac / (T * mp.topk)
    mean_prob = probs.mean(axis=0)
    return top_idx, top_w, (frac, mean_prob), probs


def _expert_ffn(wi, wo, buf, act=jax.nn.silu):
    """buf (E_loc, C, d) -> (E_loc, C, d)."""
    h = jnp.einsum("ecd,edgf->ecgf", buf, wi.astype(buf.dtype))
    gated = act(h[:, :, 0, :]) * h[:, :, 1, :]
    return jnp.einsum("ecf,efd->ecd", gated, wo.astype(buf.dtype))


def _moe_local(router_w, wi, wo, x, mp: MoEParams, *, e_offset, e_local,
               combine_scale=1.0):
    """Dispatch/FFN/combine for the experts [e_offset, e_offset+e_local).

    x (B, S, d). Returns (partial_out (B,S,d), (frac, mean_prob), dropped_frac)
    where aux_loss = E * sum(frac * mean_prob) is assembled by the caller (so
    the sharded path can average frac/mean_prob over shards first, making the
    loss exactly layout-invariant).
    """
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    top_idx, top_w, (frac, mean_prob), _ = _route(router_w, xf, mp)
    C = max(8, int(math.ceil(T * mp.topk * mp.capacity_factor / mp.n_experts)))

    flat_e = top_idx.reshape(-1)                     # (T*k,) global expert id
    le = flat_e - e_offset
    valid = (le >= 0) & (le < e_local)
    le_c = jnp.where(valid, le, 0)
    oh = jnp.where(valid[:, None],
                   jax.nn.one_hot(le_c, e_local, dtype=jnp.int32), 0)
    pos = jnp.cumsum(oh, axis=0) - 1                 # (T*k, E_loc)
    mypos = jnp.sum(jnp.where(oh > 0, pos, 0), axis=1)
    keep = valid & (mypos < C)

    # scatter one top-k slot at a time: peak transient is (T, d), not (T*k, d)
    buf = jnp.zeros((e_local, C, d), x.dtype)
    for s in range(mp.topk):                          # static small loop
        le_s, pos_s, keep_s = le_c[s::mp.topk], mypos[s::mp.topk], keep[s::mp.topk]
        buf = buf.at[jnp.where(keep_s, le_s, 0), jnp.where(keep_s, pos_s, 0)].add(
            jnp.where(keep_s[:, None], xf, 0).astype(x.dtype))
    y = _expert_ffn(wi, wo, buf)                     # (E_loc, C, d)

    out = jnp.zeros((T, d), jnp.float32)
    for s in range(mp.topk):                          # static small loop
        le_s, pos_s = le_c[s::mp.topk], mypos[s::mp.topk]
        keep_s, w_s = keep[s::mp.topk], top_w[:, s]
        contrib = y[le_s, pos_s].astype(jnp.float32)
        out = out + contrib * (w_s * keep_s)[:, None]
    dropped = 1.0 - keep.sum().astype(jnp.float32) / (valid.sum() + 1e-9)
    return ((out * combine_scale).reshape(B, S, d).astype(x.dtype),
            (frac, mean_prob), dropped)


def moe_apply(p, x: jax.Array, mp: MoEParams, *,
              mesh: Optional[jax.sharding.Mesh] = None,
              batch_axes=("pod", "data"), ep_axis: str = "model",
              fsdp_axis: Optional[str] = "data"):
    """MoE FFN. Returns (out (B,S,d), aux_loss, dropped_frac metric)."""
    if mesh is None or ep_axis not in mesh.axis_names:
        out, (frac, mean_prob), dropped = _moe_local(
            p["router"], p["wi"], p["wo"], x, mp,
            e_offset=0, e_local=mp.n_experts)
        aux = mp.n_experts * jnp.sum(frac * mean_prob)
        return out, aux, dropped

    tp = mesh.shape[ep_axis]
    assert mp.n_experts % tp == 0, (mp.n_experts, tp)
    e_local = mp.n_experts // tp
    baxes = tuple(a for a in batch_axes if a in mesh.axis_names)
    fsdp = fsdp_axis if (fsdp_axis in mesh.axis_names) else None

    x_spec = P(baxes if baxes else None, None, None)
    wi_spec = P(ep_axis, fsdp, None, None)
    wo_spec = P(ep_axis, None, fsdp)
    rw_spec = P(fsdp, None)

    all_axes = tuple(baxes) + (ep_axis,)
    n_reduce = 1
    for a in all_axes:
        n_reduce *= mesh.shape[a]

    def inner(router_w, wi, wo, xl):
        if fsdp is not None:
            router_w = jax.lax.all_gather(router_w, fsdp, axis=0, tiled=True)
            wi = jax.lax.all_gather(wi, fsdp, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, fsdp, axis=2, tiled=True)
        r = jax.lax.axis_index(ep_axis)
        out, (frac, mean_prob), dropped = _moe_local(
            router_w, wi, wo, xl, mp, e_offset=r * e_local, e_local=e_local)
        # combine in the activation dtype (bf16): halves the dominant psum
        # wire bytes vs f32 (§Perf iteration 6); each token's partials come
        # from ≤topk ranks so the bf16 accumulation depth is ≤8.
        out = jax.lax.psum(out.astype(xl.dtype), ep_axis)
        # Exact layout-invariant aux: average the routing statistics over all
        # shards (model ranks see identical stats, batch shards partition the
        # tokens), THEN form E * sum(frac * mean_prob).
        frac = jax.lax.psum(frac, all_axes) / n_reduce
        mean_prob = jax.lax.psum(mean_prob, all_axes) / n_reduce
        aux = mp.n_experts * jnp.sum(frac * mean_prob)
        dropped = jax.lax.psum(dropped, all_axes) / n_reduce
        return out, aux, dropped

    out, aux, dropped = shard_map(
        inner, mesh=mesh,
        in_specs=(rw_spec, wi_spec, wo_spec, x_spec),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(p["router"], p["wi"], p["wo"], x)
    return out, aux, dropped
