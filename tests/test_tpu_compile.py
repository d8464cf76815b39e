"""The aggregation kernels compile for a TPU v5e chip.

The chip is described, not attached: `jax.experimental.topologies` hands
the TPU compiler a v5e:2x2 topology and each kernel is lowered and compiled
for its first device.  This catches what the Pallas interpreter cannot —
block shapes the chip's tiling refuses, in-kernel ops Mosaic cannot lower,
and working sets over the scoped VMEM limit — at no chip time.  Nothing
runs, so these tests say nothing about results or speed.
"""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.model import AggConfig, config_is_feasible, vmem_working_set
from repro.core.tuner import SEARCH_SPACE
from repro.kernels.group_aggregate import (group_aggregate_pallas,
                                           group_edge_grad_pallas)
from repro.kernels.ops import dim_tile

DTYPES = ("float32", "bfloat16")
T = 1024                       # tiles: the grid length, any size compiles


@pytest.fixture(scope="module")
def one_chip():
    # describe the topology only once a test of this file runs, never at
    # import: the TPU library is loaded by one process at a time, and every
    # test worker imports every test module
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but can never be read back without the chip: keep the cache off
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _padded(cfg: AggConfig, d: int, dtype: str) -> tuple[int, int]:
    dt = dim_tile(cfg.dt, d, jnp.dtype(dtype))
    return dt, -(-d // dt) * dt


def _compile_fwd(sharding, cfg: AggConfig, d: int, dtype: str) -> str:
    dt, d_pad = _padded(cfg, d, dtype)
    s = lambda shape, dt_: _shape(sharding, shape, dt_)
    args = (s((4 * cfg.src_win, d_pad), dtype),
            s((T, cfg.gpt, cfg.gs), "int32"), s((T, cfg.gpt, cfg.gs), "float32"),
            s((T, cfg.gpt), "int32"), s((T,), "int32"), s((T,), "int32"))
    fn = lambda *a: group_aggregate_pallas(
        *a, gs=cfg.gs, gpt=cfg.gpt, ont=cfg.ont, src_win=cfg.src_win, dt=dt,
        out_rows=T * cfg.ont)
    return jax.jit(fn).lower(*args).compile().as_text()


def _compile_edge_grad(sharding, cfg: AggConfig, d: int, dtype: str) -> str:
    dt, d_pad = _padded(cfg, d, dtype)
    s = lambda shape, dt_: _shape(sharding, shape, dt_)
    args = (s((T * cfg.ont, d_pad), dtype), s((4 * cfg.src_win, d_pad), dtype),
            s((T, cfg.gpt, cfg.gs), "int32"), s((T, cfg.gpt), "int32"),
            s((T,), "int32"), s((T,), "int32"))
    fn = lambda *a: group_edge_grad_pallas(
        *a, gs=cfg.gs, gpt=cfg.gpt, ont=cfg.ont, src_win=cfg.src_win, dt=dt)
    return jax.jit(fn).lower(*args).compile().as_text()


_BASE = AggConfig(gs=16, gpt=16, dt=128, src_win=512)
# GCN-blogcatalog's plan (at its widths 16 and 40), and the longest fold
# of slot positions at the tallest window, gs 64 over src_win 2048: the
# forward kernel holds no per-slot matrix, so it compiles where Eq. 4's
# model (which prices the edge-gradient kernel's per-slot matrices too)
# refuses the plan
_GCN = AggConfig(gs=4, gpt=32, dt=128, src_win=1024, ont=128)
_UNROLL = AggConfig(gs=64, gpt=8, dt=128, src_win=2048)

_AGG_CASES = (
    [pytest.param(_BASE, dtype, d, id=f"{dtype}-{d}")
     for dtype in DTYPES for d in (16, 128, 640)]
    + [pytest.param(_GCN, "float32", d, id=f"gcn_plan-{d}") for d in (16, 40)]
    + [pytest.param(_UNROLL, dtype, 16, id=f"gs64_unroll-{dtype}")
       for dtype in DTYPES])


@pytest.mark.parametrize("cfg,dtype,d", _AGG_CASES)
def test_aggregate_compiles(one_chip, cfg, dtype, d):
    assert "tpu_custom_call" in _compile_fwd(one_chip, cfg, d, dtype)


@pytest.mark.parametrize("d", [16, 128, 640])
@pytest.mark.parametrize("dtype", DTYPES)
def test_edge_grad_compiles(one_chip, dtype, d):
    assert "tpu_custom_call" in _compile_edge_grad(one_chip, _BASE, d, dtype)


@pytest.mark.parametrize("d", [16, 128])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kernel", ["aggregate", "edge_grad"])
def test_tallest_node_block_compiles(one_chip, kernel, dtype, d):
    """The tallest node block the tuner searches: a (128, dt) output block
    and a (128, gpt) scatter one-hot."""
    cfg = dataclasses.replace(_BASE, ont=max(SEARCH_SPACE["ont"]))
    compile_ = _compile_fwd if kernel == "aggregate" else _compile_edge_grad
    assert "tpu_custom_call" in compile_(one_chip, cfg, d, dtype)


# "largest" by the whole modelled working set, and by the per-slot
# (gpt*gs, src_win) matrices alone — the term the compiler counts several
# times over
_SIZE = {"working_set": vmem_working_set,
         "slot_matrix": lambda c: c.gpt * c.gs * c.src_win}


def _largest_feasible(dtype: str, measure: str) -> AggConfig:
    """The feasible config of the tuner's search space that is largest by
    ``measure``."""
    cands = [AggConfig(**dict(zip(SEARCH_SPACE, vals)), feat_dtype=dtype)
             for vals in itertools.product(*SEARCH_SPACE.values())]
    return max((c for c in cands if config_is_feasible(c)),
               key=lambda c: (_SIZE[measure](c), vmem_working_set(c)))


@pytest.mark.parametrize("measure", sorted(_SIZE))
@pytest.mark.parametrize("kernel", ["aggregate", "edge_grad"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_largest_feasible_config_compiles(one_chip, dtype, kernel, measure):
    """Eq. 4's VMEM model must not accept a config the compiler refuses
    (the scoped VMEM limit): compile at the largest ones it accepts, with
    the feature operand as wide as the config's dim tile."""
    cfg = _largest_feasible(dtype, measure)
    compile_ = _compile_fwd if kernel == "aggregate" else _compile_edge_grad
    assert "tpu_custom_call" in compile_(one_chip, cfg, cfg.dt, dtype)


def test_forward_and_backward_launches_are_named_by_direction(one_chip):
    """The forward pass and the backward pass over the transposed schedule
    compile to launches named ``group_aggregate_fwd`` and
    ``group_aggregate_bwd``: the device trace tells them apart by name."""
    from repro.core.advisor import plan_for
    from repro.graphs.csr import random_power_law
    from repro.kernels.ops import (SchedView, aggregate, sched_arrays,
                                   sched_statics)

    plan = plan_for(random_power_law(600, 6.0, seed=5), in_dim=16,
                    config=_BASE, with_backward=True)
    fwd, bwd = plan.sched(), plan.sched_bwd()
    st, st_bwd = sched_statics(fwd), sched_statics(bwd)

    def loss(feat, arrs, arrs_bwd):
        return aggregate(feat, SchedView(arrs, st), backend="pallas",
                         sched_bwd=SchedView(arrs_bwd, st_bwd)).sum()

    def abstract(tree):
        return jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype),
                            tree)

    # the value too, or the forward launch is dead code in a gradient
    text = jax.jit(jax.value_and_grad(loss)).lower(
        _shape(one_chip, (600, 16), "float32"),
        abstract(sched_arrays(fwd)), abstract(sched_arrays(bwd)),
    ).compile().as_text()
    assert "%group_aggregate_fwd" in text and "%group_aggregate_bwd" in text
    assert "%group_aggregate." not in text


# GAT-PPI's plan (the tuner's pick at width 1,024) and its head layouts:
# layers 1-2 (4 heads of 256), layer 3 (6 of 121, each padded to a lane
# tile) and the softmax normalisers (a column of ones a head)
_PPI = AggConfig(gs=4, gpt=32, dt=512, src_win=512, ont=128)


@pytest.mark.parametrize("heads,width", [(4, 256), (6, 121), (4, 1)],
                         ids=["hidden_heads", "output_heads", "normaliser"])
@pytest.mark.parametrize("kernel", ["aggregate", "edge_grad"])
def test_head_indexed_kernels_compile(one_chip, kernel, heads, width):
    """A value per edge and head: each dim tile weights its window with
    its head's values (forward), and each head's dim tiles accumulate the
    edge cotangent of that head (backward)."""
    from repro.kernels.ops import _head_tile

    cfg, dtype = _PPI, "float32"
    dt = _head_tile(cfg.dt, heads * width, heads, jnp.dtype(dtype))
    d_pad = heads * -(-width // dt) * dt
    s = lambda shape, dt_: _shape(one_chip, shape, dt_)
    meta = (s((T, cfg.gpt, cfg.gs), "int32"),)
    tail = (s((T, cfg.gpt), "int32"), s((T,), "int32"), s((T,), "int32"))
    if kernel == "aggregate":
        args = (s((4 * cfg.src_win, d_pad), dtype), *meta,
                s((heads, T, cfg.gpt, cfg.gs), "float32"), *tail)
        fn = lambda *a: group_aggregate_pallas(
            *a, gs=cfg.gs, gpt=cfg.gpt, ont=cfg.ont, src_win=cfg.src_win,
            dt=dt, out_rows=T * cfg.ont)
    else:
        args = (s((T * cfg.ont, d_pad), dtype),
                s((4 * cfg.src_win, d_pad), dtype), *meta, *tail)
        fn = lambda *a: group_edge_grad_pallas(
            *a, gs=cfg.gs, gpt=cfg.gpt, ont=cfg.ont, src_win=cfg.src_win,
            dt=dt, heads=heads)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
