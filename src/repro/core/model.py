"""Modeling (paper §7.1) — analytical performance model, TPU-adapted.

Two model layers:

  1. `paper_eq2_latency` — the literal Eq. 2 latency surrogate from the
     paper, with its hyper-parameters mapped onto our TPU knobs
     (gs→gs, tpb→gpt, dw→dt).  Kept for fidelity: the tuner can run on it,
     and `benchmarks/bench_model_fit.py` compares its ranking quality
     against the refined model below.

  2. `KernelModel` — a white-box three-term model of the actual Pallas
     schedule: exact tile counts are predicted from input-level statistics
     (degree distribution + numbering locality), then converted to
     compute / memory / overhead seconds with TPU constants.  This is the
     paper's Eq. 2-4 *re-derived* for the TPU memory hierarchy:
       Eq. 3 (single-thread capability)  -> VPU/VREG work per group bound
       Eq. 4 (shared-memory capacity)    -> VMEM working-set bound.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.core.extractor import GraphProps
from repro.hw import LANES, SUBLANES, TPUSpec, device_spec
from repro.kernels.ops import dim_tile

__all__ = ["AggConfig", "paper_eq2_latency", "KernelModel", "vmem_working_set",
           "config_is_feasible", "config_infeasibility",
           "feat_dtype_align", "feat_dtype_bytes"]

# The end-to-end dtype policy's vocabulary.  ``feat_dtype`` names the dtype
# of node features and activations flowing through the aggregation kernel;
# accumulation is ALWAYS float32 (the kernels use preferred_element_type)
# and parameters stay float32 — only the bandwidth-carrying tensors change.
# Bytes per element feed Eq. 4 (VMEM working set) and the memory term of
# `KernelModel`; the alignment unit is the vreg second-minor tile for the
# dtype (8 rows f32, 16 rows for 16-bit types), which `dim_tile`
# (kernels.ops) and the dt feasibility check below both honor.
_FEAT_DTYPES = {"float32": (4, 8), "bfloat16": (2, 16), "float16": (2, 16)}


def feat_dtype_bytes(feat_dtype: str) -> int:
    """Bytes per feature element for a policy dtype name."""
    try:
        return _FEAT_DTYPES[feat_dtype][0]
    except KeyError:
        raise ValueError(
            f"unknown feat_dtype {feat_dtype!r}; one of {sorted(_FEAT_DTYPES)}"
        ) from None


def feat_dtype_align(feat_dtype: str) -> int:
    """Lane-tile alignment unit (rows of the second-minor dim) for a policy
    dtype name — dim tiles must be a multiple of this."""
    try:
        return _FEAT_DTYPES[feat_dtype][1]
    except KeyError:
        raise ValueError(
            f"unknown feat_dtype {feat_dtype!r}; one of {sorted(_FEAT_DTYPES)}"
        ) from None


@dataclasses.dataclass(frozen=True)
class AggConfig:
    """The tunable hyper-parameters (paper: gs, tpb, dw; +TPU window and
    node-block height).  The tuner searches every field but ``feat_dtype``
    (`core.tuner.SEARCH_SPACE`); the defaults are what a pinned config
    leaves unset."""

    gs: int = 16          # group size (paper gs)
    gpt: int = 16         # groups per tile (paper tpb analogue)
    dt: int = 128         # dim-tile width (paper dw analogue)
    src_win: int = 512    # feature-window rows (TPU shared-memory analogue)
    ont: int = 8          # output rows per node block (paper's leader block)
    feat_dtype: str = "float32"   # feature/activation dtype policy

    def astuple(self):
        return (self.gs, self.gpt, self.dt, self.src_win, self.ont)

    @property
    def bytes_feat(self) -> int:
        return feat_dtype_bytes(self.feat_dtype)


# ---------------------------------------------------------------------------
# 1. Paper Eq. 2, faithfully.
# ---------------------------------------------------------------------------

def paper_eq2_latency(props: GraphProps, dim: int, cfg: AggConfig,
                      *, max_tpb: int = 1024) -> float:
    """Eq. 2 of the paper (surrogate units, lower = better).

    Latency = E*D / (gs * |dw - D/3| * |tpb - sqrt(max_tpb)|)
              * (1 + |gs - alpha*N/E|)

    N/E in the paper's formula is deg^-1; the alpha*N/E pivot expresses
    "gs should approach alpha * avg_degree^{-1} scaled" — we keep the exact
    published form (including its quirks) and only guard the poles.
    """
    n, e, d = props.num_nodes, props.num_edges, float(dim)
    gs, tpb, dw = float(cfg.gs), float(cfg.gpt), float(cfg.dt)
    denom = gs * max(abs(dw - d / 3.0), 0.5) * max(abs(tpb - math.sqrt(max_tpb)), 0.5)
    pivot = props.alpha * (n / max(e, 1))
    return (e * d) / denom * (1.0 + abs(gs - pivot))


# ---------------------------------------------------------------------------
# 2. Refined white-box model of the Pallas schedule.
# ---------------------------------------------------------------------------

def predict_tiles(props: GraphProps, cfg: AggConfig) -> float:
    """Predict the tile count T from input statistics.

    Groups per node v: ceil over window-splits of deg_v — approximated with
    the measured degree mean/stddev and the numbering locality:
      windows touched per node  ~ 1 + spread_factor
      groups per node           ~ sum_w ceil(deg_vw / gs)
    Padding to gpt multiples happens per (node_block, window) bucket.
    """
    n, e = props.num_nodes, max(props.num_edges, 1)
    avg_deg = e / max(n, 1)
    # windows per node: how scattered are a node's neighbors? numbering_spread
    # is mean |u-v|/N over edges; windows touched ≈ deg * min(1, spread*N/win).
    win_per_node = 1.0 + min(avg_deg - 1.0, avg_deg * min(
        1.0, props.numbering_spread * n / max(cfg.src_win, 1))) if avg_deg > 1 else 1.0
    deg_per_win = avg_deg / win_per_node
    groups_per_node = win_per_node * (1.0 + max(deg_per_win - 1.0, 0.0) // cfg.gs)
    groups = n * groups_per_node
    # bucket padding: buckets ≈ node_blocks * windows-per-block
    node_blocks = max(n / cfg.ont, 1.0)
    buckets = node_blocks * max(1.0, min(win_per_node * cfg.ont,
                                         n / max(cfg.src_win, 1)))
    padded = groups + 0.5 * cfg.gpt * buckets
    return max(padded / cfg.gpt, 1.0)


def _round_up(x: int, unit: int) -> int:
    return -(-x // unit) * unit


def vmem_working_set(cfg: AggConfig, bytes_feat: int | None = None, *,
                     dim: int | None = None) -> int:
    """VMEM bytes per grid step of the folded kernel — Eq. 4 analogue.

    Counts what Mosaic allocates: every VMEM array is laid out in (8, 128)
    tiles, so minor dims round up to 128 lanes and second-minor dims to 8
    sublanes.  Terms: the double-buffered (src_win, dt) feature window;
    four (gpt*gs, src_win) 32-bit arrays the W-build materializes — the
    column iota, the compare mask, the per-slot gather matrix, and its
    product with the group one-hot's operand (the edge-grad kernel's
    per-slot score matrix has the same shape); the (gpt, src_win) f32 W;
    the double-buffered (8, gpt*gs) metadata blocks and (8, gpt)
    local_node block; the (ont, dt) f32 output block and its aliased
    prior-sum input, both double-buffered.  With ``dim`` the window is
    priced at the tile `kernels.ops.dim_tile` runs for a ``dim``-wide
    operand.

    ``bytes_feat`` defaults to the config's own dtype policy
    (``cfg.feat_dtype``); pass it explicitly only to price a hypothetical."""
    if bytes_feat is None:
        bytes_feat = cfg.bytes_feat
    dt = _round_up(cfg.dt if dim is None
                   else dim_tile(cfg.dt, dim, cfg.feat_dtype), LANES)
    win = _round_up(cfg.src_win, LANES)
    slots = cfg.gpt * cfg.gs
    window = 2 * cfg.src_win * dt * bytes_feat              # double-buffered
    slot_mat = 4 * _round_up(slots, SUBLANES) * win * 4
    gather_mat = _round_up(cfg.gpt, SUBLANES) * win * 4
    meta = 2 * SUBLANES * (2 * _round_up(slots, LANES)
                           + _round_up(cfg.gpt, LANES)) * 4
    out_block = 2 * 2 * cfg.ont * dt * 4
    return window + slot_mat + gather_mat + meta + out_block


def config_infeasibility(cfg: AggConfig, *, hw: TPUSpec | None = None,
                         bytes_feat: int | None = None,
                         dim: int | None = None) -> str | None:
    """Eq. 3 + Eq. 4 feasibility, TPU-re-derived: None when the config is
    feasible, else a human-readable reason naming the violated constraint
    (the tuner surfaces it when rejection sampling exhausts the space).

    ``hw`` defaults to the local chip's spec (`repro.hw.device_spec`).
    ``dim`` is the feature width the kernel sees; given it, the chip's
    block rule applies: a ``dt`` narrower than the padded width must be a
    multiple of 128 lanes."""
    if hw is None:
        hw = device_spec()
    if bytes_feat is None:
        bytes_feat = cfg.bytes_feat
    # Eq. 4: VMEM capacity (use half of VMEM as the safety envelope).
    ws = vmem_working_set(cfg, bytes_feat, dim=dim)
    if ws > hw.vmem_bytes * 0.5:
        return (f"Eq. 4 VMEM working set {ws}B > half of "
                f"{hw.name} VMEM ({hw.vmem_bytes / 2:.0f}B) at "
                f"bytes_feat={bytes_feat}")
    # Eq. 3: per-group work must fit a sane VPU budget (avoid pathological
    # single-unit serialization): gs*dt elements per group-slot.
    if cfg.gs * cfg.dt > 64 * 1024:
        return f"Eq. 3 per-group work gs*dt={cfg.gs * cfg.dt} > 64Ki"
    # structural alignment: dim tiles must be lane-tile aligned for the
    # feature dtype (8 for f32, 16 for 16-bit types), windows sublane-aligned
    align = feat_dtype_align(cfg.feat_dtype)
    if cfg.dt % align != 0:
        return (f"dt={cfg.dt} not a multiple of the {cfg.feat_dtype} "
                f"alignment unit {align}")
    if dim is not None:
        d_pad = _round_up(dim, align)
        if cfg.dt < d_pad and cfg.dt % LANES:
            return (f"dt={cfg.dt} is narrower than the padded D={d_pad} "
                    f"but not a multiple of {LANES} lanes")
    if cfg.src_win % 8 != 0:
        return f"src_win={cfg.src_win} not a multiple of 8"
    return None


def config_is_feasible(cfg: AggConfig, *, hw: TPUSpec | None = None,
                       bytes_feat: int | None = None,
                       dim: int | None = None) -> bool:
    return config_infeasibility(cfg, hw=hw, bytes_feat=bytes_feat,
                                dim=dim) is None


# bf16 MXU passes of one f32 x f32 matmul at Precision.HIGHEST (each
# operand split into three bf16 terms, six products kept); the kernels run
# every f32 matmul at HIGHEST
_F32_HIGHEST_PASSES = 6


@dataclasses.dataclass
class KernelModel:
    """Three-term latency model of the group_aggregate schedule.

    Compute prices every grid step of `kernels.group_aggregate._kernel`:
    the per-slot compare-select on the VPU (gpt*gs*src_win elements), and
    three MXU matmuls — the group one-hot sum (gpt, gpt*gs) @ (gpt*gs,
    src_win), which grows as gpt²; the gather (gpt, src_win) @ (src_win,
    dt); and the node scatter (ont, gpt) @ (gpt, dt).  Matmuls with f32
    operands run at HIGHEST precision, priced as `_F32_HIGHEST_PASSES`
    bf16 passes; the gather over a bf16 window is one pass.  Memory is the
    window DMA bytes plus metadata and output flushes; overhead is per
    grid step."""

    hw: TPUSpec = dataclasses.field(default_factory=device_spec)

    def terms(self, props: GraphProps, dim: int, cfg: AggConfig,
              *, tiles: float | None = None,
              bytes_feat: int | None = None) -> dict:
        if bytes_feat is None:
            bytes_feat = cfg.bytes_feat
        T = float(tiles if tiles is not None else predict_tiles(props, cfg))
        dt = dim_tile(cfg.dt, dim, cfg.feat_dtype)
        J = max(math.ceil(dim / dt), 1)
        steps = T * J
        slots = cfg.gpt * cfg.gs
        f32 = _F32_HIGHEST_PASSES
        onehot = 2 * cfg.gpt * slots * cfg.src_win            # f32 always
        gather = 2 * cfg.gpt * cfg.src_win * dt
        scatter = 2 * cfg.ont * cfg.gpt * dt                  # f32 always
        mxu_flops = steps * (onehot + gather + scatter)
        mxu_passes = steps * (f32 * (onehot + scatter)
                              + (1 if bytes_feat == 2 else f32) * gather)
        vpu_ops = steps * slots * cfg.src_win                 # compare-select
        t_compute = (mxu_passes / self.hw.peak_flops_bf16
                     + vpu_ops / (self.hw.peak_flops_f32 / 2))
        # memory: feature-window DMAs (dominant), metadata, output flushes
        n_blocks = max(props.num_nodes / cfg.ont, 1.0)
        bytes_windows = steps * cfg.src_win * dt * bytes_feat
        bytes_meta = steps * (cfg.gpt * cfg.gs * 8 + cfg.gpt * 4)
        bytes_out = n_blocks * J * cfg.ont * dt * 4 * 2  # load + flush
        t_memory = (bytes_windows + bytes_meta + bytes_out) / self.hw.hbm_bw
        t_overhead = steps * self.hw.grid_step_overhead_s
        return {
            "tiles": T, "steps": steps,
            "mxu_flops": mxu_flops, "mxu_passes": mxu_passes,
            "vpu_ops": vpu_ops,
            "bytes": bytes_windows + bytes_meta + bytes_out,
            "t_compute": t_compute, "t_memory": t_memory,
            "t_overhead": t_overhead,
            "latency": max(t_compute, t_memory) + t_overhead,
        }

    def latency(self, props: GraphProps, dim: int, cfg: AggConfig, **kw) -> float:
        return self.terms(props, dim, cfg, **kw)["latency"]
