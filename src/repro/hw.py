"""Hardware constants used by the analytical model, the advisor, and the
roofline analysis, keyed by the `device_kind` JAX reports.

`device_spec()` is the spec of the chip the process runs on.  Off a TPU
(CPU tests, planning ahead of a chip run) it is the design target, TPU v5e;
on a TPU whose kind is not in `TPU_SPECS` it is an error, never a default.
Peaks: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s
HBM, 16 GB HBM per chip).
"""
from __future__ import annotations

import dataclasses

__all__ = ["TPUSpec", "TPU_V5E", "TPU_SPECS", "device_spec", "MXU_DIM",
           "SUBLANES", "LANES"]

MXU_DIM = 128      # systolic array edge; matmul dims should be multiples
SUBLANES = 8       # vreg sublane count (f32)
LANES = 128        # vreg lane count


@dataclasses.dataclass(frozen=True)
class TPUSpec:
    name: str
    peak_flops_bf16: float      # FLOP/s per chip
    peak_flops_f32: float
    hbm_bw: float               # bytes/s per chip
    hbm_bytes: float            # capacity per chip
    vmem_bytes: float           # per core
    smem_bytes: float
    ici_link_bw: float          # bytes/s per link per direction
    ici_links: int              # links per chip (2-D torus: 4)
    grid_step_overhead_s: float # per Pallas grid step (DMA issue + prefetch)

    @property
    def mxu_dim(self) -> int:
        return MXU_DIM


TPU_V5E = TPUSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    peak_flops_f32=98.5e12,
    hbm_bw=819e9,
    hbm_bytes=16 * 2**30,
    vmem_bytes=16 * 2**20,
    smem_bytes=1 * 2**20,
    ici_link_bw=50e9,
    ici_links=4,
    grid_step_overhead_s=1.5e-6,
)

# device_kind (as `jax.devices()[0].device_kind` reports it) -> spec
TPU_SPECS = {"TPU v5 lite": TPU_V5E}


def device_spec() -> TPUSpec:
    """Spec of the local chip; TPU v5e (the design target) off a TPU."""
    import jax

    if jax.default_backend() != "tpu":
        return TPU_V5E
    kind = jax.devices()[0].device_kind
    try:
        return TPU_SPECS[kind]
    except KeyError:
        raise ValueError(f"no hardware spec for TPU kind {kind!r}; "
                         f"known kinds: {sorted(TPU_SPECS)}") from None
