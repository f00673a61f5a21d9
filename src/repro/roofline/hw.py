"""Per-chip hardware constants, keyed by ``jax.Device.device_kind``.

A TPU kind that is not in :data:`CHIPS` is an error, never a default: a
peak rate assumed for a chip the code does not know would make every
roofline share and every autotuner pick silently wrong.

Source of the published figures: Google Cloud documentation, "TPU v5e"
(system architecture table): 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB of
HBM2 at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip.
"""
from __future__ import annotations

import dataclasses

#: ``device_kind`` JAX reports for a TPU v5e chip
V5E = "TPU v5 lite"


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Peak rates and capacities of one chip."""

    peak_flops_bf16: float      # FLOP/s on the MXU, bf16 operands
    hbm_bw: float               # bytes/s
    hbm_bytes: int
    ici_bw_per_link: float      # bytes/s per link
    vmem_bytes: int             # vector memory the autotuner may plan in
    vpu_ops: float              # elementwise f32 op/s

    @property
    def peak_flops_f32(self) -> float:
        """MXU f32 rate: an f32 contraction costs four bf16 passes."""
        return self.peak_flops_bf16 / 4


CHIPS = {
    V5E: ChipSpec(
        peak_flops_bf16=197e12,           # published
        hbm_bw=819e9,                     # published
        hbm_bytes=16 * 1024**3,           # published (16 GB HBM2)
        # published 1,600 Gbit/s per chip over 4 links
        ici_bw_per_link=50e9,
        # not on the published page: the autotuner's planning figure;
        # tests/test_tpu_compile.py checks its picks against the compiler
        vmem_bytes=128 * 1024**2,
        vpu_ops=4e12,                     # modelling estimate, not published
    ),
}


def chip(device_kind: str) -> ChipSpec:
    """The :class:`ChipSpec` of ``device_kind``; raises ``KeyError`` for a
    chip this table does not describe."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no hardware constants for device kind {device_kind!r}; add "
            f"it to repro.roofline.hw.CHIPS with its source") from None


# Architecture and cost-model constants shared by every TPU generation here.
MXU_ALIGN = 128
SUBLANES = 8                    # f32 tile is (8, 128)
GRID_STEP_OVERHEAD_S = 2e-6     # model: per kernel grid step (DMA issue + sync)
HOST_DISPATCH_S = 200e-6        # model: per jit dispatch from the host loop
