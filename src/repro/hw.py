"""Hardware constants used by the roofline model and the WSMC planner.

TPU v5e is the target platform. A run on a chip plans against that chip's
entry in DEVICES (keyed by jax's `Device.device_kind`); planning on the CPU
(the simulate backend, tests) targets the v5e entry explicitly.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops_bf16: float      # FLOP/s per chip
    hbm_bytes: int              # HBM capacity per chip
    hbm_bw: float               # bytes/s per chip
    ici_link_bw: float          # bytes/s per ICI link (one direction)
    ici_links_per_chip: int     # links on the 2-D torus
    vmem_bytes: int             # VMEM per core (Pallas tiling budget)
    # Runtime reserve: XLA runtime + infeed/outfeed scratch. Plays the role of
    # the paper's "Reserved Memory" (RM, 300MB in Spark's default).
    reserved_bytes: int = 300 * 1024 * 1024


TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    hbm_bytes=16 * 1024**3,
    hbm_bw=819e9,
    ici_link_bw=50e9,
    ici_links_per_chip=4,
    vmem_bytes=128 * 1024 * 1024,
)

# One chip's peaks by `jax.Device.device_kind`. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of chip-to-chip interconnect over 4 links).
DEVICES = {"TPU v5 lite": TPU_V5E}


def for_device_kind(kind: str) -> HardwareSpec:
    """The table entry of a device kind; an unknown kind is an error, never
    a silent fallback to another chip's numbers."""
    try:
        return DEVICES[kind]
    except KeyError:
        raise ValueError(f"no hardware entry for device kind {kind!r}; "
                         f"known: {sorted(DEVICES)}") from None


# The paper's Eq. 11 headroom factor: capacity = spark_mem * 4/3 + RM.
# We keep 4/3 as the HBM fragmentation / runtime-scratch margin.
CAPACITY_HEADROOM = 4.0 / 3.0


def capacity_from_requirement(resident_bytes: float, transient_bytes: float,
                              hw: HardwareSpec = TPU_V5E) -> float:
    """Paper Eq. 11: Mem_cap = Mem_spark * 4/3 + RM, per device."""
    return (resident_bytes + transient_bytes) * CAPACITY_HEADROOM + hw.reserved_bytes
