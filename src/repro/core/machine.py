"""Machine models: the paper's four x86 contention domains (Table I) plus the
TPU v5e chip model this framework targets.

A :class:`MachineModel` describes one *memory contention domain* — the unit over
which the paper's bandwidth-sharing model (core/sharing.py) arbitrates.  On the
x86 systems that is a ccNUMA domain; on TPU v5e it is a single chip's HBM
interface, shared between the MXU/VPU load streams, DMA engines, and the
ICI send/recv buffers of in-flight collectives.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping


@dataclasses.dataclass(frozen=True)
class CacheLevel:
    """One level of the cache/memory hierarchy (per-core unless ``shared``)."""

    name: str
    size_bytes: int
    shared: bool = False
    # Bandwidth of the data path *into* this level from the level above
    # (closer to the core), in bytes per core cycle.  ``None`` for L1 (register
    # file path is modelled via ld/st throughput instead).
    bw_bytes_per_cycle: float | None = None


@dataclasses.dataclass(frozen=True)
class MachineModel:
    """A memory contention domain.

    ``overlapping_transfers`` switches the ECM composition rule (paper Eq. 1):
    ``False`` → Intel-style serial addition of transfer times,
    ``True``  → AMD-Rome-style full overlap (max of contributions).
    """

    name: str
    cores_per_domain: int
    clock_ghz: float
    # Theoretical (pin) memory bandwidth of the domain, GB/s.
    theoretical_bw_gbs: float
    # Measured saturated bandwidth envelope, GB/s.  Keyed by "read_only" /
    # "read_write"; kernels interpolate between these by their stream mix.
    saturated_bw_gbs: Mapping[str, float]
    cache_levels: tuple[CacheLevel, ...]
    # SIMD width in bytes for loads/stores (AVX2: 32, AVX-512: 64).
    simd_bytes: int
    # Sustained load / store slots per cycle.
    loads_per_cycle: float
    stores_per_cycle: float
    # FMA throughput: SIMD FMA instructions retired per cycle.
    fma_per_cycle: float
    overlapping_transfers: bool
    victim_llc: bool
    inclusive_llc: bool

    @property
    def cycle_s(self) -> float:
        return 1.0 / (self.clock_ghz * 1e9)

    def bw_bytes_per_cycle(self, gbs: float) -> float:
        """Convert a GB/s figure to bytes per core cycle on this machine."""
        return gbs * 1e9 / (self.clock_ghz * 1e9)

    @property
    def llc(self) -> CacheLevel:
        return self.cache_levels[-1]


# ---------------------------------------------------------------------------
# Paper Table I.  Saturated-bandwidth envelopes are taken from the read-only /
# read-write extremes of Table II (vectorSUM vs. Schoenauer family).
# ---------------------------------------------------------------------------

KiB = 1024
MiB = 1024 * KiB

BDW1 = MachineModel(
    name="BDW-1",
    cores_per_domain=10,
    clock_ghz=2.2,
    theoretical_bw_gbs=68.3,
    saturated_bw_gbs={"read_only": 59.9, "read_write": 53.2},
    cache_levels=(
        CacheLevel("L1", 32 * KiB),
        CacheLevel("L2", 256 * KiB, bw_bytes_per_cycle=64.0),
        CacheLevel("L3", 25 * MiB, shared=True, bw_bytes_per_cycle=32.0),
    ),
    simd_bytes=32,
    loads_per_cycle=2.0,
    stores_per_cycle=1.0,
    fma_per_cycle=2.0,
    overlapping_transfers=False,
    victim_llc=False,
    inclusive_llc=True,
)

BDW2 = MachineModel(
    name="BDW-2",
    cores_per_domain=18,
    clock_ghz=2.3,
    theoretical_bw_gbs=76.8,
    saturated_bw_gbs={"read_only": 66.9, "read_write": 62.2},
    cache_levels=(
        CacheLevel("L1", 32 * KiB),
        CacheLevel("L2", 256 * KiB, bw_bytes_per_cycle=64.0),
        CacheLevel("L3", 45 * MiB, shared=True, bw_bytes_per_cycle=32.0),
    ),
    simd_bytes=32,
    loads_per_cycle=2.0,
    stores_per_cycle=1.0,
    fma_per_cycle=2.0,
    overlapping_transfers=False,
    victim_llc=False,
    inclusive_llc=True,
)

CLX = MachineModel(
    name="CLX",
    cores_per_domain=20,
    clock_ghz=2.5,
    theoretical_bw_gbs=140.8,
    saturated_bw_gbs={"read_only": 111.1, "read_write": 102.4},
    cache_levels=(
        CacheLevel("L1", 32 * KiB),
        CacheLevel("L2", 1048 * KiB, bw_bytes_per_cycle=64.0),
        # 16+16 B/cy bidirectional mesh link to the (exclusive) LLC.
        CacheLevel("L3", int(27.5 * MiB), shared=True, bw_bytes_per_cycle=32.0),
    ),
    simd_bytes=64,
    loads_per_cycle=2.0,
    stores_per_cycle=1.0,
    fma_per_cycle=2.0,
    overlapping_transfers=False,
    victim_llc=True,
    inclusive_llc=False,
)

ROME = MachineModel(
    name="ROME",
    cores_per_domain=8,
    clock_ghz=2.35,
    theoretical_bw_gbs=42.7,  # one NPS4 quadrant of the 170.6 GB/s socket
    saturated_bw_gbs={"read_only": 36.0, "read_write": 32.2},
    cache_levels=(
        CacheLevel("L1", 32 * KiB),
        CacheLevel("L2", 512 * KiB, bw_bytes_per_cycle=64.0),  # 32+32 B/cy
        CacheLevel("L3", 8 * MiB, shared=True, bw_bytes_per_cycle=32.0),
    ),
    simd_bytes=32,
    loads_per_cycle=2.0,
    stores_per_cycle=1.0,
    fma_per_cycle=2.0,
    overlapping_transfers=True,
    victim_llc=True,
    inclusive_llc=False,
)

X86_MACHINES: dict[str, MachineModel] = {
    m.name: m for m in (BDW1, BDW2, CLX, ROME)
}


# ---------------------------------------------------------------------------
# TPU v5e — the target of the framework.  The "contention domain" is one
# chip's HBM interface; the "cores" of the paper map to concurrent on-chip
# streams (compute-phase loads, DMA prefetch, collective send/recv drains).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TpuModel:
    name: str
    peak_flops_bf16: float  # FLOP/s per chip
    hbm_bw_gbs: float       # GB/s per chip
    hbm_bytes: int
    vmem_bytes: int
    ici_link_gbs: float     # GB/s per ICI link direction
    ici_links: int          # links per chip in a 2D torus
    mxu_dim: int = 128      # systolic array edge — matmul tiling granularity
    lane_dim: int = 128     # VPU lane count — last-axis tiling granularity
    sublane_dim: int = 8    # VPU sublanes (fp32); 16 for bf16

    @property
    def balance_flops_per_byte(self) -> float:
        """Machine balance: flops per HBM byte at roofline ridge."""
        return self.peak_flops_bf16 / (self.hbm_bw_gbs * 1e9)


TPU_V5E = TpuModel(
    name="TPUv5e",
    peak_flops_bf16=197e12,
    hbm_bw_gbs=819.0,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=128 * MiB,
    ici_link_gbs=50.0,
    ici_links=4,
)

#: TPU machine models by the ``device_kind`` JAX reports.  A device that
#: is missing here has no known peaks, and callers treat it as an error.
TPU_BY_DEVICE_KIND: dict[str, TpuModel] = {"TPU v5 lite": TPU_V5E}
