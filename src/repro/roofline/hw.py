"""Hardware constants for roofline analysis and the power model.

The TARGET platform is a TPU v5e pod (this container is a CPU host used only
for lowering/compiling).  The paper's devices are kept alongside so the
paper-reproduction benchmarks (Fig 6/8) can report the same TDP-normalized
metrics the paper uses.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops_bf16: float      # FLOP/s per chip
    hbm_bandwidth: float        # bytes/s per chip
    ici_link_bandwidth: float   # bytes/s per link
    ici_links: int              # links per chip (torus degree)
    hbm_bytes: float            # HBM capacity per chip
    vmem_bytes: float           # on-chip scratchpad (VMEM / CMX analogue)
    tdp_watts: float            # thermal design power per chip


# Assignment-specified constants: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link.
TPU_V5E = ChipSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bandwidth=819e9,
    ici_link_bandwidth=50e9,
    ici_links=4,                 # 2D torus: 4 links/chip
    hbm_bytes=16 * 1024**3,
    vmem_bytes=128 * 1024**2,
    tdp_watts=200.0,
)

# The paper's co-processor: Movidius Myriad 2 VPU (MA2450) on the NCS.
# 12 SHAVEs @600MHz; manufacturer-claimed 1000 Gflops FP16; CMX 2MB; TDP 0.9W
# (2.5W peak for the whole NCS stick).
MYRIAD2_VPU = ChipSpec(
    name="myriad2-vpu",
    peak_flops_bf16=1e12,        # FP16 claimed peak
    hbm_bandwidth=4e9,           # LPDDR3 ballpark
    ici_link_bandwidth=0.4e9,    # USB 3.0 effective
    ici_links=1,
    hbm_bytes=4 * 1024**3,       # 4GB stacked LPDDR3
    vmem_bytes=2 * 1024**2,      # CMX
    tdp_watts=0.9,
)

NCS_STICK_PEAK_WATTS = 2.5       # whole-stick peak per the paper

# Reference devices from the paper's evaluation (TDP only is used).
XEON_E5_2609V2 = ChipSpec(
    name="xeon-e5-2609v2",
    peak_flops_bf16=80e9 * 4,    # 4 cores @2.5GHz, AVX fp32-ish; not used for roofline
    hbm_bandwidth=51.2e9,
    ici_link_bandwidth=8e9,
    ici_links=1,
    hbm_bytes=72 * 1024**3,
    vmem_bytes=10 * 1024**2,
    tdp_watts=80.0,
)
QUADRO_K4000 = ChipSpec(
    name="quadro-k4000",
    peak_flops_bf16=1.246e12,
    hbm_bandwidth=134e9,
    ici_link_bandwidth=8e9,
    ici_links=1,
    hbm_bytes=3 * 1024**3,
    vmem_bytes=0.5 * 1024**2,
    tdp_watts=80.0,
)

# TPU chips by ``device_kind`` as JAX reports it (a v5e reports "TPU v5 lite").
TPU_KINDS = {"TPU v5 lite": TPU_V5E}


def chip_for(device) -> ChipSpec | None:
    """The spec of a JAX device, or None when it is not a TPU.  A TPU kind
    missing from :data:`TPU_KINDS` is an error, never a default."""
    if device.platform != "tpu":
        return None
    try:
        return TPU_KINDS[device.device_kind]
    except KeyError:
        raise ValueError(f"unknown TPU device_kind {device.device_kind!r}; "
                         f"add its spec to TPU_KINDS") from None


def bisection_bandwidth(chip: ChipSpec, num_chips: int) -> float:
    """Aggregate ICI bandwidth available to one chip for collectives (bytes/s).

    For ring-based collectives on a torus, each chip drives ``ici_links`` links
    concurrently; the assignment's collective term divides total collective
    bytes by chips x link_bw, so we expose per-chip link bandwidth directly.
    """
    del num_chips
    return chip.ici_link_bandwidth * chip.ici_links
