"""Power accounting (paper §5, Eq. 1): Throughput_Watt = (items/s) / TDP.

TDP models for the paper's devices and for the TPU chips (keyed by JAX's
``device_kind``) live in `repro.roofline.hw`; this module turns
offload/benchmark stats into the paper's img/W metric and the LM-serving
analogues (tokens/s/W, tokens/J).
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.roofline.hw import (MYRIAD2_VPU, QUADRO_K4000, XEON_E5_2609V2,
                               chip_for)

# Paper-calibrated single-inference latencies (Fig 6b normalization bases).
PAPER_LATENCY_S = {
    "vpu": 0.1007,     # Myriad 2 VPU, single NCS
    "cpu": 0.0260,     # dual Xeon E5-2609v2, Caffe-MKL
    "gpu": 0.0259,     # Quadro K4000, Caffe-cuDNN
}
# Paper-reported batch-8 throughputs (Fig 6a), img/s.
PAPER_THROUGHPUT_8 = {"vpu": 77.2, "cpu": 44.0, "gpu": 74.2}

PAPER_TDP_W = {
    "vpu": MYRIAD2_VPU.tdp_watts,        # 0.9 W chip (2.5 W stick peak)
    "cpu": XEON_E5_2609V2.tdp_watts,     # 80 W
    "gpu": QUADRO_K4000.tdp_watts,       # 80 W
}


def throughput_per_watt(items_per_s: float, tdp_watts: float) -> float:
    """Paper Eq. (1)."""
    return items_per_s / tdp_watts


def joules_per_item(items_per_s: float, tdp_watts: float) -> float:
    return tdp_watts / items_per_s if items_per_s else float("inf")


@dataclass(frozen=True)
class PowerReport:
    device: str
    n_devices: int
    items_per_s: float
    tdp_watts_total: float

    @property
    def items_per_watt(self) -> float:
        return throughput_per_watt(self.items_per_s, self.tdp_watts_total)

    @property
    def joules_per_item(self) -> float:
        return joules_per_item(self.items_per_s, self.tdp_watts_total)

    def row(self) -> str:
        return (f"{self.device:>14s} x{self.n_devices:<3d} "
                f"{self.items_per_s:10.2f} items/s  "
                f"{self.tdp_watts_total:8.1f} W  "
                f"{self.items_per_watt:8.3f} items/W  "
                f"{self.joules_per_item:8.3f} J/item")


def report(device: str, n_devices: int, items_per_s: float,
           *, per_device_watts: float | None = None) -> PowerReport:
    if per_device_watts is None:
        per_device_watts = PAPER_TDP_W[device]
    return PowerReport(device=device, n_devices=n_devices,
                       items_per_s=items_per_s,
                       tdp_watts_total=per_device_watts * n_devices)


def serving_power_report(tokens_per_s: float,
                         devices) -> PowerReport | None:
    """LM-serving analogue of the paper's metric over the distinct chips
    that served (replicas sharing a chip count it once); None when they
    are not TPUs, whose power is not modelled."""
    devices = set(devices)
    chip = chip_for(next(iter(devices)))      # one host holds one kind
    if chip is None:
        return None
    return PowerReport(device=chip.name, n_devices=len(devices),
                       items_per_s=tokens_per_s,
                       tdp_watts_total=chip.tdp_watts * len(devices))


def power_row(report: PowerReport | None) -> str:
    """The report's row, or the line saying power was not measured."""
    return (report.row() if report is not None
            else "power: not measured (no TPU)")
