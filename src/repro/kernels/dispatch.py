"""Kernel backend dispatch.

Pallas kernels target TPU.  On a TPU backend the model layers call the
compiled kernels; on any other backend (the CPU test runs,
``JAX_PLATFORMS=cpu``) they call their jnp oracles, and a kernel forced on
with ``enable_pallas()`` runs in ``interpret=True`` mode (Python
evaluation of the kernel body: correct, slow, and blind to what Mosaic
would refuse — `tests/test_tpu_compile.py` compiles the paged kernels
for a described v5e to cover that).  `chip_smoke.py` checks that the
compiled serving steps hold the kernels (``tpu_custom_call``).

Each kernel family's ops module registers its (pallas, ref) pair in the
kernel table via :func:`register_kernel` (backend selection itself lives
in the ops wrappers, which also own the interpret-mode fallback).
`benchmarks/kernel_bench.py --smoke` (a tier-1 CI gate) cross-checks the
table against its correctness cases — registering a kernel without a
smoke case fails the build, as does any kernel-vs-oracle mismatch.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Callable, NamedTuple

_STATE = threading.local()


class KernelEntry(NamedTuple):
    pallas: Callable
    ref: Callable


_TABLE: dict[str, KernelEntry] = {}


def register_kernel(name: str, pallas_fn: Callable, ref_fn: Callable) -> None:
    """Register a kernel's Pallas implementation and its jnp oracle."""
    _TABLE[name] = KernelEntry(pallas_fn, ref_fn)


def kernel_table() -> dict[str, KernelEntry]:
    return dict(_TABLE)


def use_pallas() -> bool:
    import jax
    forced = getattr(_STATE, "forced", None)
    if forced is not None:
        return forced
    return jax.default_backend() == "tpu"


def enable_pallas(on: bool = True) -> None:
    _STATE.forced = on


@contextlib.contextmanager
def pallas_enabled(on: bool = True):
    prev = getattr(_STATE, "forced", None)
    _STATE.forced = on
    try:
        yield
    finally:
        _STATE.forced = prev
