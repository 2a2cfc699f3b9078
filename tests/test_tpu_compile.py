"""Compile-only checks of the paged attention kernels for a described TPU
v5e, at qwen2.5-3b widths and serving shapes.

Nothing runs: the TPU compiler lowers each kernel for a chip that is
described, not attached, and refuses what the chip would refuse (block
shapes Mosaic cannot tile, more VMEM than a kernel may use).  Interpret
mode, which every other kernel test uses, checks neither.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, so the call must run
in the worker that was handed this file.  All such tests stay in this one
file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry as R
from repro.kernels.decode_attention.kernel import paged_decode_attention
from repro.kernels.prefill_attention.kernel import paged_prefill_attention

CFG = R.config("qwen2.5-3b")
BS = 16            # ServingEngine's default pool block size
SLOTS = 4          # decode batch of the one-chip smoke run
MB = 64            # table width: 1,024 rows per sequence


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool(sharding, quant: bool):
    """k/v pools (N, K, bs, D) and, for int8, their (N, K, bs) scales."""
    N, K, D = 1 + SLOTS * MB, CFG.num_kv_heads, CFG.resolved_head_dim
    dt = jnp.int8 if quant else jnp.bfloat16
    kv = [_sds(sharding, (N, K, BS, D), dt)] * 2
    sc = [_sds(sharding, (N, K, BS), jnp.float32)] * 2 if quant else []
    return kv, sc


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
def test_paged_decode_compiles_for_v5e(one_chip, cache_dtype):
    quant = cache_dtype == "int8"
    (kp, vp), sc = _pool(one_chip, quant)
    q = _sds(one_chip, (SLOTS, CFG.num_heads, CFG.resolved_head_dim),
             jnp.bfloat16)
    tables = _sds(one_chip, (SLOTS, MB), jnp.int32)
    lengths = _sds(one_chip, (SLOTS,), jnp.int32)

    def step(q, kp, vp, tables, lengths, *scales):
        kw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
        return paged_decode_attention(q, kp, vp, tables, lengths, **kw)

    _assert_kernel(jax.jit(step).lower(q, kp, vp, tables, lengths,
                                       *sc).compile())


# The kernel splits a chunk into query tiles of at most 1,024 rows, so its
# VMEM does not grow with C: 2,048 (an unchunked prompt near qwen2.5-3b's
# serving lengths) stands for any longer chunk the engine may hand it.
@pytest.mark.parametrize("chunk", [16, 256, 1024, 2048])
def test_paged_prefill_compiles_for_v5e(one_chip, chunk):
    (kp, vp), _ = _pool(one_chip, quant=False)
    mb = max(MB, chunk // BS)
    q = _sds(one_chip, (1, chunk, CFG.num_heads, CFG.resolved_head_dim),
             jnp.bfloat16)
    table = _sds(one_chip, (1, mb), jnp.int32)
    q_start = _sds(one_chip, (1,), jnp.int32)
    kv_len = _sds(one_chip, (1,), jnp.int32)
    _assert_kernel(jax.jit(paged_prefill_attention).lower(
        q, kp, vp, table, q_start, kv_len).compile())


def test_paged_prefill_int8_verify_shape_compiles_for_v5e(one_chip):
    """The speculative verify pass: all slots, k+1 = 4 rows each, int8."""
    (kp, vp), (ks, vs) = _pool(one_chip, quant=True)
    q = _sds(one_chip, (SLOTS, 4, CFG.num_heads, CFG.resolved_head_dim),
             jnp.bfloat16)
    tables = _sds(one_chip, (SLOTS, MB), jnp.int32)
    starts = _sds(one_chip, (SLOTS,), jnp.int32)

    def step(q, kp, vp, tables, starts, kv_len, ks, vs):
        return paged_prefill_attention(q, kp, vp, tables, starts, kv_len,
                                       k_scale=ks, v_scale=vs)

    _assert_kernel(jax.jit(step).lower(q, kp, vp, tables, starts, starts,
                                       ks, vs).compile())
