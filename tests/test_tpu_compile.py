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


def _pool(sharding, quant: bool, cfg=CFG, N=1 + SLOTS * MB):
    """k/v pools (N, K, bs, D) and, for int8, their (N, K, bs) scales."""
    K, D = cfg.num_kv_heads, cfg.resolved_head_dim
    dt = jnp.int8 if quant else jnp.bfloat16
    kv = [_sds(sharding, (N, K, BS, D), dt)] * 2
    sc = [_sds(sharding, (N, K, BS), jnp.float32)] * 2 if quant else []
    return kv, sc


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


# The decode kernel at the reasoning cell's serving shapes (32 slots, a
# 288-page table for max_len 4,608), for both head layouts it serves:
# qwen2.5-3b (K = 2, G = 8) and the qwen3-32b stage (K = 8, G = 8).
DECODE_SLOTS, DECODE_MB = 32, 288


def _decode_cases():
    for arch in ("qwen2.5-3b", "qwen3-32b"):
        for dtype in ("bfloat16", "int8"):
            yield pytest.param(arch, dtype, id=dtype if arch == CFG.name
                               else f"{arch}-{dtype}")


@pytest.mark.parametrize("arch,cache_dtype", _decode_cases())
def test_paged_decode_compiles_for_v5e(one_chip, arch, cache_dtype):
    cfg = R.config(arch)
    (kp, vp), sc = _pool(one_chip, cache_dtype == "int8", cfg,
                         N=1 + DECODE_SLOTS * DECODE_MB)
    q = _sds(one_chip, (DECODE_SLOTS, cfg.num_heads, cfg.resolved_head_dim),
             jnp.bfloat16)
    tables = _sds(one_chip, (DECODE_SLOTS, DECODE_MB), jnp.int32)
    lengths = _sds(one_chip, (DECODE_SLOTS,), jnp.int32)

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


# Moonlight-16B-A3B's latent pool at the reasoning cell's shapes: 32 slots,
# 161-page tables (2,576 rows), one 640-lane entry per token (the 576-wide
# latent and RoPE key padded to whole lane tiles), 16 query heads.
MLA_SLOTS, MLA_MB, MLA_W, MLA_DV, MLA_H = 32, 161, 640, 512, 16


def test_latent_paged_decode_compiles_for_v5e(one_chip):
    pool = _sds(one_chip, (1 + MLA_SLOTS * MLA_MB, 1, BS, MLA_W),
                jnp.bfloat16)
    q = _sds(one_chip, (MLA_SLOTS, MLA_H, MLA_W), jnp.bfloat16)
    tables = _sds(one_chip, (MLA_SLOTS, MLA_MB), jnp.int32)
    lengths = _sds(one_chip, (MLA_SLOTS,), jnp.int32)

    def step(q, pool, tables, lengths):
        return paged_decode_attention(q, pool, None, tables, lengths,
                                      scale=192 ** -0.5, v_dim=MLA_DV)

    _assert_kernel(jax.jit(step).lower(q, pool, tables, lengths).compile())


@pytest.mark.parametrize("chunk", [16, 512])
def test_latent_paged_prefill_compiles_for_v5e(one_chip, chunk):
    pool = _sds(one_chip, (1 + MLA_SLOTS * MLA_MB, 1, BS, MLA_W),
                jnp.bfloat16)
    q = _sds(one_chip, (1, chunk, MLA_H, MLA_W), jnp.bfloat16)
    table = _sds(one_chip, (1, MLA_MB), jnp.int32)
    one = _sds(one_chip, (1,), jnp.int32)

    def step(q, pool, table, q_start, kv_len):
        return paged_prefill_attention(q, pool, None, table, q_start, kv_len,
                                       scale=192 ** -0.5, v_dim=MLA_DV)

    _assert_kernel(jax.jit(step).lower(q, pool, table, one, one).compile())


# The held experts' grouped SwiGLU at Moonlight's widths (16 held experts of
# 2,048 x 1,408): a decode step's 32 tokens and a 256-token prefill call,
# on one layer's stacks and on the 26-layer stacks read in place.
@pytest.mark.parametrize("tokens,tm,stacked", [(32, 16, False),
                                               (32, 16, True),
                                               (256, 32, True)])
def test_moe_expert_ffn_compiles_for_v5e(one_chip, tokens, tm, stacked):
    from repro.kernels.moe_expert_ffn.kernel import moe_expert_ffn, rows_bound
    lead = (26,) if stacked else ()
    M = rows_bound(tokens, 6, 16, tm)
    xs = _sds(one_chip, (M, 2048), jnp.bfloat16)
    wg = _sds(one_chip, lead + (16, 2048, 1408), jnp.bfloat16)
    wd = _sds(one_chip, lead + (16, 1408, 2048), jnp.bfloat16)
    sizes = _sds(one_chip, (16,), jnp.int32)
    layer = _sds(one_chip, (), jnp.int32)

    def step(xs, wg, wu, wd, sizes, layer):
        return moe_expert_ffn(xs, wg, wu, wd, sizes,
                              layer=layer if stacked else None, tm=tm,
                              group_rows=tokens)

    _assert_kernel(jax.jit(step).lower(xs, wg, wg, wd, sizes,
                                       layer).compile())
