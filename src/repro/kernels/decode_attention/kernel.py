"""Flash-decode: one query token vs a long KV cache — Pallas TPU.

Grid (B, K_heads, S/bkv): for each (batch, kv-head) the G grouped query
heads attend to KV blocks streamed through VMEM; running (m, l, acc) live
in scratch, per-sequence valid length masks dead slots.  This is the
split-K decode kernel whose distributed twin is the LSE-merge path in
`distributed.collectives` (the per-shard partials there are exactly this
kernel's (out, m, l) triple).

`paged_decode_attention` is the paged variant: the KV lives in a global
pool of fixed-size blocks and each sequence's block table is a
scalar-prefetch input, so the BlockSpec index map gathers exactly the
sequence's live blocks from HBM — decode traffic scales with actual
sequence length, not the worst-case ``max_len``.  Blocks past ``length``
are skipped outright (`pl.when`), and an int8 pool is dequantized in-VMEM
from per-row absmax scales.

The pool is laid out ``(N, K, bs, D)``: one grid step DMAs the ``(bs, D)``
tile of one kv-head of one block, whose trailing dims are (8, 128)-aligned
as Mosaic requires.  The int8 scales ``(N, K, bs)`` come in whole per block
(``(K, bs)`` equals the array's trailing dims); the kv-head's row of them
scales the score and probability tiles along their key axis, which equals
dequantizing K and V row by row.

Oracle: `models.layers.attention.chunked_attention` with kv_len masking
(`ref.decode_attention_ref` / `ref.paged_decode_attention_ref`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, bkv: int, n_kv: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0, :, :]                         # (G, D)
    k = k_ref[0, :, 0, :]                         # (bkv, D)
    v = v_ref[0, :, 0, :]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = len_ref[0, 0]
    k_pos = ik * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < valid, s, NEG_INF)

    m_prev = m_ref[...]                           # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.maximum(m_new, NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        o_ref[0, 0, :, :] = (acc_ref[...] /
                             jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bkv", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, bkv: int = 1024,
                     interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k/v: (B, S, K, D); lengths: (B,) valid KV per sequence.

    Returns (B, H, D).
    """
    B, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    bkv = min(bkv, S)
    assert S % bkv == 0, (S, bkv)
    n_kv = S // bkv
    scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, K, G, D)
    len2d = lengths.reshape(B, 1).astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bkv=bkv, n_kv=n_kv),
        grid=(B, K, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, ik: (b, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, bkv, 1, D), lambda b, h, ik: (b, ik, h, 0)),
            pl.BlockSpec((1, bkv, 1, D), lambda b, h, ik: (b, ik, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(len2d, qg, k, v)
    return out.reshape(B, H, D)


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest, scale: float,
                  bs: int, mb: int, softcap: float, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    ib = pl.program_id(2)

    @pl.when(ib == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = len_ref[b]

    # Dead logical blocks (table entry -> trash block 0) are skipped: the
    # kernel's read traffic follows the live length, not the table width.
    @pl.when(ib * bs < valid)
    def _update():
        q = q_ref[0, 0, :, :]                     # (G, D)
        k = k_ref[0, 0, :, :].astype(q.dtype)     # (bs, D)
        v = v_ref[0, 0, :, :].astype(q.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quant:
            s = s * ks_ref[0, pl.ds(h, 1), :]     # (1, bs) row scales
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = ib * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < valid, s, NEG_INF)

        m_prev = m_ref[...]                       # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_safe)
        corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        if quant:
            p = p * vs_ref[0, pl.ds(h, 1), :]
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ib == mb - 1)
    def _finalize():
        o_ref[0, 0, :, :] = (acc_ref[...] /
                             jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           block_tables: jax.Array, lengths: jax.Array, *,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           softcap: float = 0.0,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k_pool/v_pool: (N, K, bs, D) global block pool;
    block_tables: (B, max_blocks) physical block per logical block;
    lengths: (B,) valid rows; k_scale/v_scale: (N, K, bs) for int8 pools.

    Returns (B, H, D).  Grid (B, K, max_blocks); the block table is a
    scalar-prefetch operand so the k/v BlockSpec index maps dereference it
    to DMA each sequence's physical blocks in logical order.
    """
    B, H, D = q.shape
    N, K, bs, _ = k_pool.shape
    mb = block_tables.shape[1]
    G = H // K
    scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, K, G, D)
    quant = k_scale is not None

    def q_map(b, h, ib, bt_ref, len_ref):
        return (b, h, 0, 0)

    def kv_map(b, h, ib, bt_ref, len_ref):
        return (bt_ref[b, ib], h, 0, 0)

    def sc_map(b, h, ib, bt_ref, len_ref):
        return (bt_ref[b, ib], 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, G, D), q_map),
        pl.BlockSpec((1, 1, bs, D), kv_map),
        pl.BlockSpec((1, 1, bs, D), kv_map),
    ]
    args = [qg, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, K, bs), sc_map),
                     pl.BlockSpec((1, K, bs), sc_map)]
        args += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bs=bs, mb=mb,
                          softcap=softcap, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), *args)
    return out.reshape(B, H, D)
