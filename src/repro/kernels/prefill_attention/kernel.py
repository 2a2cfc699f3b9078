"""Paged prefill attention — Pallas TPU.

The cache-seeded prefill path's kernel: a multi-row query chunk (C prompt
tokens starting at absolute position ``q_start``) attends over KV that
lives in the global block pool, addressed through a per-sequence block
table.  This is the multi-row sibling of `decode_attention`'s paged
kernel: same ``(N, K, bs, D)`` pool layout, same scalar-prefetched block
table driving the k/v BlockSpec index map (DMA gathers exactly the live
blocks), same online-softmax scratch, same int8 dequantization through
per-row scales on the score and probability tiles.  The mask is *causal
against absolute positions*, so the chunk attends fully over
already-seeded blocks (shared prefixes, resumed histories) and
triangularly within itself.

Grid (B, K, C / tq, max_blocks): the chunk's ``C*G`` query rows are split
into tiles of ``tq`` positions (``tq*G`` rows, at most ``_TILE_ROWS``), so
the query, output and accumulator tiles held in VMEM stay the same size
however long the chunk is.  A tile skips the blocks wholly past the valid
rows and the blocks wholly after its last query position.

Oracle: `ref.paged_prefill_attention_ref` (gather + chunked attention).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# query rows (positions x grouped heads) per grid step
_TILE_ROWS = 1024


def _prefill_kernel(bt_ref, qs_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                    scale: float, bs: int, mb: int, G: int, tq: int,
                    softcap: float, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    iq = pl.program_id(2)
    ib = pl.program_id(3)

    @pl.when(ib == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = len_ref[b]
    q0 = qs_ref[b] + iq * tq                      # tile's first position

    # Blocks wholly past the valid rows (trash entries, spare decode
    # blocks) or wholly after the tile's last query are masked for every
    # row of the tile, so they are skipped.
    @pl.when((ib * bs < valid) & (ib * bs < q0 + tq))
    def _update():
        q = q_ref[0, 0, :, :]                     # (tq*G, D)
        k = k_ref[0, 0, :, :].astype(q.dtype)     # (bs, D)
        v = v_ref[0, 0, :, :].astype(q.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quant:
            s = s * ks_ref[0, pl.ds(h, 1), :]     # (1, bs) row scales
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        # row r of the (tq*G, bs) score tile is query offset r // G; causal
        # against absolute positions lets the chunk see every seeded row
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
        k_pos = ib * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((k_pos <= q_pos) & (k_pos < valid), s, NEG_INF)

        m_prev = m_ref[...]                       # (tq*G, 1)
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


def _query_tile(C: int, G: int) -> int:
    """Positions per query tile: the whole chunk when its ``C*G`` rows fit
    one tile, else the largest power of two dividing C whose rows fit
    (C is then a multiple of the block size, so the tile's rows stay a
    multiple of 8)."""
    if C * G <= _TILE_ROWS:
        return C
    return math.gcd(C, 1 << (max(1, _TILE_ROWS // G).bit_length() - 1))


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, block_tables: jax.Array,
                            q_start: jax.Array, lengths: jax.Array, *,
                            k_scale: jax.Array | None = None,
                            v_scale: jax.Array | None = None,
                            softcap: float = 0.0,
                            interpret: bool = False) -> jax.Array:
    """q: (B, C, H, D) query chunk at positions ``q_start .. q_start+C-1``;
    k_pool/v_pool: (N, K, bs, D) global block pool; block_tables:
    (B, max_blocks); q_start: (B,) chunk origin; lengths: (B,) valid rows
    incl. the chunk; k_scale/v_scale: (N, K, bs) for int8 pools.

    Returns (B, C, H, D).  Grid (B, K, C / tq, max_blocks); tables,
    q_start, and lengths are scalar-prefetch operands, so the k/v
    BlockSpec index maps DMA each sequence's physical blocks in logical
    order.
    """
    B, C, H, D = q.shape
    N, K, bs, _ = k_pool.shape
    mb = block_tables.shape[1]
    G = H // K
    tq = _query_tile(C, G)
    scale = 1.0 / (D ** 0.5)
    qg = (q.reshape(B, C, K, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(B, K, C * G, D))
    quant = k_scale is not None

    def q_map(b, h, iq, ib, bt_ref, qs_ref, len_ref):
        return (b, h, iq, 0)

    def kv_map(b, h, iq, ib, bt_ref, qs_ref, len_ref):
        return (bt_ref[b, ib], h, 0, 0)

    def sc_map(b, h, iq, ib, bt_ref, qs_ref, len_ref):
        return (bt_ref[b, ib], 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, tq * G, D), q_map),
        pl.BlockSpec((1, 1, bs, D), kv_map),
        pl.BlockSpec((1, 1, bs, D), kv_map),
    ]
    args = [qg, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, K, bs), sc_map),
                     pl.BlockSpec((1, K, bs), sc_map)]
        args += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, K, C // tq, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, tq * G, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((tq * G, 1), jnp.float32),
            pltpu.VMEM((tq * G, 1), jnp.float32),
            pltpu.VMEM((tq * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, bs=bs, mb=mb, G=G,
                          tq=tq, softcap=softcap, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, C * G, D), q.dtype),
        interpret=interpret,
        name="paged_prefill_attention",
    )(block_tables.astype(jnp.int32), q_start.astype(jnp.int32),
      lengths.astype(jnp.int32), *args)
    return (out.reshape(B, K, C, G, D).transpose(0, 2, 1, 3, 4)
            .reshape(B, C, H, D))
