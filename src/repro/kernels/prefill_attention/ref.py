"""Oracle for paged prefill attention: gather pool blocks by block table,
then causal chunked attention with the query chunk offset to ``q_start``."""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.layers.attention import chunked_attention


def paged_prefill_attention_ref(q, k_pool, v_pool, block_tables, q_start,
                                lengths, *, k_scale=None, v_scale=None,
                                softcap=0.0, chunk=1024):
    """Multi-row query chunk vs block-table-gathered pool KV.

    q: (B, C, H, D) — a prompt chunk whose row ``o`` sits at absolute
    position ``q_start[b] + o``; k_pool/v_pool: (N, K, bs, D) global pool;
    block_tables: (B, max_blocks) physical block per logical block;
    q_start: (B,) first query position; lengths: (B,) total valid KV rows
    *including* this chunk's (the chunk's own rows are already written to
    the pool before attending).  k_scale/v_scale: (N, K, bs) for int8
    pools (absmax-dequantized before attending, matching the decode path).

    Causality makes row ``o`` attend to every seeded/earlier row plus the
    chunk rows at or before it; table entries past ``lengths`` (trash or
    spare decode blocks) sit at higher kv positions and are masked out.
    Returns (B, C, H, D).
    """
    B, C, H, D = q.shape
    N, K, bs, _ = k_pool.shape
    mb = block_tables.shape[1]
    k = k_pool[block_tables]                     # (B, mb, K, bs, D)
    v = v_pool[block_tables]
    if k_scale is not None:
        k = (k.astype(jnp.float32)
             * k_scale[block_tables][..., None]).astype(q.dtype)
        v = (v.astype(jnp.float32)
             * v_scale[block_tables][..., None]).astype(q.dtype)
    S = mb * bs
    k = k.swapaxes(2, 3).reshape(B, S, K, D).astype(q.dtype)
    v = v.swapaxes(2, 3).reshape(B, S, K, D).astype(q.dtype)
    q_pos = q_start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    out = chunked_attention(
        q, k, v, causal=True, q_positions=q_pos,
        kv_positions=jnp.arange(S, dtype=jnp.int32),
        kv_len=lengths, softcap=softcap, chunk=chunk)
    return out
