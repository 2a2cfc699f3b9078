"""Oracles for flash-decode (dense and paged): chunked attention with
kv_len masking; the paged variant gathers pool blocks by block table."""
from __future__ import annotations

import jax.numpy as jnp

from repro.models.layers.attention import chunked_attention


def decode_attention_ref(q, k, v, lengths, *, chunk=1024):
    """q: (B, H, D); k/v: (B, S, K, D); lengths: (B,)."""
    B, H, D = q.shape
    S = k.shape[1]
    out = chunked_attention(
        q[:, None], k, v, causal=False,
        q_positions=jnp.zeros((B, 1), jnp.int32),
        kv_positions=jnp.arange(S, dtype=jnp.int32),
        kv_len=lengths, chunk=chunk)
    return out[:, 0]


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths, *,
                               k_scale=None, v_scale=None, softcap=0.0,
                               scale=None, v_dim=None, chunk=1024):
    """Paged oracle: gather blocks into logical order, then dense decode.

    q: (B, H, D); k_pool/v_pool: (N, K, bs, D) global pool; block_tables:
    (B, max_blocks) physical block ids per logical block; lengths: (B,)
    valid rows per sequence.  k_scale/v_scale: (N, K, bs) when the pool is
    int8 (absmax-dequantized to q.dtype before attending, matching the
    dense quantized-cache path bit for bit).  ``v_pool`` None: values are
    the first ``v_dim`` lanes of the key rows; ``scale`` the softmax scale.
    """
    B, H, D = q.shape
    N, K, bs, _ = k_pool.shape
    mb = block_tables.shape[1]
    k = k_pool[block_tables]                     # (B, mb, K, bs, D)
    v = k[..., :v_dim] if v_pool is None else v_pool[block_tables]
    if k_scale is not None:
        k = (k.astype(jnp.float32)
             * k_scale[block_tables][..., None]).astype(q.dtype)
        v = (v.astype(jnp.float32)
             * v_scale[block_tables][..., None]).astype(q.dtype)
    S = mb * bs
    k = k.swapaxes(2, 3).reshape(B, S, K, D).astype(q.dtype)
    v = v.swapaxes(2, 3).reshape(B, S, K, -1).astype(q.dtype)
    out = chunked_attention(
        q[:, None], k, v, causal=False,
        q_positions=jnp.zeros((B, 1), jnp.int32),
        kv_positions=jnp.arange(S, dtype=jnp.int32),
        kv_len=lengths, softcap=softcap, scale=scale, chunk=chunk)
    return out[:, 0]
