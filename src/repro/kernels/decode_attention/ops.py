"""Jit'd wrappers with backend dispatch for flash-decode (dense + paged)."""
from __future__ import annotations

import jax

from repro.kernels.decode_attention.kernel import decode_attention as _pallas
from repro.kernels.decode_attention.kernel import \
    paged_decode_attention as _pallas_paged
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref)
from repro.kernels.dispatch import register_kernel, use_pallas

register_kernel("decode_attention", _pallas, decode_attention_ref)
register_kernel("paged_decode_attention", _pallas_paged,
                paged_decode_attention_ref)


def decode_attention(q, k, v, lengths, **block_kw):
    if use_pallas():
        interpret = jax.default_backend() != "tpu"
        return _pallas(q, k, v, lengths, interpret=interpret, **block_kw)
    return decode_attention_ref(q, k, v, lengths)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           k_scale=None, v_scale=None, softcap: float = 0.0,
                           scale: float | None = None,
                           v_dim: int | None = None, chunk: int = 1024):
    """Paged decode attention over a block pool + per-sequence block tables.

    The serving decode path calls this per layer; on TPU it lowers to the
    Pallas gather-by-block-table kernel, elsewhere to the jnp oracle
    (gather + chunked attention), bit-compatible with the dense path.
    ``v_pool`` None reads values as the first ``v_dim`` lanes of the keys
    (latent attention); ``scale`` overrides 1 / sqrt(head width).
    """
    if use_pallas():
        interpret = jax.default_backend() != "tpu"
        return _pallas_paged(q, k_pool, v_pool, block_tables, lengths,
                             k_scale=k_scale, v_scale=v_scale,
                             softcap=softcap, scale=scale, v_dim=v_dim,
                             interpret=interpret)
    return paged_decode_attention_ref(q, k_pool, v_pool, block_tables,
                                      lengths, k_scale=k_scale,
                                      v_scale=v_scale, softcap=softcap,
                                      scale=scale, v_dim=v_dim, chunk=chunk)
