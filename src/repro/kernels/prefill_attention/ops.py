"""Jit'd wrapper with backend dispatch for paged prefill attention."""
from __future__ import annotations

import jax

from repro.kernels.dispatch import register_kernel, use_pallas
from repro.kernels.prefill_attention.kernel import \
    paged_prefill_attention as _pallas_prefill
from repro.kernels.prefill_attention.ref import paged_prefill_attention_ref

register_kernel("paged_prefill_attention", _pallas_prefill,
                paged_prefill_attention_ref)


def paged_prefill_attention(q, k_pool, v_pool, block_tables, q_start,
                            lengths, *, k_scale=None, v_scale=None,
                            softcap: float = 0.0,
                            scale: float | None = None,
                            v_dim: int | None = None, chunk: int = 1024):
    """Prompt-chunk attention over a block pool + per-sequence tables.

    The cache-seeded prefill path calls this per layer after writing the
    chunk's KV rows into the pool; on TPU it lowers to the Pallas
    gather-by-block-table kernel, elsewhere to the jnp oracle — both
    causal against absolute positions so already-seeded blocks (shared
    prefixes, resumed histories) are attended without being recomputed.
    ``v_pool`` None reads values as the first ``v_dim`` lanes of the keys
    (latent attention); ``scale`` overrides 1 / sqrt(head width).
    """
    if use_pallas():
        interpret = jax.default_backend() != "tpu"
        return _pallas_prefill(q, k_pool, v_pool, block_tables, q_start,
                               lengths, k_scale=k_scale, v_scale=v_scale,
                               softcap=softcap, scale=scale, v_dim=v_dim,
                               interpret=interpret)
    return paged_prefill_attention_ref(q, k_pool, v_pool, block_tables,
                                       q_start, lengths, k_scale=k_scale,
                                       v_scale=v_scale, softcap=softcap,
                                       scale=scale, v_dim=v_dim, chunk=chunk)
