"""Oracle for the grouped expert SwiGLU: ``jax.lax.ragged_dot`` over the
same padded group layout, in the kernel's precision (bf16 products
accumulated in float32, the hidden activations rounded once)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_expert_ffn_ref(xs, w_gate, w_up, w_down, sizes, *, tm: int,
                       group_rows: int | None = None):
    """xs: (M, d) rows grouped as ``kernel.group_offsets`` lays them out;
    returns (M, d), zero outside the groups.  ``group_rows`` is the
    kernel's buffer bound and changes nothing here."""
    del group_rows
    padded = (-(-sizes.astype(jnp.int32) // tm) * tm).astype(jnp.int32)

    def dot(a, w):
        return jax.lax.ragged_dot(a, w.astype(a.dtype), padded,
                                  preferred_element_type=jnp.float32)

    h = (jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up)).astype(xs.dtype)
    return dot(h, w_down).astype(xs.dtype)
