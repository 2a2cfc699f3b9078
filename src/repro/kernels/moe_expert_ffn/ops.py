"""Jit'd wrapper with backend dispatch for the grouped expert SwiGLU."""
from __future__ import annotations

import jax

from repro.kernels.dispatch import register_kernel, use_pallas
from repro.kernels.moe_expert_ffn.kernel import moe_expert_ffn as _pallas
from repro.kernels.moe_expert_ffn.ref import moe_expert_ffn_ref

register_kernel("moe_expert_ffn", _pallas, moe_expert_ffn_ref)


def moe_expert_ffn(xs, w_gate, w_up, w_down, sizes, *, layer=None, tm: int,
                   group_rows: int):
    """Every held expert's SwiGLU over its group of token-choice rows.
    ``layer`` given, the weights are stacks over layers and that layer's
    experts are used.  On TPU the Pallas kernel, elsewhere the
    ``ragged_dot`` oracle."""
    if use_pallas():
        interpret = jax.default_backend() != "tpu"
        return _pallas(xs, w_gate, w_up, w_down, sizes, layer=layer, tm=tm,
                       group_rows=group_rows, interpret=interpret)
    if layer is not None:
        w_gate, w_up, w_down = (w[layer] for w in (w_gate, w_up, w_down))
    return moe_expert_ffn_ref(xs, w_gate, w_up, w_down, sizes, tm=tm)
