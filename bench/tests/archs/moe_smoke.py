"""A test-only architecture: the program's ``moe`` family (the
DeepSeekMoE layout) at smoke size, through the interface of
``bench/archs/__init__.py``.  No cell runs it; its tests find it by
pointing the module lookup at this package.

The leading ``first_k_dense_replace`` layers have a dense SwiGLU FFN and
form the program's ``dense_blocks`` (a list); the rest form ``blocks``
(stacked), each with a router, routed experts and shared experts.
Attention, norms, RoPE and the head are ``qwen_dense``'s.  The FFN of an
expert layer, for one token h (``MoEConfig``'s routing, softmax scores):
    p = softmax(h W_router)                  over the E routed experts
    (p_1..p_k, e_1..e_k) = the k largest p   [norm_topk_prob: p_j / sum p]
    y = sum_j p_j SwiGLU_{e_j}(h) + SwiGLU_shared(h)
with the shared experts fused into one SwiGLU of n_shared times the
expert width, as the program holds them.  The reference computes every
expert on every token and combines by the routing weights; the program
dispatches each token to its experts with a capacity, which
``capacity_factor`` = E / k makes a chunk's whole row count, so no token
is dropped.

Kernels: the paged attention kernels of ``qwen_dense``, and
``expert_ffn``, a name of its own for the routed experts' SwiGLU (no
program emits it yet; a synthetic trace event carries it in the tests).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.archs import HI, Kernel
from bench.archs import qwen_dense as QD
from bench.work import Work

BF16 = 2


def spec_of(conf: dict) -> dict:
    s = QD.spec_of(conf)
    s.update(E=conf["n_routed_experts"], k=conf["num_experts_per_tok"],
             fe=conf["moe_intermediate_size"],
             shared=conf["n_shared_experts"],
             dense=conf["first_k_dense_replace"],
             norm_topk=bool(conf["norm_topk_prob"]),
             capacity=float(conf["capacity_factor"]))
    return s


def groups(s: dict) -> list[tuple[str, int, str]]:
    return [("dense_blocks", s["dense"], "list"),
            ("blocks", s["L"] - s["dense"], "stacked")]


def leaves(s: dict) -> list[tuple]:
    d, E, fe = s["d"], s["E"], s["fe"]
    out = QD.embed_leaves(s)
    for g in ("dense_blocks", "blocks"):
        out += QD.attn_leaves(s, g) + [(f"{g}.ln2.scale", g, (d,), 1.0, 0.1)]
    out += QD.swiglu_leaves("dense_blocks.mlp", "dense_blocks", d, s["f"])
    out += [("blocks.moe.router", "blocks", (d, E), 0.0, d ** -0.5),
            ("blocks.moe.w_gate", "blocks", (E, d, fe), 0.0, d ** -0.5),
            ("blocks.moe.w_up", "blocks", (E, d, fe), 0.0, d ** -0.5),
            ("blocks.moe.w_down", "blocks", (E, fe, d), 0.0, fe ** -0.5)]
    out += QD.swiglu_leaves("blocks.shared", "blocks", d, s["shared"] * fe)
    return out + [("ln_f.scale", None, (d,), 1.0, 0.1)]


def model_config(conf: dict):
    from repro.configs.base import MoEConfig
    s = spec_of(conf)
    moe = MoEConfig(num_experts=s["E"], top_k=s["k"], d_ff_expert=s["fe"],
                    num_shared_experts=s["shared"], d_ff_shared=s["fe"],
                    first_k_dense=s["dense"], d_ff_dense=s["f"],
                    capacity_factor=s["capacity"],
                    norm_topk_prob=s["norm_topk"])
    return QD.model_config(conf).replace(family="moe", d_ff=s["fe"], moe=moe)


# -- the reference's equations -----------------------------------------------

def experts(s, w, h, mm):
    """The routed experts' share of one sequence h: (S, d) float32."""
    p = jax.nn.softmax(mm(h, w["moe.router"]), axis=-1)          # (S, E)
    top, idx = jax.lax.top_k(p, s["k"])
    if s["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    comb = jnp.sum(jax.nn.one_hot(idx, s["E"]) * top[..., None], axis=1)

    def expert(wg, wu, wd):
        return mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)

    ys = jax.vmap(expert)(w["moe.w_gate"], w["moe.w_up"], w["moe.w_down"])
    return jnp.einsum("se,esd->sd", comb, ys, precision=HI)


def layer(s, group, w, x, mm):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = x + QD.attention(s, w, QD.rms(x, w["ln1.scale"], s["eps"]), mm)
    h = QD.rms(x, w["ln2.scale"], s["eps"])
    if group == "dense_blocks":
        return x + QD.swiglu(w, "mlp", h, mm)
    return x + (experts(s, w, h, mm) + QD.swiglu(w, "shared", h, mm))


embed, final, head = QD.embed, QD.final, QD.head


# -- work --------------------------------------------------------------------

def model_flops(s: dict, keys, sampled: int) -> float:
    d, H, K, hd = s["d"], s["H"], s["K"], s["hd"]
    attn = d * H * hd + 2 * d * K * hd + H * hd * d
    dense = attn + 3 * d * s["f"]
    moe = attn + d * s["E"] + 3 * d * s["fe"] * (s["k"] + s["shared"])
    per_token = s["dense"] * dense + (s["L"] - s["dense"]) * moe
    return (2.0 * per_token * np.size(keys) + QD.attn_flops(s, keys)
            + 2.0 * d * s["V"] * sampled)


def expert_ffn(s: dict, tokens: int) -> Work:
    """The routed experts' least work for ``tokens`` tokens over every
    expert layer: each token's k expert SwiGLUs, and its activations
    read and outputs written once per routed expert."""
    n = tokens * s["k"] * (s["L"] - s["dense"])
    return Work(6.0 * s["d"] * s["fe"] * n, 2.0 * s["d"] * BF16 * n)


KERNELS = dict(QD.KERNELS, expert_ffn=Kernel(
    ("moe_expert_ffn",), None,
    lambda s, served: expert_ffn(s, np.size(served.decode_keys))
    + served.per_prompt(lambda start, end: expert_ffn(s, end - start))))
