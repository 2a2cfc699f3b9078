"""A DeepSeek-V3 decoder (``model_type`` ``deepseek_v3``: Moonlight,
DeepSeek-V3, Kimi-K2 and their kin) served as one chip's share of expert
parallelism: the chip holds ``n_routed_experts`` of the router's
``experts_held.of`` experts, ``experts_held.first`` onwards.  It
implements the interface of ``bench/archs/__init__.py``.  The leading
``first_k_dense_replace`` layers have a dense SwiGLU FFN and form the
program's ``dense_blocks`` (a list); the rest form ``blocks`` (stacked).

Reference, per layer (pre-norm, as ``DeepseekV3DecoderLayer``), written
from the published equations in the published, not the absorbed, form:
    h = RMSNorm(x)
    q = h Wq, per head [q_nope (qk_nope) ; q_pe (qk_rope)], q_pe = RoPE(q_pe)
    [c ; k_pe] = h Wkv_a, c = RMSNorm(c), k_pe = RoPE(k_pe) (one per token)
    [k_nope ; v] = c Wkv_b per head
    x = x + softmax([q_nope ; q_pe] . [k_nope ; k_pe] / sqrt(qk_nope +
        qk_rope) + causal) v Wo
    h2 = RMSNorm(x)
    dense layer: x = x + SwiGLU(h2)
    expert layer (sigmoid scores, ``noaux_tc``, one group):
        s = sigmoid(h2 W_router)                 over all routed experts
        e_1..e_k = the k largest of s + b       (b: the correction bias)
        w_j = scaling * s_{e_j} / sum_j s_{e_j}
        x = x + sum_{j: e_j held here} w_j SwiGLU_{e_j}(h2) + SwiGLU_shared(h2)
    logits = RMSNorm(x) W_head (untied).
RoPE rotates the halves of the rope columns as they are laid out (the
published code first de-interleaves them: with seeded weights that is a
fixed permutation of Wq's and Wkv_a's rope columns).  The experts not held
here add nothing, in the program and in the reference alike.

Work, from the published widths and the served lengths: a multiply-add
is 2 FLOPs; a token at position p attends p + 1 keys; the LM head is
counted once per sampled token; attention is counted in the absorbed
form the program serves (a query's score over a key's latent and RoPE
parts, its value read back from the latent); the routed experts are
counted at their expected share, ``k * held / of`` experts per token
(1.5 for Moonlight's 6 of 64 with 16 held), since which experts a token
chose is not known from the lengths.  Cache entries are counted as
stored: ``kv_lora_rank + qk_rope_head_dim`` lanes padded to a multiple
of 128, bf16.

Kernels: ``decode_attn`` is the paged decode-attention kernel over the
latent pool and marks the decode programs; ``prefill_attn`` the paged
prefill-attention kernel, marking the prefill (and verify) programs;
``expert_ffn`` the grouped SwiGLU over the held experts, whose work here
is the expected share without the weights' reads (the reader
``kernel.expert_ffn_roofline`` counts what the program routed instead).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.archs import HI, QBLOCK, Kernel
from bench.archs import qwen_dense as QD
from bench.work import Work, prefill_keys

BF16 = 2


def spec_of(conf: dict) -> dict:
    """The sizes and switches of a configuration file, by short name."""
    if conf.get("q_lora_rank") is not None:
        raise ValueError("deepseek_v3: a query LoRA is not served")
    if conf.get("rope_scaling"):
        raise ValueError("deepseek_v3: rope_scaling is not served")
    if (conf["scoring_func"], conf["topk_method"]) != ("sigmoid", "noaux_tc"):
        raise ValueError("deepseek_v3: only sigmoid noaux_tc routing")
    if conf["n_group"] != 1 or conf["topk_group"] != 1:
        raise ValueError("deepseek_v3: group-limited routing is not served")
    held = conf["experts_held"]
    d = conf["hidden_size"]
    return dict(
        L=conf["num_hidden_layers"], d=d, H=conf["num_attention_heads"],
        r=conf["kv_lora_rank"], dn=conf["qk_nope_head_dim"],
        dr=conf["qk_rope_head_dim"], dv=conf["v_head_dim"],
        f=conf["intermediate_size"], fe=conf["moe_intermediate_size"],
        E=held["of"], first=held["first"], held=conf["n_routed_experts"],
        k=conf["num_experts_per_tok"], shared=conf["n_shared_experts"],
        dense=conf["first_k_dense_replace"],
        norm_topk=bool(conf["norm_topk_prob"]),
        scaling=float(conf["routed_scaling_factor"]),
        V=conf["vocab_size"], tie=bool(conf["tie_word_embeddings"]),
        eps=float(conf["rms_norm_eps"]), theta=float(conf["rope_theta"]),
        dtype=conf["torch_dtype"])


def groups(s: dict) -> list[tuple[str, int, str]]:
    return [("dense_blocks", s["dense"], "list"),
            ("blocks", s["L"] - s["dense"], "stacked")]


# stddev of the correction bias: about the spread of the sigmoid scores of
# nearby experts, so it changes some choices without deciding all of them
BIAS_STD = 0.05


def attn_leaves(s: dict, group: str) -> list[tuple]:
    d, H, r = s["d"], s["H"], s["r"]
    dn, dr, dv = s["dn"], s["dr"], s["dv"]
    g = group
    return [(f"{g}.ln1.scale", g, (d,), 1.0, 0.1),
            (f"{g}.attn.wq", g, (d, H, dn + dr), 0.0, d ** -0.5),
            (f"{g}.attn.wkv_a", g, (d, r + dr), 0.0, d ** -0.5),
            (f"{g}.attn.kv_norm", g, (r,), 1.0, 0.1),
            (f"{g}.attn.wkv_b", g, (r, H, dn + dv), 0.0, r ** -0.5),
            (f"{g}.attn.wo", g, (H, dv, d), 0.0, (H * dv) ** -0.5),
            (f"{g}.ln2.scale", g, (d,), 1.0, 0.1)]


def leaves(s: dict) -> list[tuple]:
    """(name, group, shape, mean, stddev) of every weight: matrices
    N(0, 1/fan-in over the contracted dims), norm scales N(1, 0.1), the
    correction bias N(0, BIAS_STD); the held experts' stacks hold
    ``held`` experts, the router scores all ``E``."""
    d, E, fe, n = s["d"], s["E"], s["fe"], s["held"]
    out = QD.embed_leaves(s)
    for g in ("dense_blocks", "blocks"):
        out += attn_leaves(s, g)
    out += QD.swiglu_leaves("dense_blocks.mlp", "dense_blocks", d, s["f"])
    out += [("blocks.moe.router", "blocks", (d, E), 0.0, d ** -0.5),
            ("blocks.moe.router_bias", "blocks", (E,), 0.0, BIAS_STD),
            ("blocks.moe.w_gate", "blocks", (n, d, fe), 0.0, d ** -0.5),
            ("blocks.moe.w_up", "blocks", (n, d, fe), 0.0, d ** -0.5),
            ("blocks.moe.w_down", "blocks", (n, fe, d), 0.0, fe ** -0.5)]
    out += QD.swiglu_leaves("blocks.shared", "blocks", d, s["shared"] * fe)
    return out + [("ln_f.scale", None, (d,), 1.0, 0.1)]


def model_config(conf: dict):
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
    s = spec_of(conf)
    moe = MoEConfig(num_experts=s["E"], top_k=s["k"], d_ff_expert=s["fe"],
                    num_shared_experts=s["shared"], d_ff_shared=s["fe"],
                    first_k_dense=s["dense"], d_ff_dense=s["f"],
                    norm_topk_prob=s["norm_topk"], scoring="sigmoid",
                    router_bias=True, routed_scaling=s["scaling"],
                    held=(s["first"], s["held"]))
    mla = MLAConfig(kv_lora_rank=s["r"], qk_nope_head_dim=s["dn"],
                    qk_rope_head_dim=s["dr"], v_head_dim=s["dv"])
    return ModelConfig(
        name=conf["name"], family="moe", num_layers=s["L"], d_model=s["d"],
        num_heads=s["H"], num_kv_heads=s["H"], d_ff=s["fe"],
        vocab_size=s["V"], rope_theta=s["theta"], tie_embeddings=s["tie"],
        norm_eps=s["eps"], moe=moe, mla=mla, param_dtype=s["dtype"],
        compute_dtype=s["dtype"])


# -- the reference's equations -----------------------------------------------

def attention(s, w, h, mm):
    """Causal latent attention of one whole sequence in the published
    form, projected back: h: (S, d) normed float32."""
    S, d = h.shape
    H, r, dn, dr, dv = s["H"], s["r"], s["dn"], s["dr"], s["dv"]
    pos = jnp.arange(S, dtype=jnp.int32)
    q = mm(h, w["attn.wq"].reshape(d, H * (dn + dr))).reshape(S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], QD.rope(q[..., dn:], pos, s["theta"])],
                        -1)
    kv = mm(h, w["attn.wkv_a"])                                 # (S, r+dr)
    c = QD.rms(kv[:, :r], w["attn.kv_norm"], s["eps"])
    k_pe = QD.rope(kv[:, None, r:], pos, s["theta"])            # (S, 1, dr)
    kvb = mm(c, w["attn.wkv_b"].reshape(r, H * (dn + dv))).reshape(
        S, H, dn + dv)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(k_pe, (S, H, dr))],
                        -1)
    v = kvb[..., dn:]
    qb = q.reshape(S // QBLOCK, QBLOCK, H, dn + dr)

    def attend(args):
        qq, q0 = args                                   # (QB, H, dn + dr)
        sc = jnp.einsum("qhd,shd->hqs", qq, k, precision=HI) / np.sqrt(
            dn + dr)
        qpos = q0 + jnp.arange(QBLOCK)
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqs,shd->qhd", p, v, precision=HI)

    starts = jnp.arange(S // QBLOCK, dtype=jnp.int32) * QBLOCK
    att = jax.lax.map(attend, (qb, starts)).reshape(S, H * dv)
    return mm(att, w["attn.wo"].reshape(H * dv, d))


def route(s, w, h, mm):
    """Sigmoid scores with the correction bias steering the choice:
    returns the (S, k) chosen experts and their weights."""
    sc = jax.nn.sigmoid(mm(h, w["moe.router"]))                 # (S, E)
    _, idx = jax.lax.top_k(sc + w["moe.router_bias"], s["k"])
    top = jnp.take_along_axis(sc, idx, axis=-1)
    if s["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return idx, s["scaling"] * top


def experts(s, w, h, mm):
    """The held experts' share of one sequence h: (S, d) float32; a choice
    of an expert held elsewhere adds nothing."""
    idx, top = route(s, w, h, mm)
    comb = jnp.sum(jax.nn.one_hot(idx - s["first"], s["held"])
                   * top[..., None], axis=1)                    # (S, held)

    def expert(wg, wu, wd):
        return mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)

    ys = jax.vmap(expert)(w["moe.w_gate"], w["moe.w_up"], w["moe.w_down"])
    return jnp.einsum("se,esd->sd", comb, ys, precision=HI)


def layer(s, group, w, x, mm):
    """One decoder layer over one whole sequence x: (S, d) float32."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = x + attention(s, w, QD.rms(x, w["ln1.scale"], s["eps"]), mm)
    h = QD.rms(x, w["ln2.scale"], s["eps"])
    if group == "dense_blocks":
        return x + QD.swiglu(w, "mlp", h, mm)
    return x + (experts(s, w, h, mm) + QD.swiglu(w, "shared", h, mm))


embed, final, head = QD.embed, QD.final, QD.head


# -- work --------------------------------------------------------------------

def entry_lanes(s: dict) -> int:
    """Lanes of a latent cache entry as stored (padded to 128)."""
    return -(-(s["r"] + s["dr"]) // 128) * 128


def attn_flops(s: dict, keys) -> float:
    """Scores over the latent and RoPE parts and values read from the
    latent, over ``keys`` attended keys (summed over tokens), all
    layers."""
    per_key = 2 * s["H"] * (s["r"] + s["dr"]) + 2 * s["H"] * s["r"]
    return float(s["L"] * per_key) * float(np.sum(keys))


def attn_params(s: dict) -> int:
    """Weights a token multiplies through in one layer's attention, in
    the absorbed form: its query and latent projections, the query taken
    into the latent space, the values read out, and Wo."""
    d, H, r, dn, dr, dv = s["d"], s["H"], s["r"], s["dn"], s["dr"], s["dv"]
    return d * H * (dn + dr) + d * (r + dr) + H * dn * r + H * r * dv \
        + H * dv * d


def model_flops(s: dict, keys, sampled: int) -> float:
    """Forward FLOPs of tokens that attend ``keys`` keys each, ``sampled``
    of them through the LM head; routed experts at their expected share
    here, ``k * held / E`` per token."""
    d, fe = s["d"], s["fe"]
    routed = s["k"] * s["held"] / s["E"]
    dense = attn_params(s) + 3 * d * s["f"]
    moe = attn_params(s) + d * s["E"] + 3 * d * fe * (routed + s["shared"])
    per_token = s["dense"] * dense + (s["L"] - s["dense"]) * moe
    return (2.0 * per_token * np.size(keys) + attn_flops(s, keys)
            + 2.0 * d * s["V"] * sampled)


def decode_attn(s: dict, keys) -> Work:
    """The latent decode kernel's least work for decode tokens attending
    ``keys`` keys each: every live entry read once per token, as stored,
    the queries read and the attended latents written."""
    n = np.size(keys)
    W = entry_lanes(s)
    kv = W * BF16 * float(np.sum(keys))
    qo = s["H"] * (W + s["r"]) * BF16 * n
    return Work(attn_flops(s, keys), s["L"] * (kv + qo))


def prefill_attn(s: dict, start: int, end: int, chunk: int) -> Work:
    """The latent prefill kernel's least work for rows ``start .. end-1``
    computed in chunks of ``chunk`` rows: per chunk, every entry up to the
    chunk's end read once, its queries read and latents written."""
    W = entry_lanes(s)
    byt = 0.0
    for c0 in range(start, end, chunk):
        c1 = min(c0 + chunk, end)
        byt += W * BF16 * c1 + s["H"] * (W + s["r"]) * BF16 * (c1 - c0)
    return Work(attn_flops(s, prefill_keys(start, end)), s["L"] * byt)


def expert_ffn(s: dict, tokens: int) -> Work:
    """The held experts' expected work for ``tokens`` tokens over every
    expert layer: each token's expected held choices, their SwiGLU, and
    activations read and outputs written once per choice.  The weights'
    reads depend on the steps, which the lengths do not give: the reader
    ``kernel.expert_ffn_roofline`` takes them from the program's
    counters."""
    n = tokens * s["k"] * s["held"] / s["E"] * (s["L"] - s["dense"])
    return Work(6.0 * s["d"] * s["fe"] * n, 2.0 * s["d"] * BF16 * n)


KERNELS = {
    "decode_attn": Kernel(
        ("paged_decode_attention",), "decode",
        lambda s, served: decode_attn(s, served.decode_keys)),
    "prefill_attn": Kernel(
        ("paged_prefill_attention",), "prefill",
        lambda s, served: served.per_prompt(
            lambda start, end: prefill_attn(s, start, end, served.chunk))),
    "expert_ffn": Kernel(
        ("moe_expert_ffn",), None,
        lambda s, served: expert_ffn(s, np.size(served.decode_keys))
        + served.per_prompt(lambda start, end: expert_ffn(s, end - start))),
}
