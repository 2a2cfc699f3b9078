"""A Qwen2/Qwen3 dense decoder: GQA attention with RoPE (QKV bias in
Qwen2, a per-head RMSNorm on q and k in Qwen3), a SwiGLU MLP, RMSNorm
before each, and a tied or untied LM head.  It implements the interface
of ``bench/archs/__init__.py``; every layer is one stacked group,
``blocks``.

Reference, per layer (pre-norm, as ``Qwen2DecoderLayer`` /
``Qwen3DecoderLayer``), written from the published equations:
    h = RMSNorm(x); q,k,v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
    [Qwen3] q,k = RMSNorm_head(q), RMSNorm_head(k)
    q,k = RoPE(q), RoPE(k)  (rotate-half form, theta from the config)
    x = x + softmax(q k^T / sqrt(hd) + causal) v Wo
    x = x + W_down(silu(h2 W_gate) * h2 W_up),  h2 = RMSNorm(x)
logits = RMSNorm(x) E^T (tied) or RMSNorm(x) W_head (untied).

Work, computed from the published widths and the served lengths (the
numerators of every utilization and roofline share): a multiply-add is
2 FLOPs; a token attends to every key up to and including itself
(causal), so a token at position p attends p + 1 keys; the LM head is
counted once per sampled token; the KV cache and the activations a kernel
reads and writes are bf16 (2 bytes).

Kernels: ``decode_attn`` is the paged decode-attention kernel and marks
the decode step programs; ``prefill_attn`` is the paged prefill-attention
kernel and marks the prefill (and verify) programs.  The Pallas calls
appear among the device operations as custom-calls named after their
``pallas_call`` names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.archs import HI, QBLOCK, Kernel
from bench.work import Work, prefill_keys

BF16 = 2


def spec_of(conf: dict) -> dict:
    """The sizes and switches of a configuration file, by short name."""
    arch = conf["architecture"]
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    return dict(
        L=conf["num_hidden_layers"], d=d, H=H,
        K=conf["num_key_value_heads"],
        hd=conf.get("head_dim") or d // H,
        f=conf["intermediate_size"], V=conf["vocab_size"],
        tie=bool(conf["tie_word_embeddings"]),
        eps=float(conf["rms_norm_eps"]), theta=float(conf["rope_theta"]),
        qkv_bias=bool(arch["qkv_bias"]), qk_norm=bool(arch["qk_norm"]),
        dtype=conf["torch_dtype"])


def groups(s: dict) -> list[tuple[str, int, str]]:
    return [("blocks", s["L"], "stacked")]


def embed_leaves(s: dict) -> list[tuple]:
    """The embedding, and the head when untied.  The embedding draws with
    stddev 1/sqrt(hidden size), as a matrix read as the head: a tied
    head's logits then have unit scale, and the current token's own row
    adds only about 1/sqrt(2 L) to its own logit, so the served token
    depends on the layers and the cache, not on the token before it
    alone."""
    d, V = s["d"], s["V"]
    out = [("embed.tok", None, (V, d), 0.0, d ** -0.5)]
    if not s["tie"]:
        out.append(("embed.lm_head", None, (d, V), 0.0, d ** -0.5))
    return out


def attn_leaves(s: dict, group: str) -> list[tuple]:
    """The attention half of a layer of ``group``, with its norm."""
    d, H, K, hd = s["d"], s["H"], s["K"], s["hd"]
    g = group
    out = [
        (f"{g}.ln1.scale", g, (d,), 1.0, 0.1),
        (f"{g}.attn.wq", g, (d, H, hd), 0.0, d ** -0.5),
        (f"{g}.attn.wk", g, (d, K, hd), 0.0, d ** -0.5),
        (f"{g}.attn.wv", g, (d, K, hd), 0.0, d ** -0.5),
        (f"{g}.attn.wo", g, (H, hd, d), 0.0, (H * hd) ** -0.5),
    ]
    if s["qkv_bias"]:
        out += [(f"{g}.attn.bq", g, (H, hd), 0.0, 0.1),
                (f"{g}.attn.bk", g, (K, hd), 0.0, 0.1),
                (f"{g}.attn.bv", g, (K, hd), 0.0, 0.1)]
    if s["qk_norm"]:
        out += [(f"{g}.attn.q_norm", g, (hd,), 1.0, 0.1),
                (f"{g}.attn.k_norm", g, (hd,), 1.0, 0.1)]
    return out


def swiglu_leaves(path: str, group: str, d: int, f: int) -> list[tuple]:
    return [(f"{path}.w_gate", group, (d, f), 0.0, d ** -0.5),
            (f"{path}.w_up", group, (d, f), 0.0, d ** -0.5),
            (f"{path}.w_down", group, (f, d), 0.0, f ** -0.5)]


def leaves(s: dict) -> list[tuple[str, str | None, tuple, float, float]]:
    """(name, group, shape, mean, stddev) of every weight.  Matrices draw
    with stddev 1/sqrt(fan-in over the contracted dims); norm scales
    draw around 1 and biases around 0, so neither is a no-op."""
    d = s["d"]
    return (embed_leaves(s) + attn_leaves(s, "blocks")
            + swiglu_leaves("blocks.mlp", "blocks", d, s["f"])
            + [("blocks.ln2.scale", "blocks", (d,), 1.0, 0.1),
               ("ln_f.scale", None, (d,), 1.0, 0.1)])


def model_config(conf: dict):
    from repro.configs.base import ModelConfig
    s = spec_of(conf)
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=s["L"],
        d_model=s["d"], num_heads=s["H"], num_kv_heads=s["K"], d_ff=s["f"],
        vocab_size=s["V"], head_dim=s["hd"], qkv_bias=s["qkv_bias"],
        qk_norm=s["qk_norm"], rope_theta=s["theta"],
        tie_embeddings=s["tie"], norm_eps=s["eps"],
        param_dtype=s["dtype"], compute_dtype=s["dtype"])


# -- the reference's equations -----------------------------------------------

def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x: (S, n, hd); rotate-half RoPE at absolute positions ``pos``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(s, w, h, mm):
    """Causal GQA self-attention of one whole sequence, projected back:
    h: (S, d) normed float32, ``w`` the layer's float32 leaves."""
    S, d = h.shape
    H, K, hd = s["H"], s["K"], s["hd"]
    G = H // K
    pos = jnp.arange(S, dtype=jnp.int32)
    q = mm(h, w["attn.wq"].reshape(d, H * hd)).reshape(S, H, hd)
    k = mm(h, w["attn.wk"].reshape(d, K * hd)).reshape(S, K, hd)
    v = mm(h, w["attn.wv"].reshape(d, K * hd)).reshape(S, K, hd)
    if s["qkv_bias"]:
        q, k, v = q + w["attn.bq"], k + w["attn.bk"], v + w["attn.bv"]
    if s["qk_norm"]:
        q = rms(q, w["attn.q_norm"], s["eps"])
        k = rms(k, w["attn.k_norm"], s["eps"])
    q, k = rope(q, pos, s["theta"]), rope(k, pos, s["theta"])
    qg = q.reshape(S // QBLOCK, QBLOCK, K, G, hd)

    def attend(args):
        qb, q0 = args                                   # (QB, K, G, hd)
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) / np.sqrt(hd)
        qpos = q0 + jnp.arange(QBLOCK)
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    starts = jnp.arange(S // QBLOCK, dtype=jnp.int32) * QBLOCK
    att = jax.lax.map(attend, (qg, starts)).reshape(S, H * hd)
    return mm(att, w["attn.wo"].reshape(H * hd, d))


def swiglu(w, path, h, mm):
    g = mm(h, w[f"{path}.w_gate"])
    u = mm(h, w[f"{path}.w_up"])
    return mm(jax.nn.silu(g) * u, w[f"{path}.w_down"])


def layer(s, group, w, x, mm):
    """One decoder layer over one whole sequence x: (S, d) float32."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = x + attention(s, w, rms(x, w["ln1.scale"], s["eps"]), mm)
    return x + swiglu(w, "mlp", rms(x, w["ln2.scale"], s["eps"]), mm)


def embed(s, g, toks):
    return g["embed.tok"][toks].astype(jnp.float32)


def final(s, g, h):
    return rms(h, g["ln_f.scale"].astype(jnp.float32), s["eps"])


def head(s, g):
    wh = g["embed.tok"].T if s["tie"] else g["embed.lm_head"]
    return wh.astype(jnp.float32)


# -- work --------------------------------------------------------------------

def layer_matmul_params(s: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, H, K, hd, f = s["d"], s["H"], s["K"], s["hd"], s["f"]
    return d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * f


def attn_flops(s: dict, keys) -> float:
    """QK^T and PV over ``keys`` attended keys (summed over tokens),
    all layers."""
    return 4.0 * s["L"] * s["H"] * s["hd"] * float(np.sum(keys))


def model_flops(s: dict, keys, sampled: int) -> float:
    """Forward FLOPs of tokens that attend ``keys`` keys each (an array,
    one entry per token), with ``sampled`` of them going through the LM
    head."""
    n = np.size(keys)
    return (2.0 * s["L"] * layer_matmul_params(s) * n + attn_flops(s, keys)
            + 2.0 * s["d"] * s["V"] * sampled)


def decode_attn(s: dict, keys) -> Work:
    """The paged decode kernel's least work for decode tokens attending
    ``keys`` keys each: every live K and V row read once per token, the
    query read and the output written."""
    n = np.size(keys)
    kv = 2.0 * s["K"] * s["hd"] * BF16 * float(np.sum(keys))
    qo = 2.0 * s["H"] * s["hd"] * BF16 * n
    return Work(attn_flops(s, keys), s["L"] * (kv + qo))


def prefill_attn(s: dict, start: int, end: int, chunk: int) -> Work:
    """The paged prefill kernel's least work for rows ``start .. end-1``
    computed in chunks of ``chunk`` rows: per chunk, every K and V row up
    to the chunk's end read once, its queries read and outputs written."""
    byt = 0.0
    for c0 in range(start, end, chunk):
        c1 = min(c0 + chunk, end)
        byt += (2.0 * s["K"] * s["hd"] * BF16 * c1
                + 2.0 * s["H"] * s["hd"] * BF16 * (c1 - c0))
    return Work(attn_flops(s, prefill_keys(start, end)), s["L"] * byt)


KERNELS = {
    "decode_attn": Kernel(
        ("paged_decode_attention",), "decode",
        lambda s, served: decode_attn(s, served.decode_keys)),
    "prefill_attn": Kernel(
        ("paged_prefill_attention",), "prefill",
        lambda s, served: served.per_prompt(
            lambda start, end: prefill_attn(s, start, end, served.chunk))),
}
