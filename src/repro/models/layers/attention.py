"""Attention: GQA/MHA with RoPE / M-RoPE, qk-norm, bias options, and a
memory-efficient chunked online-softmax core.

The chunked core (`chunked_attention`) is the pure-jnp oracle shared by the
Pallas flash kernels (`repro.kernels.flash_attention` / `decode_attention`);
it scans KV blocks carrying (max, sum, acc) so the S x S score matrix is never
materialized — this is what makes 32k prefill lowering memory-sane.

Decode against a sequence-sharded KV cache uses the LSE-merge path in
`repro.distributed.collectives` built on the `return_residuals=True` output.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.layers.module import bias, scale, weight
from repro.models.layers.norms import head_rmsnorm
from repro.models.layers.rope import apply_m_rope, apply_rope

NEG_INF = -1e30


def attention_table(cfg, d_model: int | None = None):
    """Parameter table for one attention block."""
    if cfg.mla is not None:
        return mla_table(cfg)
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    # 1/sqrt(fan_in) over the contracted dims: the default would take the
    # fan-in from the heads axis, making scores hot enough that the
    # softmax picks single keys and rounding flips outputs layer by layer
    s_in, s_out = d ** -0.5, (cfg.num_heads * hd) ** -0.5
    t = {
        "wq": weight((d, cfg.num_heads, hd), ("embed", "heads", None), s_in),
        "wk": weight((d, cfg.num_kv_heads, hd), ("embed", "kv_heads", None),
                     s_in),
        "wv": weight((d, cfg.num_kv_heads, hd), ("embed", "kv_heads", None),
                     s_in),
        "wo": weight((cfg.num_heads, hd, d), ("heads", None, "embed"), s_out),
    }
    if cfg.qkv_bias:
        t["bq"] = bias((cfg.num_heads, hd), ("heads", None))
        t["bk"] = bias((cfg.num_kv_heads, hd), ("kv_heads", None))
        t["bv"] = bias((cfg.num_kv_heads, hd), ("kv_heads", None))
    if cfg.qk_norm:
        t["q_norm"] = scale((hd,), (None,))
        t["k_norm"] = scale((hd,), (None,))
    return t


def mla_table(cfg):
    """Latent attention (DeepSeek-V2/V3, no query LoRA): ``wq`` gives each
    head a ``qk_nope``-wide query and a ``qk_rope``-wide RoPE query;
    ``wkv_a`` gives the token's latent and its RoPE key, shared by every
    head; ``kv_norm`` scales the normed latent; ``wkv_b`` reads each head's
    key (``qk_nope`` wide) and value (``v_head_dim``) back from it."""
    m, d, H = cfg.mla, cfg.d_model, cfg.num_heads
    r, dn, dv = m.kv_lora_rank, m.qk_nope_head_dim, m.v_head_dim
    return {
        "wq": weight((d, H, m.qk_head_dim), ("embed", "heads", None),
                     d ** -0.5),
        "wkv_a": weight((d, m.latent_dim), ("embed", None), d ** -0.5),
        "kv_norm": scale((r,), (None,)),
        "wkv_b": weight((r, H, dn + dv), (None, "heads", None), r ** -0.5),
        "wo": weight((H, dv, d), ("heads", None, "embed"), (H * dv) ** -0.5),
    }


def mla_entry_dim(cfg) -> int:
    """Lanes of a latent cache entry as stored: the latent and the RoPE
    key, zero-padded to a multiple of 128 lanes (Mosaic copies a page only
    in whole 128-lane tiles; the chip's tiled layout pads the row to that
    width in HBM anyway)."""
    return -(-cfg.mla.latent_dim // 128) * 128


def mla_scale(cfg) -> float:
    """Softmax scale: one over the root of a head's whole query width."""
    return cfg.mla.qk_head_dim ** -0.5


def mla_project(cfg, params, x: jax.Array, positions: jax.Array):
    """x: (B, S, D) -> q_nope (B, S, H, dn), q_pe (B, S, H, dr) roped,
    c (B, S, r) the normed latent, k_pe (B, S, 1, dr) the roped shared key.
    RoPE rotates the halves of the rope columns as they are laid out."""
    m, dt = cfg.mla, x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    q_nope, q_pe = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    kv = jnp.einsum("bsd,dk->bsk", x, params["wkv_a"].astype(dt))
    c = head_rmsnorm(params["kv_norm"], kv[..., :m.kv_lora_rank],
                     cfg.norm_eps)
    k_pe = kv[..., None, m.kv_lora_rank:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    k_pe = apply_rope(k_pe, positions, cfg.rope_theta)
    return q_nope, q_pe, c, k_pe


def mla_attention(cfg, params, x: jax.Array, positions: jax.Array, *,
                  chunk: int = 1024) -> jax.Array:
    """The published form over a whole sequence: every head's key
    ``[c W_kvb,k ; k_pe]`` and value ``c W_kvb,v`` made explicit, then
    causal attention.  Returns the heads' values (B, S, H, v_head_dim)."""
    m = cfg.mla
    q_nope, q_pe, c, k_pe = mla_project(cfg, params, x, positions)
    kvb = jnp.einsum("bsr,rhk->bshk", c, params["wkv_b"].astype(c.dtype))
    k_nope, v = kvb[..., :m.qk_nope_head_dim], kvb[..., m.qk_nope_head_dim:]
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:3] + k_pe.shape[3:])],
        -1)
    return chunked_attention(q, k, v, causal=True, q_positions=positions,
                             kv_positions=positions, chunk=chunk)


def mla_absorb(cfg, params, q_nope, q_pe, c, k_pe):
    """The absorbed form's query and cache entry.  Each head's no-RoPE
    query is taken into the latent space, ``q_lat = q_nope W_kvb,k^T``, so
    its score against a token is ``q_lat . c + q_pe . k_pe`` over the
    token's one entry ``[c ; k_pe]``.  Returns the queries (B, S, H, W)
    and the entries (B, S, 1, W), both zero-padded to the stored width W
    (:func:`mla_entry_dim`)."""
    m = cfg.mla
    w = mla_entry_dim(cfg)
    wk = params["wkv_b"][..., :m.qk_nope_head_dim].astype(q_nope.dtype)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wk)
    pad = [(0, 0)] * 3 + [(0, w - m.latent_dim)]
    q = jnp.pad(jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], -1), pad)
    e = jnp.pad(jnp.concatenate([c[:, :, None], k_pe.astype(c.dtype)], -1),
                pad)
    return q, e


def mla_values(cfg, params, o_lat: jax.Array) -> jax.Array:
    """Attention over the latents, (B, S, H, kv_lora_rank), read out as
    each head's value, (B, S, H, v_head_dim)."""
    wv = params["wkv_b"][..., cfg.mla.qk_nope_head_dim:].astype(o_lat.dtype)
    return jnp.einsum("bshr,rhk->bshk", o_lat, wv)


def cross_attention_table(cfg, d_model: int | None = None):
    """Cross-attention (enc-dec): same shape family, separate KV source."""
    return attention_table(cfg, d_model)


def qkv_project(cfg, params, x: jax.Array,
                positions: jax.Array | None) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D) -> q (B, S, H, hd), k/v (B, S, K, hd), RoPE applied."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    if cfg.qk_norm:
        q = head_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = head_rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if positions is not None:
        if cfg.m_rope:
            q = apply_m_rope(q, positions, cfg.rope_theta, cfg.m_rope_sections)
            k = apply_m_rope(k, positions, cfg.rope_theta, cfg.m_rope_sections)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)
    return q, k, v


class AttnResiduals(NamedTuple):
    """Per-query-row log-sum-exp residuals for distributed (LSE) merging."""
    out: jax.Array   # (B, Sq, H, D) un-normalized accumulator / or normalized
    m: jax.Array     # (B, H, Sq) running max
    l: jax.Array     # (B, H, Sq) running sum


def _mask_bias(q_pos, kv_pos, *, causal: bool, window: int,
               kv_len=None) -> jax.Array:
    """Additive mask bias (..., Sq, C) in fp32; 0 where attended."""
    # q_pos: (B, Sq); kv_pos: (C,) or (B, C)
    if kv_pos.ndim == 1:
        kv = kv_pos[None, None, :]
    else:
        kv = kv_pos[:, None, :]
    qp = q_pos[:, :, None]
    allowed = jnp.ones(jnp.broadcast_shapes(qp.shape, kv.shape), bool)
    if causal:
        allowed &= kv <= qp
    if window:
        allowed &= kv > qp - window
    if kv_len is not None:
        allowed &= kv < kv_len[:, None, None]
    return jnp.where(allowed, 0.0, NEG_INF).astype(jnp.float32)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True,
                      q_positions: jax.Array | None = None,
                      kv_positions: jax.Array | None = None,
                      kv_len: jax.Array | None = None,
                      softcap: float = 0.0,
                      window: int = 0,
                      chunk: int = 1024,
                      scale: float | None = None,
                      return_residuals: bool = False):
    """Online-softmax attention, scanning KV in chunks.

    Args:
      q: (B, Sq, H, D); k: (B, Skv, K, D), v: (B, Skv, K, Dv) with
        H % K == 0 (GQA).
      scale: the softmax scale (default 1 / sqrt(D)).
      q_positions: (B, Sq) absolute positions (defaults to arange).
      kv_positions: (B, Skv) or (Skv,) absolute positions of cache slots.
      kv_len: (B,) valid cache length per sequence (decode masking).
      return_residuals: also return (m, l) LSE stats for distributed merge.

    Returns:
      out (B, Sq, H, Dv) [, AttnResiduals].
    """
    B, Sq, H, D = q.shape
    _, Skv, K, _ = k.shape
    Dv = v.shape[-1]
    G = H // K
    scale_ = 1.0 / math.sqrt(D) if scale is None else scale
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32), (B, Sq))
    if kv_positions is None:
        kv_positions = jnp.arange(Skv, dtype=jnp.int32)

    chunk = min(chunk, Skv)
    n_chunks = math.ceil(Skv / chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # Padded slots get a huge positive position: masked by causality and
        # by any kv_len bound; for the non-causal/no-len case we add a bound.
        if kv_positions.ndim == 1:
            kv_positions = jnp.pad(kv_positions, (0, pad), constant_values=10**9)
        else:
            kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                                   constant_values=10**9)
        if kv_len is None and not causal:
            kv_len = jnp.full((B,), Skv, jnp.int32)

    qg = q.reshape(B, Sq, K, G, D)

    def seg(arr, i):
        return jax.lax.dynamic_slice_in_dim(arr, i * chunk, chunk,
                                            axis=1 if arr.ndim > 1 else 0)

    def body(carry, i):
        m, l, acc = carry
        k_c = seg(k, i)                                   # (B, C, K, D)
        v_c = seg(v, i)
        kp_c = seg(kv_positions, i)                       # (C,) or (B, C)
        s = jnp.einsum("bqkgd,bckd->bkgqc", qg, k_c).astype(jnp.float32)
        s = s * scale_
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        mb = _mask_bias(q_positions, kp_c, causal=causal, window=window,
                        kv_len=kv_len)                    # (B, Sq, C)
        s = s + mb[:, None, None, :, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))       # (B, K, G, Sq)
        # Guard fully-masked rows: keep m finite so exp() stays 0, not nan.
        m_safe = jnp.maximum(m_new, NEG_INF / 2)
        p = jnp.exp(s - m_safe[..., None])                # (B, K, G, Sq, C)
        corr = jnp.exp(jnp.clip(m - m_new, None, 0.0))
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(v_c.dtype), v_c)
        acc = acc * corr[..., None].astype(acc.dtype) + pv.astype(jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, K, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, K, G, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  jnp.arange(n_chunks, dtype=jnp.int32))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.reshape(B, K * G, Sq, Dv).transpose(0, 2, 1, 3).astype(q.dtype)
    if return_residuals:
        res = AttnResiduals(out=out,
                            m=m.reshape(B, H, Sq), l=l.reshape(B, H, Sq))
        return out, res
    return out


def merge_lse(parts: list[AttnResiduals]) -> jax.Array:
    """Merge attention partials computed over disjoint KV shards.

    Each part's `out` is already normalized by its local `l`; we re-weight by
    softmax-consistent factors: w_i = l_i * exp(m_i - m*) / sum_j l_j exp(...).
    """
    m_star = parts[0].m
    for p in parts[1:]:
        m_star = jnp.maximum(m_star, p.m)
    num = 0.0
    den = 0.0
    for p in parts:
        w = p.l * jnp.exp(jnp.clip(p.m - m_star, None, 0.0))   # (B, H, Sq)
        num = num + p.out.astype(jnp.float32) * w.transpose(0, 2, 1)[..., None]
        den = den + w.transpose(0, 2, 1)[..., None]
    return (num / jnp.maximum(den, 1e-30)).astype(parts[0].out.dtype)


def attn_output(cfg, params, attn: jax.Array) -> jax.Array:
    """attn: (B, S, H, hd) -> (B, S, D)."""
    out = jnp.einsum("bshk,hkd->bsd", attn, params["wo"].astype(attn.dtype))
    return constrain(out, "batch", "seq", "embed_act")


def self_attention(cfg, params, x: jax.Array, positions: jax.Array,
                   *, causal: bool = True, chunk: int = 1024) -> jax.Array:
    """Full-sequence self-attention (train / prefill), no cache."""
    q, k, v = qkv_project(cfg, params, x, positions)
    pos1d = positions[0] if cfg.m_rope else positions  # mask uses temporal ids
    out = chunked_attention(q, k, v, causal=causal,
                            q_positions=pos1d, kv_positions=pos1d,
                            softcap=cfg.attn_logit_softcap,
                            window=cfg.sliding_window, chunk=chunk)
    return attn_output(cfg, params, out)
