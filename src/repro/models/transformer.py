"""Decoder-only transformer LM (dense, MoE, parallel-block, M-RoPE variants).

Homogeneous layer stacks are `lax.scan`-ed over stacked parameters so HLO
size is O(1) in depth (llama3-405b's 126 layers compile as one body).
Heterogeneous prefixes (DeepSeekMoE's first-k dense layers) are unrolled.

Entry points:
  * ``forward``       — full-sequence logits (training).
  * ``prefill``       — logits at the last position + filled KV cache.
  * ``prefill_paged`` — one prompt chunk written *directly* into paged
    pool blocks (no dense bucket cache + scatter round-trip), attending
    over already-seeded blocks, so shared prefixes and resumed histories
    are never recomputed.
  * ``decode_step``   — one token against a KV cache (serving).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.common import dtype_of, split_keys
from repro.distributed.sharding import constrain
from repro.models.layers import attention as A
from repro.models.layers import moe as MOE
from repro.models.layers.embedding import embed, embedding_table, logits as lm_logits
from repro.models.layers.mlp import swiglu, swiglu_table
from repro.models.layers.module import init_table, stack_table
from repro.models.layers.norms import apply_norm, norm_table


class KVCache(NamedTuple):
    """Stacked per-layer KV cache. k/v: (L, B, S, K, D); length: (B,)."""
    k: jax.Array
    v: jax.Array
    length: jax.Array

    @property
    def max_len(self) -> int:
        return self.k.shape[2]


class QuantKVCache(NamedTuple):
    """int8 KV cache [beyond-paper]: values quantized per (slot, kv-head)
    with absmax scales — halves cache HBM footprint and read traffic vs
    bf16 (the paper's FP16-is-safe finding pushed one step further).
    k/v: (L, B, S, K, D) int8; k_scale/v_scale: (L, B, S, K) f32."""
    k: jax.Array
    v: jax.Array
    k_scale: jax.Array
    v_scale: jax.Array
    length: jax.Array

    @property
    def max_len(self) -> int:
        return self.k.shape[2]


class PagedKVCache(NamedTuple):
    """Paged KV cache: one global pool of fixed-size blocks shared by every
    decode slot, indexed through per-slot block tables (vLLM-style).

    k/v: (L, N_blocks, K, block_size, D) — each (block, kv-head) holds a
    (block_size, D) tile, the unit the Pallas kernels DMA; block 0 is the
    trash block that retired slots write into; block_tables: (B,
    max_blocks) physical block id per logical block, 0 where unassigned;
    length: (B,) valid KV rows.
    """
    k: jax.Array
    v: jax.Array
    block_tables: jax.Array
    length: jax.Array

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def max_len(self) -> int:
        """Max addressable rows per sequence (table width x block size)."""
        return self.block_tables.shape[1] * self.k.shape[3]


class QuantPagedKVCache(NamedTuple):
    """int8 variant of :class:`PagedKVCache`: pools are int8 with absmax
    scales per (block, kv-head, row).  k/v: (L, N, K, bs, D) int8;
    k_scale/v_scale: (L, N, K, bs) f32."""
    k: jax.Array
    v: jax.Array
    k_scale: jax.Array
    v_scale: jax.Array
    block_tables: jax.Array
    length: jax.Array

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def max_len(self) -> int:
        return self.block_tables.shape[1] * self.k.shape[3]


def quantize_kv(x: jax.Array):
    """x: (..., D) -> (int8 (..., D), scale (...,) f32)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def make_cache(cfg, batch: int, max_len: int, dtype="bfloat16",
               num_layers: int | None = None,
               length: jax.Array | None = None):
    L = num_layers if num_layers is not None else cfg.num_layers
    hd = cfg.resolved_head_dim
    shape = (L, batch, max_len, cfg.num_kv_heads, hd)
    ln = jnp.zeros((batch,), jnp.int32) if length is None else length
    if dtype == "int8":
        return QuantKVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32), length=ln)
    dt = dtype_of(dtype)
    return KVCache(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt), length=ln)


def make_paged_cache(cfg, num_blocks: int, block_size: int, batch: int,
                     max_blocks: int, dtype="bfloat16",
                     num_layers: int | None = None):
    """Paged cache sized to ``num_blocks`` pool blocks (incl. trash block 0)
    with ``batch`` block tables of ``max_blocks`` entries each."""
    L = num_layers if num_layers is not None else cfg.num_layers
    hd = cfg.resolved_head_dim
    shape = (L, num_blocks, cfg.num_kv_heads, block_size, hd)
    tables = jnp.zeros((batch, max_blocks), jnp.int32)
    ln = jnp.zeros((batch,), jnp.int32)
    if dtype == "int8":
        return QuantPagedKVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros(shape[:-1], jnp.float32),
            v_scale=jnp.zeros(shape[:-1], jnp.float32),
            block_tables=tables, length=ln)
    dt = dtype_of(dtype)
    return PagedKVCache(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt),
                        block_tables=tables, length=ln)


def scatter_prefill_blocks(cache, dense: KVCache, ids: jax.Array):
    """Write a batch-1 dense prefill cache into pool blocks ``ids``.

    dense.k/v: (L, 1, S, K, D) with S a multiple of the pool block size;
    ids: (S // block_size,) physical block ids in logical order (entries
    past the prompt's blocks point at the trash block 0, so bucket padding
    rows land in trash).  Returns the cache with the pools updated.
    """
    L, N, K, bs, D = cache.k.shape
    S = dense.k.shape[2]
    nb = S // bs
    kb = dense.k[:, 0].reshape(L, nb, bs, K, D).swapaxes(2, 3)
    vb = dense.v[:, 0].reshape(L, nb, bs, K, D).swapaxes(2, 3)
    if isinstance(cache, QuantPagedKVCache):
        kq, ksc = quantize_kv(kb)
        vq, vsc = quantize_kv(vb)
        return cache._replace(
            k=cache.k.at[:, ids].set(kq), v=cache.v.at[:, ids].set(vq),
            k_scale=cache.k_scale.at[:, ids].set(ksc),
            v_scale=cache.v_scale.at[:, ids].set(vsc))
    return cache._replace(k=cache.k.at[:, ids].set(kb.astype(cache.k.dtype)),
                          v=cache.v.at[:, ids].set(vb.astype(cache.v.dtype)))


# ---------------------------------------------------------------------------
# parameter tables
# ---------------------------------------------------------------------------

def _ffn_table(cfg):
    """Dense FFN or MoE table for one block."""
    if cfg.moe is None:
        return {"mlp": swiglu_table(cfg.d_model, cfg.d_ff)}
    m = cfg.moe
    t = {"moe": MOE.moe_table(cfg.d_model, m.num_experts, m.d_ff_expert)}
    if m.num_shared_experts:
        t["shared"] = swiglu_table(cfg.d_model,
                                   m.num_shared_experts * m.d_ff_shared)
    return t


def block_table(cfg, *, dense_ffn: bool = False):
    t = {"ln1": norm_table(cfg), "attn": A.attention_table(cfg)}
    if dense_ffn:
        ffn = {"mlp": swiglu_table(cfg.d_model,
                                   (cfg.moe.d_ff_dense or cfg.d_ff)
                                   if cfg.moe else cfg.d_ff)}
    else:
        ffn = _ffn_table(cfg)
    t.update(ffn)
    if not cfg.parallel_block:
        t["ln2"] = norm_table(cfg)
    return t


def lm_table(cfg):
    m = cfg.moe
    first_k = m.first_k_dense if m else 0
    t = {
        "embed": embedding_table(cfg.vocab_size, cfg.d_model, cfg.tie_embeddings),
        "blocks": stack_table(block_table(cfg), cfg.num_layers - first_k),
        "ln_f": norm_table(cfg),
    }
    if first_k:
        t["dense_blocks"] = [block_table(cfg, dense_ffn=True)
                             for _ in range(first_k)]
    return t


def init(cfg, key: jax.Array):
    return init_table(key, lm_table(cfg), cfg.param_dtype)


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _ffn_apply(cfg, p, h):
    """FFN half of a block. Returns (out, aux_loss)."""
    if cfg.moe is None or "moe" not in p:
        return swiglu(p["mlp"], h), jnp.zeros((), jnp.float32)
    m = cfg.moe
    idx, prob, aux = MOE.route(m, p["moe"], h)
    out = MOE.moe_apply(m, p["moe"], h, idx, prob)
    if m.num_shared_experts:
        out = out + swiglu(p["shared"], h)
    return out, aux


def _paged_attend(cfg, q, k_new, v_new, pool_k, pool_v, scales,
                  block_tables, length, chunk):
    """Paged decode attention for one layer: write the new KV row into the
    block-table-addressed pool slot, then attend over live blocks only.

    q/k_new/v_new: (B, 1, H|K, D); pool_k/pool_v: (N, K, bs, D) this
    layer's slice of the global pool; block_tables: (B, max_blocks);
    length: (B,) rows already valid (the new row is written at ``length``).
    Retired slots have all-zero tables, so their writes land in the trash
    block and never corrupt blocks reused by live requests.
    """
    from repro.kernels.decode_attention.ops import paged_decode_attention
    N, K, bs, D = pool_k.shape
    B = q.shape[0]
    mb = block_tables.shape[1]
    bi = jnp.clip(length // bs, 0, mb - 1)
    bt = block_tables[jnp.arange(B), bi]            # physical block per seq
    off = length % bs
    row_k, row_v = k_new[:, 0], v_new[:, 0]
    if scales is not None:
        k_scale, v_scale = scales
        kq, ks = quantize_kv(row_k)
        vq, vs = quantize_kv(row_v)
        nk = pool_k.at[bt, :, off].set(kq)
        nv = pool_v.at[bt, :, off].set(vq)
        nks = k_scale.at[bt, :, off].set(ks)
        nvs = v_scale.at[bt, :, off].set(vs)
        out = paged_decode_attention(
            q[:, 0], nk, nv, block_tables, length + 1,
            k_scale=nks, v_scale=nvs, softcap=cfg.attn_logit_softcap,
            chunk=chunk)
        return out[:, None], (nk, nv, nks, nvs)
    nk = pool_k.at[bt, :, off].set(row_k.astype(pool_k.dtype))
    nv = pool_v.at[bt, :, off].set(row_v.astype(pool_v.dtype))
    out = paged_decode_attention(q[:, 0], nk, nv, block_tables, length + 1,
                                 softcap=cfg.attn_logit_softcap, chunk=chunk)
    return out[:, None], (nk, nv)


def _paged_prefill_attend(cfg, q, k_new, v_new, pool_k, pool_v, scales,
                          write_ids, table, q_start, kv_len, chunk):
    """Paged prefill for one layer: write the chunk's KV rows directly
    into pool blocks, then attend causally over the table's blocks.

    q/k_new/v_new: (1, C, H|K, D) with C a multiple of the pool block
    size; write_ids: (C // bs,) physical block per chunk block (trash 0
    for rows that must not land anywhere — bucket padding, and the
    recompute-baseline's shared prefix); table: (1, max_blocks) read
    table; q_start: (1,) absolute position of the chunk's first row;
    kv_len: (1,) valid rows incl. this chunk.  Seeded blocks (shared
    prefix, resumed history) are attended without being recomputed —
    causality against absolute positions does the masking.

    ``write_ids=None`` switches to the *verify* write layout (speculative
    decoding): q/k_new/v_new are (B, C) candidate rows starting at an
    arbitrary in-block offset ``q_start`` per sequence, so instead of
    whole-block writes each row is scattered individually through
    ``table`` — row ``q_start + j`` lands at block ``table[b, pos // bs]``
    offset ``pos % bs``.  Padding sequences carry all-trash tables, so
    their rows (and any duplicate trash hits) are harmless garbage.
    """
    from repro.kernels.prefill_attention.ops import paged_prefill_attention
    N, K, bs, D = pool_k.shape
    C = q.shape[1]
    if write_ids is None:
        B = q.shape[0]
        mb = table.shape[1]
        pos = q_start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        bi = jnp.clip(pos // bs, 0, mb - 1)
        bt = jnp.take_along_axis(table, bi, axis=1)     # (B, C) physical
        off = pos % bs
        if scales is not None:
            k_scale, v_scale = scales
            kq, ksc = quantize_kv(k_new)
            vq, vsc = quantize_kv(v_new)
            nk = pool_k.at[bt, :, off].set(kq)
            nv = pool_v.at[bt, :, off].set(vq)
            nks = k_scale.at[bt, :, off].set(ksc)
            nvs = v_scale.at[bt, :, off].set(vsc)
            out = paged_prefill_attention(
                q, nk, nv, table, q_start, kv_len, k_scale=nks, v_scale=nvs,
                softcap=cfg.attn_logit_softcap, chunk=chunk)
            return out, (nk, nv, nks, nvs)
        nk = pool_k.at[bt, :, off].set(k_new.astype(pool_k.dtype))
        nv = pool_v.at[bt, :, off].set(v_new.astype(pool_v.dtype))
        out = paged_prefill_attention(q, nk, nv, table, q_start, kv_len,
                                      softcap=cfg.attn_logit_softcap,
                                      chunk=chunk)
        return out, (nk, nv)
    kb = k_new[0].reshape(C // bs, bs, K, D).swapaxes(1, 2)
    vb = v_new[0].reshape(C // bs, bs, K, D).swapaxes(1, 2)
    if scales is not None:
        k_scale, v_scale = scales
        kq, ksc = quantize_kv(kb)
        vq, vsc = quantize_kv(vb)
        nk = pool_k.at[write_ids].set(kq)
        nv = pool_v.at[write_ids].set(vq)
        nks = k_scale.at[write_ids].set(ksc)
        nvs = v_scale.at[write_ids].set(vsc)
        out = paged_prefill_attention(
            q, nk, nv, table, q_start, kv_len, k_scale=nks, v_scale=nvs,
            softcap=cfg.attn_logit_softcap, chunk=chunk)
        return out, (nk, nv, nks, nvs)
    nk = pool_k.at[write_ids].set(kb.astype(pool_k.dtype))
    nv = pool_v.at[write_ids].set(vb.astype(pool_v.dtype))
    out = paged_prefill_attention(q, nk, nv, table, q_start, kv_len,
                                  softcap=cfg.attn_logit_softcap,
                                  chunk=chunk)
    return out, (nk, nv)


def block_apply(cfg, p, x, positions, *,
                cache_k=None, cache_v=None, cache_scales=None, kv_len=None,
                block_tables=None, paged_prefill=None, chunk=1024):
    """One transformer block. Returns (x, aux, new_kv) where new_kv is
    (k, v) or (k, v, k_scale, v_scale) for the int8 cache.

    Without cache: full self-attention over x (train / prefill).
    With cache (decode): x is (B, 1, D); the new KV row is written at
    ``kv_len`` and attention runs over the whole cache.  With
    ``block_tables`` the cache is paged: cache_k/v are (N, K, bs, D) pool
    slices and reads gather only live blocks.  ``paged_prefill`` (a dict
    of write_ids/table/q_start/kv_len) switches the paged path to the
    multi-row chunk prefill: KV written straight into pool blocks,
    attention causal over the table's blocks.
    """
    h = apply_norm(cfg, p["ln1"], x)
    # SP boundary: norm runs on the seq-sharded carry; attention needs the
    # full sequence, so the gather happens here (post-norm, bf16).
    h = constrain(h, "batch", "seq", "embed_act")
    pos1d = positions[0] if cfg.m_rope else positions
    if cache_k is None:
        q, k, v = A.qkv_project(cfg, p["attn"], h, positions)
        attn = A.chunked_attention(
            q, k, v, causal=True, q_positions=pos1d, kv_positions=pos1d,
            softcap=cfg.attn_logit_softcap, window=cfg.sliding_window,
            chunk=chunk)
        new_kv = (k, v)
    elif block_tables is not None and paged_prefill is not None:
        q, k, v = A.qkv_project(cfg, p["attn"], h, positions)
        attn, new_kv = _paged_prefill_attend(cfg, q, k, v, cache_k, cache_v,
                                             cache_scales, chunk=chunk,
                                             **paged_prefill)
    elif block_tables is not None:
        q, k, v = A.qkv_project(cfg, p["attn"], h, positions)
        attn, new_kv = _paged_attend(cfg, q, k, v, cache_k, cache_v,
                                     cache_scales, block_tables, kv_len,
                                     chunk)
    else:
        from repro.distributed.collectives import seq_sharded_decode_attention
        q, k, v = A.qkv_project(cfg, p["attn"], h, positions)
        ks, vs = cache_scales if cache_scales is not None else (None, None)
        attn, *new_kv = seq_sharded_decode_attention(
            q, cache_k, cache_v, k, v, kv_len, k_scale=ks, v_scale=vs,
            softcap=cfg.attn_logit_softcap, chunk=chunk)
        new_kv = tuple(new_kv)
    attn = A.attn_output(cfg, p["attn"], attn)
    if cfg.parallel_block:
        ffn, aux = _ffn_apply(cfg, p, h)
        x = x + attn + ffn
    else:
        x = x + attn
        h2 = apply_norm(cfg, p["ln2"], x)
        ffn, aux = _ffn_apply(cfg, p, h2)
        x = x + ffn
    # carry leaves the block sequence-sharded (training SP; no-op otherwise)
    x = constrain(x, "batch", "seq_sp", "embed_act")
    return x, aux, new_kv


_REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


def _scan_blocks(cfg, stacked, x, positions, *, remat, cache=None,
                 collect_kv=False, paged_prefill=None, chunk=1024):
    """Scan the homogeneous block stack. Returns (x, aux_sum, (ks, vs)).

    ``collect_kv`` stacks each layer's fresh K/V as scan outputs (prefill);
    training leaves it off so no (L, B, S, K, D) buffer is ever requested.
    """

    quant = isinstance(cache, (QuantKVCache, QuantPagedKVCache))
    tables = getattr(cache, "block_tables", None)

    def body_nocache(carry, p):
        h, aux = carry
        h, a, kv = block_apply(cfg, p, h, positions, chunk=chunk)
        ys = kv if collect_kv else None
        return (h, aux + a), ys

    def body_cache(carry, layer):
        h, aux = carry
        if quant:
            p, ck, cv, ks, vs = layer
            scales = (ks, vs)
        else:
            p, ck, cv = layer
            scales = None
        h, a, kv = block_apply(cfg, p, h, positions,
                               cache_k=ck, cache_v=cv, cache_scales=scales,
                               kv_len=cache.length, block_tables=tables,
                               paged_prefill=paged_prefill, chunk=chunk)
        return (h, aux + a), kv

    body = body_cache if cache is not None else body_nocache
    if remat and cfg.remat != "none":
        policy = _REMAT_POLICIES.get(cfg.remat, _REMAT_POLICIES["full"])
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)

    carry0 = (x, jnp.zeros((), jnp.float32))
    if cache is None:
        (x, aux), ys = jax.lax.scan(body, carry0, stacked)
        kv = ys if collect_kv else None
    else:
        xs = ((stacked, cache.k, cache.v, cache.k_scale, cache.v_scale)
              if quant else (stacked, cache.k, cache.v))
        (x, aux), kv = jax.lax.scan(body, carry0, xs)
    return x, aux, kv


def _apply_backbone(cfg, params, tokens, positions, *, remat,
                    cache: KVCache | None = None, collect_kv=False,
                    paged_prefill=None, chunk=1024):
    compute_dt = dtype_of(cfg.compute_dtype)
    x = embed(params["embed"], tokens, compute_dt)
    aux_total = jnp.zeros((), jnp.float32)
    quant = isinstance(cache, (QuantKVCache, QuantPagedKVCache))
    paged = isinstance(cache, (PagedKVCache, QuantPagedKVCache))
    dense_caches = []
    n_dense = len(params.get("dense_blocks", ()))
    for i, bp in enumerate(params.get("dense_blocks", ())):
        ck = cv = scales = tables = None
        kl = None
        if cache is not None:
            ck, cv, kl = cache.k[i], cache.v[i], cache.length
            if quant:
                scales = (cache.k_scale[i], cache.v_scale[i])
            if paged:
                tables = cache.block_tables
        x, a, kv = block_apply(cfg, bp, x, positions,
                               cache_k=ck, cache_v=cv, cache_scales=scales,
                               kv_len=kl, block_tables=tables,
                               paged_prefill=paged_prefill, chunk=chunk)
        aux_total += a
        if cache is not None or collect_kv:
            dense_caches.append(kv)
    sub = None
    if cache is not None:
        # slice off the unrolled dense layers; only the stacked pools /
        # caches have a leading layer axis (block_tables and length don't)
        sub = jax.tree_util.tree_map(
            lambda c: c[n_dense:] if c.ndim > 2 else c, cache)
        sub = sub._replace(length=cache.length)
    x, aux, kv = _scan_blocks(cfg, params["blocks"], x, positions,
                              remat=remat, cache=sub,
                              collect_kv=collect_kv,
                              paged_prefill=paged_prefill, chunk=chunk)
    aux_total += aux
    x = apply_norm(cfg, params["ln_f"], x)
    new_cache = None
    if kv is not None:
        if dense_caches:
            kv = tuple(
                jnp.concatenate([jnp.stack([c[j] for c in dense_caches]),
                                 kv[j]])
                for j in range(len(kv)))
        length = (cache.length if cache is not None
                  else jnp.full((tokens.shape[0],), tokens.shape[1],
                                jnp.int32))
        if paged:
            if len(kv) == 4:
                new_cache = QuantPagedKVCache(
                    k=kv[0], v=kv[1], k_scale=kv[2], v_scale=kv[3],
                    block_tables=cache.block_tables, length=length)
            else:
                new_cache = PagedKVCache(k=kv[0], v=kv[1],
                                         block_tables=cache.block_tables,
                                         length=length)
        elif len(kv) == 4:
            new_cache = QuantKVCache(k=kv[0], v=kv[1], k_scale=kv[2],
                                     v_scale=kv[3], length=length)
        else:
            new_cache = KVCache(k=kv[0], v=kv[1], length=length)
    return x, aux_total, new_cache


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def default_positions(cfg, tokens: jax.Array) -> jax.Array:
    B, S = tokens.shape[0], tokens.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if cfg.m_rope:
        pos = jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def forward(cfg, params, tokens, positions=None, *, remat=True, chunk=1024):
    """Training forward: full logits (B, S, V) fp32 + aux loss."""
    if positions is None:
        positions = default_positions(cfg, tokens)
    x, aux, _ = _apply_backbone(cfg, params, tokens, positions, remat=remat,
                                chunk=chunk)
    lg = lm_logits(params["embed"], x, cfg.tie_embeddings,
                   cfg.final_logit_softcap)
    return lg, aux


def prefill(cfg, params, tokens, positions=None, *, cache_dtype="bfloat16",
            max_len: int | None = None, chunk=1024, last_pos=None):
    """Prefill: last-position logits (B, V) + KV cache sized to ``max_len``.

    ``last_pos`` (B,) reads logits at an arbitrary position instead of the
    final one — the bucketed-prefill path right-pads prompts to a compile
    bucket, so the real last token sits at ``prompt_len - 1`` (causality
    keeps its logits independent of the padding that follows).
    """
    if positions is None:
        positions = default_positions(cfg, tokens)
    x, _, cache = _apply_backbone(cfg, params, tokens, positions, remat=False,
                                  collect_kv=True, chunk=chunk)
    Sq = tokens.shape[1]
    max_len = max_len or Sq
    cdt = dtype_of(cache_dtype)

    def grow(c):
        if max_len == Sq:
            return c.astype(cdt)
        out = jnp.zeros(c.shape[:2] + (max_len,) + c.shape[3:], cdt)
        return out.at[:, :, :Sq].set(c.astype(cdt))

    cache = KVCache(k=grow(cache.k), v=grow(cache.v), length=cache.length)
    if last_pos is None:
        last = x[:, -1:]
    else:
        last = x[jnp.arange(x.shape[0]), last_pos][:, None]
    lg = lm_logits(params["embed"], last, cfg.tie_embeddings,
                   cfg.final_logit_softcap)
    return lg[:, 0], cache


def prefill_paged(cfg, params, tokens, cache, write_ids, table, *,
                  q_start, kv_len, last_idx, chunk=1024):
    """Cache-seeded chunked prefill: write one prompt chunk straight into
    paged pool blocks and attend over everything already seeded.

    tokens: (1, C) chunk (C a multiple of the pool block size; rows past
    the real prompt are padding whose writes land in the trash block via
    ``write_ids``); cache: Paged/QuantPagedKVCache whose pools are shared
    by every slot; write_ids: (C // block_size,) physical block per chunk
    block; table: (1, max_blocks) the request's read table; q_start: (1,)
    absolute position of the chunk's first token; kv_len: (1,) valid KV
    rows including this chunk's real tokens; last_idx: row whose logits
    to return (the chunk's last real token).

    Computation starts at the first unseeded token: rows before
    ``q_start`` (shared prefix blocks, a preemption victim's surviving
    history) are *read* through the table, never re-run — this is what
    the bucketed dense-prefill + scatter path could not do.  Returns
    ((1, V) logits at ``last_idx``, cache with updated pools).
    """
    B, C = tokens.shape
    pos = q_start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    pos = jnp.broadcast_to(pos, (B, C))
    if cfg.m_rope:
        pos = jnp.broadcast_to(pos[None], (3, B, C))
    x, _, new_cache = _apply_backbone(
        cfg, params, tokens, pos, remat=False, cache=cache, chunk=chunk,
        paged_prefill=dict(write_ids=write_ids, table=table,
                           q_start=q_start, kv_len=kv_len))
    last = x[jnp.arange(B), last_idx][:, None]
    lg = lm_logits(params["embed"], last, cfg.tie_embeddings,
                   cfg.final_logit_softcap)
    return lg[:, 0], new_cache


def verify_paged(cfg, params, tokens, cache, table, *, q_start, kv_len,
                 chunk=1024):
    """Speculative-decode verify pass: score ``k + 1`` candidate tokens per
    sequence in one batched target-model call.

    tokens: (B, C) per-slot ``[t_0, d_1 .. d_k]`` — the pending greedy
    token plus the drafter's proposals; cache: Paged/QuantPagedKVCache;
    table: (B, max_blocks) per-slot read tables (provisionally grown to
    cover the candidate rows; padding slots all-trash); q_start: (B,)
    committed rows per slot (candidate row ``j`` sits at absolute position
    ``q_start + j``); kv_len: (B,) ``q_start + C`` for live slots.

    Unlike :func:`prefill_paged` this returns logits at *every* candidate
    position — ``(B, C, V)`` with row ``j`` giving the target distribution
    after ``t_0, d_1 .. d_j`` — so greedy acceptance can take the longest
    drafter prefix matching the target's argmax chain.  Candidate KV rows
    are row-scattered through ``table`` (``write_ids=None`` layout), so
    accepted rows are already in place and the rejected tail sits in
    blocks the engine hands back via ``release_provisional``.
    """
    B, C = tokens.shape
    pos = q_start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    pos = jnp.broadcast_to(pos, (B, C))
    if cfg.m_rope:
        pos = jnp.broadcast_to(pos[None], (3, B, C))
    x, _, new_cache = _apply_backbone(
        cfg, params, tokens, pos, remat=False, cache=cache, chunk=chunk,
        paged_prefill=dict(write_ids=None, table=table,
                           q_start=q_start, kv_len=kv_len))
    lg = lm_logits(params["embed"], x, cfg.tie_embeddings,
                   cfg.final_logit_softcap)
    return lg, new_cache


def decode_step(cfg, params, tokens, cache, *, chunk=2048):
    """One decode step. tokens: (B, 1) -> logits (B, V), updated cache.

    ``cache`` may be any of the four cache types; the paged variants route
    attention through the block-table gather path (Pallas kernel on TPU,
    jnp oracle otherwise)."""
    B = tokens.shape[0]
    pos = cache.length[:, None]
    if cfg.m_rope:
        pos = jnp.broadcast_to(pos[None], (3, B, 1))
    x, _, new_cache = _apply_backbone(cfg, params, tokens, pos, remat=False,
                                      cache=cache, chunk=chunk)
    lg = lm_logits(params["embed"], x, cfg.tie_embeddings,
                   cfg.final_logit_softcap)
    new_cache = new_cache._replace(length=cache.length + 1)
    return lg[:, 0], new_cache
