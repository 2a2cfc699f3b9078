"""Flash-decode: one query token vs a long KV cache — Pallas TPU.

Grid (B, K_heads, S/bkv): for each (batch, kv-head) the G grouped query
heads attend to KV blocks streamed through VMEM; running (m, l, acc) live
in scratch, per-sequence valid length masks dead slots.  This is the
split-K decode kernel whose distributed twin is the LSE-merge path in
`distributed.collectives` (the per-shard partials there are exactly this
kernel's (out, m, l) triple).

`paged_decode_attention` is the paged variant: the KV lives in a global
pool of ``(N, K, bs, D)`` pages and each sequence's block table and length
are scalar-prefetch inputs.  Grid (B,): one step per slot, serving all its
KV heads at once (one page ``(K, bs, D)`` is contiguous in the pool).  The
pools stay in HBM; the step copies the slot's live pages, P at a time, into
a ``(P, K, bs, D)`` VMEM buffer with one async DMA per page, double
buffered so that group g+1 (or the next slot's first group) is in flight
while group g is computed: one QK^T and one PV per KV head over P*bs rows.
The walk is bounded by the live length: ``cdiv(length, P*bs)`` groups, and
no DMA for a page at or past ``length``, so the kernel's time follows the
live context, not the table width.  P follows the shapes
(`_pages_per_group`).  Only the tail of the last group is masked by
position; its unwritten rows are zeroed in V, so stale VMEM never leaks.
An int8 pool is dequantized through per-row absmax scales on the score and
probability tiles along their key axis (equal to dequantizing K and V row
by row).  A page's ``(K, bs)`` scales are too narrow for a DMA of their
own, so the wrapper gathers them by table into lanes, ``(B, K, mb*bs)``
(4 bytes per row and head, over the whole table), and the kernel copies
the ``(K, P*bs)`` slice of each live group.

The value pool may be omitted: values are then the first ``Dv`` lanes of
the key rows, and only the key pages are copied.  Latent attention (MLA)
decodes that way, all query heads against one latent entry per token
(K = 1), with the softmax scale its caller gives.

Oracle: `models.layers.attention.chunked_attention` with kv_len masking
(`ref.decode_attention_ref` / `ref.paged_decode_attention_ref`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, bkv: int, n_kv: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0, :, :]                         # (G, D)
    k = k_ref[0, :, 0, :]                         # (bkv, D)
    v = v_ref[0, :, 0, :]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = len_ref[0, 0]
    k_pos = ik * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < valid, s, NEG_INF)

    m_prev = m_ref[...]                           # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.maximum(m_new, NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        o_ref[0, 0, :, :] = (acc_ref[...] /
                             jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bkv", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, bkv: int = 1024,
                     interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k/v: (B, S, K, D); lengths: (B,) valid KV per sequence.

    Returns (B, H, D).
    """
    B, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    bkv = min(bkv, S)
    assert S % bkv == 0, (S, bkv)
    n_kv = S // bkv
    scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, K, G, D)
    len2d = lengths.reshape(B, 1).astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bkv=bkv, n_kv=n_kv),
        grid=(B, K, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, ik: (b, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, bkv, 1, D), lambda b, h, ik: (b, ik, h, 0)),
            pl.BlockSpec((1, bkv, 1, D), lambda b, h, ik: (b, ik, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(len2d, qg, k, v)
    return out.reshape(B, H, D)


# Bytes of K (and as many of V) that one group of pages moves: large enough
# that a group's DMAs run near HBM bandwidth, small enough that two groups
# of K and V (double buffering) take a small share of VMEM.
_GROUP_BYTES = 128 * 1024


def _pages_per_group(K: int, bs: int, D: int, itemsize: int, mb: int) -> int:
    """Pages per DMA group: the least power of two whose pages hold
    ``_GROUP_BYTES`` of K and at least 128 rows (a lane-dense score tile),
    capped at the table width."""
    page = K * bs * D * itemsize
    P = 1
    while (P * page < _GROUP_BYTES or P * bs < 128) and P < mb:
        P *= 2
    return min(P, mb)


def _run(copies, start: bool):
    for cp in copies:
        if start:
            cp.start()
        else:
            cp.wait()


def _paged_kernel(bt_ref, len_ref, q_ref, k_hbm, *rest, scale: float,
                  bs: int, P: int, B: int, softcap: float, quant: bool,
                  split_v: bool, Dv: int):
    rest = list(rest)
    v_hbm = rest.pop(0) if split_v else None
    if quant:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem,
         buf_ref) = rest
    elif split_v:
        o_ref, k_buf, v_buf, sem, buf_ref = rest
    else:
        o_ref, k_buf, sem, buf_ref = rest
    K = q_ref.shape[1]
    rows = P * bs
    b = pl.program_id(0)

    def group_dma(slot, g, buf, start: bool):
        """Start (or wait for) the copies of group ``g`` of ``slot`` into
        buffer ``buf``: one per live page, none past the slot's length."""
        n_pages = pl.cdiv(len_ref[slot], bs)

        def copy(src, dst):
            return pltpu.make_async_copy(src, dst, sem.at[buf])

        def one_page(j, _):
            page = bt_ref[slot, g * P + j]
            cps = [copy(k_hbm.at[page], k_buf.at[buf, j])]
            if split_v:
                cps.append(copy(v_hbm.at[page], v_buf.at[buf, j]))
            _run(cps, start)

        lax.fori_loop(0, jnp.clip(n_pages - g * P, 0, P), one_page, None)

        if quant:
            lanes = pl.ds(pl.multiple_of(g * rows, rows), rows)

            @pl.when(g * P < n_pages)
            def _():
                _run([copy(ks_hbm.at[slot, :, lanes], ks_buf.at[buf]),
                      copy(vs_hbm.at[slot, :, lanes], vs_buf.at[buf])],
                     start)

    # Slot b's first group was started by step b - 1 (by this step for
    # b == 0) into buffer buf_ref[0]; nothing else is in flight.
    @pl.when(b == 0)
    def _first():
        buf_ref[0] = 0
        group_dma(0, 0, 0, start=True)

    length = len_ref[b]
    n_groups = pl.cdiv(length, rows)
    base = buf_ref[0]
    has_next = b + 1 < B

    def body(g, carry):
        buf = (base + g) % 2

        @pl.when(g + 1 < n_groups)
        def _next_group():
            group_dma(b, g + 1, 1 - buf, start=True)

        @pl.when((g + 1 == n_groups) & has_next)
        def _next_slot():
            group_dma(b + 1, 0, 1 - buf, start=True)

        group_dma(b, g, buf, start=False)
        # rows past the length hold stale or unwritten VMEM: mask scores
        # and zero their values, so not even a NaN there can leak through
        pos = g * rows
        k_valid = pos + lax.broadcasted_iota(jnp.int32, (1, rows), 1) < length
        v_valid = pos + lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < length
        out = []
        for h in range(K):
            m_prev, l_prev, acc_prev = carry[h]
            q = q_ref[0, h]                                   # (G, D)
            k = k_buf[buf, :, h].astype(q.dtype).reshape(rows, -1)
            if split_v:
                v = v_buf[buf, :, h].astype(q.dtype).reshape(rows, -1)
            else:
                v = k[:, :Dv]
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if quant:
                s = s * ks_buf[buf, h:h + 1]
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(k_valid, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            m_safe = jnp.maximum(m_new, NEG_INF / 2)
            p = jnp.exp(s - m_safe)
            corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            if quant:
                p = jnp.where(k_valid, p * vs_buf[buf, h:h + 1], 0.0)
            v = jnp.where(v_valid, v, jnp.zeros_like(v))
            acc_new = acc_prev * corr + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out.append((m_new, l_new, acc_new))
        return tuple(out)

    G = q_ref.shape[2]
    init = tuple((jnp.full((G, 1), NEG_INF, jnp.float32),
                  jnp.zeros((G, 1), jnp.float32),
                  jnp.zeros((G, Dv), jnp.float32)) for _ in range(K))
    final = lax.fori_loop(0, n_groups, body, init)

    @pl.when((n_groups == 0) & has_next)
    def _pass_on():                      # an empty slot uses no buffer
        group_dma(b + 1, 0, base, start=True)

    buf_ref[0] = (base + n_groups) % 2
    for h in range(K):
        _, l, acc = final[h]
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "v_dim",
                                             "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array | None,
                           block_tables: jax.Array, lengths: jax.Array, *,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           softcap: float = 0.0,
                           scale: float | None = None,
                           v_dim: int | None = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k_pool/v_pool: (N, K, bs, D) global block pool, or
    ``v_pool`` None: values are the first ``v_dim`` lanes of the key rows;
    block_tables: (B, max_blocks) physical block per logical block;
    lengths: (B,) valid rows; k_scale/v_scale: (N, K, bs) for int8 pools;
    ``scale``: the softmax scale (default 1 / sqrt(D)).

    Returns (B, H, Dv), Dv = D with a value pool, else ``v_dim``.  Grid
    (B,): one step per slot serves all its KV heads; the pools stay in HBM
    and the kernel copies each slot's live pages, P at a time, into
    double-buffered VMEM.
    """
    B, H, D = q.shape
    N, K, bs, _ = k_pool.shape
    mb = block_tables.shape[1]
    G = H // K
    split_v = v_pool is not None
    Dv = D if split_v else v_dim
    P = _pages_per_group(K, bs, D, k_pool.dtype.itemsize, mb)
    scale = 1.0 / (D ** 0.5) if scale is None else scale
    qg = q.reshape(B, K, G, D)
    quant = k_scale is not None

    def q_map(b, bt_ref, len_ref):
        return (b, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, K, G, D), q_map), hbm]
    args = [qg, k_pool]
    scratch = [pltpu.VMEM((2, P, K, bs, D), k_pool.dtype)]
    if split_v:
        in_specs.append(hbm)
        args.append(v_pool)
        scratch.append(pltpu.VMEM((2, P, K, bs, D), v_pool.dtype))
    if quant:
        # row scales by slot, in lanes: (B, K, width*bs), width a whole
        # number of groups
        width = pl.cdiv(mb, P) * P
        tables = jnp.pad(block_tables, ((0, 0), (0, width - mb)))

        def by_slot(sc):
            return sc[tables].swapaxes(1, 2).reshape(B, K, width * bs)

        in_specs += [hbm, hbm]
        args += [by_slot(k_scale), by_slot(v_scale)]
        scratch += [pltpu.VMEM((2, K, P * bs), k_scale.dtype),
                    pltpu.VMEM((2, K, P * bs), v_scale.dtype)]
    scratch += [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, K, G, Dv), q_map),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bs=bs, P=P, B=B,
                          softcap=softcap, quant=quant, split_v=split_v,
                          Dv=Dv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, Dv), q.dtype),
        # a step prefetches the next slot's first group: run them in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), *args)
    return out.reshape(B, H, Dv)
