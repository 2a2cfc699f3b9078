"""Paged KV cache: block pool lifecycle, paged-vs-contiguous attention
equivalence (ragged lengths, int8 pools, Pallas interpret), bucketed
prefill, block-aware admission, and end-to-end engine agreement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry as R
from repro.kernels.decode_attention.kernel import _pages_per_group
from repro.kernels.decode_attention.kernel import \
    paged_decode_attention as pallas_paged
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref)
from repro.models import transformer as T
from repro.models.registry import fns_for
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_pool import CapacityError, KVBlockPool
from repro.serving.sampler import greedy


def _smoke():
    cfg = R.smoke("qwen2.5-3b")
    params = fns_for(cfg).init(cfg, jax.random.PRNGKey(0))
    return cfg, params


# -- block pool lifecycle ------------------------------------------------------

def test_pool_alloc_free_cycle():
    pool = KVBlockPool(4, block_size=16)
    assert pool.capacity == 4 and pool.total_blocks == 5
    assert pool.blocks_for(1) == 1 and pool.blocks_for(16) == 1
    assert pool.blocks_for(17) == 2 and pool.blocks_for(0) == 0
    assert pool.reserve(3)
    ids = pool.alloc_reserved(2)
    assert len(ids) == 2 and KVBlockPool.TRASH not in ids
    assert pool.used_blocks == 2 and pool.reserved_blocks == 1
    assert pool.free_blocks == 1                 # 4 - 2 allocated - 1 promised
    assert not pool.reserve(2)                   # transient: defer, no raise
    pool.free(ids)
    pool.unreserve(1)
    assert pool.used_blocks == 0 and pool.free_blocks == 4
    assert pool.peak_used == 2
    pool.reset_peak()
    assert pool.peak_used == 0


def test_pool_double_free_raises():
    pool = KVBlockPool(2)
    pool.reserve(1)
    [b] = pool.alloc_reserved(1)
    pool.free([b])
    with pytest.raises(ValueError, match="double free"):
        pool.free([b])
    with pytest.raises(ValueError, match="double free"):
        pool.free([KVBlockPool.TRASH])           # trash is never allocated


def test_pool_capacity_error_is_typed_and_valueerror():
    pool = KVBlockPool(2, block_size=16)
    with pytest.raises(CapacityError):
        pool.reserve(3)
    assert issubclass(CapacityError, ValueError)


def test_pool_refcount_share_free_and_double_free():
    """Prefix sharing: a shared block survives its first holder's free and
    only returns to the pool when the last holder lets go; double frees
    and shares of unallocated blocks still raise."""
    pool = KVBlockPool(4, block_size=8)
    pool.reserve(2)
    ids = pool.alloc_reserved(2)
    pool.share(ids)                              # second holder
    assert all(pool.refcount(b) == 2 for b in ids)
    assert pool.free(ids) == []                  # first holder: no release
    assert pool.used_blocks == 2 and pool.free_blocks == 2
    released = pool.free(ids)                    # last holder: released
    assert sorted(released) == sorted(ids)
    assert pool.used_blocks == 0 and pool.free_blocks == 4
    with pytest.raises(ValueError, match="double free"):
        pool.free([ids[0]])
    with pytest.raises(ValueError, match="share of unallocated"):
        pool.share([ids[0]])


def test_pool_release_provisional_grow_then_reject_is_invisible():
    """The speculative grow-then-reject cycle leaves every observable pool
    facet — free list, reservation ledger, refcounts, generation tags —
    exactly as it started, so a fully-rejected verify round is a no-op."""
    pool = KVBlockPool(6, block_size=8)
    pool.reserve(2)
    held = pool.alloc_reserved(2)                # a request's committed KV
    pool.reserve(2)                              # the +spec_rows budget
    before = (pool.free_blocks, pool.used_blocks, pool.reserved_blocks,
              [pool.generation(b) for b in range(pool.total_blocks)],
              {b: pool.refcount(b) for b in range(pool.total_blocks)})
    grown = pool.alloc_reserved(2)               # provisional verify rows
    assert pool.used_blocks == 4 and pool.reserved_blocks == 0
    pool.release_provisional(grown)              # verify rejected them all
    after = (pool.free_blocks, pool.used_blocks, pool.reserved_blocks,
             [pool.generation(b) for b in range(pool.total_blocks)],
             {b: pool.refcount(b) for b in range(pool.total_blocks)})
    assert after == before
    # the returned blocks are reserved again: re-growing cannot fail
    assert pool.alloc_reserved(2) and pool.reserved_blocks == 0
    # misuse raises without mutating: free blocks and shared blocks
    pool.share([held[0]])
    with pytest.raises(ValueError, match="shared"):
        pool.release_provisional([held[0]])
    with pytest.raises(ValueError, match="unallocated"):
        pool.release_provisional([KVBlockPool.TRASH])
    assert pool.refcount(held[0]) == 2           # nothing was mutated


def test_pool_generation_invalidates_stale_prefix_entries():
    """A (block, generation) tag goes dead on free and stays dead when the
    block is re-allocated for different contents — the prefix index can
    never alias a reused block."""
    pool = KVBlockPool(1, block_size=8)
    pool.reserve(1)
    [b] = pool.alloc_reserved(1)
    g = pool.generation(b)
    assert pool.block_live(b, g)
    pool.free([b])
    assert not pool.block_live(b, g)             # freed -> dead
    pool.reserve(1)
    [b2] = pool.alloc_reserved(1)
    assert b2 == b                               # same physical block...
    assert not pool.block_live(b, g)             # ...but the old tag stays dead
    assert pool.block_live(b2, pool.generation(b2))


def test_property_generation_tags_across_spill_free_realloc_cycles():
    """Property: through any interleaving of alloc / free / spill (hold +
    idle + demote-under-pressure) / realloc, a (block, generation) tag
    recorded at allocation reads live iff that exact allocation still owns
    the block — the guard that makes an async host-tier fetch safe to
    commit after the spill->free->realloc race."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, strategies as st

    @given(st.lists(st.tuples(st.sampled_from(["alloc", "free", "spill"]),
                              st.integers(0, 7)),
                    min_size=1, max_size=40))
    def check(ops):
        pool = KVBlockPool(4, block_size=8, host_blocks=8)
        demoted: list[int] = []
        pool.on_demote = demoted.extend
        tags: list[tuple[int, int]] = []     # (bid, gen) at alloc time
        alive: list[bool] = []               # shadow truth per tag
        owner: dict[int, int] = {}           # request-owned bid -> tag idx
        idle: dict[int, int] = {}            # demotable bid -> tag idx
        for op, pick in ops:
            if op == "alloc":
                if not pool.reserve(1):      # full even after demotions
                    continue
                for b in demoted:            # demote = spill + free: the
                    alive[idle.pop(b)] = False   # fetch guard must die
                demoted.clear()
                [b] = pool.alloc_reserved(1)
                owner[b] = len(tags)
                tags.append((b, pool.generation(b)))
                alive.append(True)
            elif op == "free" and owner:
                b = sorted(owner)[pick % len(owner)]
                pool.free([b])
                alive[owner.pop(b)] = False
            elif op == "spill" and owner:
                b = sorted(owner)[pick % len(owner)]
                pool.hold(b)                 # published to the prefix index
                pool.free([b])               # ...then its request lets go:
                idle[b] = owner.pop(b)       # demotable, still seedable
            for i, (b, g) in enumerate(tags):
                assert pool.block_live(b, g) == alive[i]
        assert pool.demotable_count == len(idle)
        assert pool.used_blocks == len(owner) + len(idle)

    check()


# -- paged attention vs dense oracle ------------------------------------------

def _ragged_case(seed, B=3, mb=4, bs=8, K=2, H=4, D=16):
    """Random pool + disjoint tables + ragged lengths, and the dense
    contiguous gather the paged read must match."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    N = 1 + B * mb
    q = jax.random.normal(ks[0], (B, H, D))
    k_pool = jax.random.normal(ks[1], (N, K, bs, D))
    v_pool = jax.random.normal(ks[2], (N, K, bs, D))
    rng = np.random.default_rng(seed)
    tables = 1 + rng.permutation(B * mb).reshape(B, mb).astype(np.int32)
    lengths = rng.integers(1, mb * bs + 1, size=B).astype(np.int32)
    return q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths)


@pytest.mark.parametrize("seed", range(4))
def test_paged_ref_matches_dense_ref_ragged(seed):
    q, kp, vp, tables, lengths = _ragged_case(seed)
    B, mb = q.shape[0], tables.shape[1]
    N, K, bs, D = kp.shape
    kd = kp[tables].swapaxes(2, 3).reshape(B, mb * bs, K, D)
    vd = vp[tables].swapaxes(2, 3).reshape(B, mb * bs, K, D)
    out = paged_decode_attention_ref(q, kp, vp, tables, lengths)
    ref = decode_attention_ref(q, kd, vd, lengths)
    np.testing.assert_allclose(out, ref, atol=1e-6)


def _group_case(K, G, cache_dtype, bs=16, D=128, seed=0):
    """Slots at the lengths where the kernel's page groups change: 1, bs,
    P*bs - 1, P*bs, P*bs + 1 and the whole table, plus retired slots (all
    zero tables) of length 0 and 1.  Tables are shuffled pool pages, out of
    order and not contiguous; P is what the kernel picks for these shapes.
    Returns (q, pools, tables, lengths, scale kwargs)."""
    itemsize = jnp.dtype(cache_dtype).itemsize
    P = _pages_per_group(K, bs, D, itemsize, 1 << 20)
    mb = 2 * P + 3
    lengths = np.array([1, bs, P * bs - 1, P * bs, P * bs + 1, mb * bs,
                        0, 1], np.int32)
    B, live = len(lengths), 6
    N = 1 + 2 * live * mb                    # half the pool is never used
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, K * G, D))
    kp = jax.random.normal(ks[1], (N, K, bs, D))
    vp = jax.random.normal(ks[2], (N, K, bs, D))
    rng = np.random.default_rng(seed)
    tables = np.zeros((B, mb), np.int32)
    tables[:live] = (1 + rng.permutation(N - 1)[:live * mb]).reshape(live, mb)
    if cache_dtype == "int8":
        kp, k_scale = T.quantize_kv(kp)
        vp, v_scale = T.quantize_kv(vp)
        scales = dict(k_scale=k_scale, v_scale=v_scale)
    else:
        kp, vp = kp.astype(cache_dtype), vp.astype(cache_dtype)
        scales = {}
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths), scales


_HEADS = {"qwen2.5-3b": (2, 8), "qwen3-32b-8l": (8, 8)}


def _pallas_cases():
    yield from (pytest.param(None, None, seed, 0.0, id=str(seed))
                for seed in range(2))
    for heads in _HEADS:
        for dtype in ("bfloat16", "int8"):
            for softcap in (0.0, 30.0):
                yield pytest.param(heads, dtype, 0, softcap,
                                   id=f"{heads}-{dtype}-softcap{softcap:g}")


@pytest.mark.parametrize("heads,cache_dtype,seed,softcap", _pallas_cases())
def test_paged_pallas_matches_ref_ragged(heads, cache_dtype, seed, softcap):
    if heads is None:
        q, kp, vp, tables, lengths = _ragged_case(seed)
        scales = {}
    else:
        q, kp, vp, tables, lengths, scales = _group_case(
            *_HEADS[heads], cache_dtype, seed=seed)
    out = pallas_paged(q, kp, vp, tables, lengths, softcap=softcap,
                       interpret=True, **scales)
    ref = paged_decode_attention_ref(q, kp, vp, tables, lengths,
                                     softcap=softcap, **scales)
    np.testing.assert_allclose(out, ref, atol=2e-5)


@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
def test_paged_pallas_reads_no_page_past_the_length(cache_dtype):
    """Every pool page outside the slots' live prefixes (the trash page,
    the tails of the tables, the pages no table names) is NaN (for int8,
    its scales): the output stays finite and equal to the clean pool's."""
    q, kp, vp, tables, lengths, scales = _group_case(2, 8, cache_dtype)
    bs = kp.shape[2]
    live = np.zeros(kp.shape[0], bool)
    for row, n in zip(np.asarray(tables), np.asarray(lengths)):
        live[row[:-(-n // bs)]] = True
    dead = jnp.asarray(~live)[:, None, None]
    clean = pallas_paged(q, kp, vp, tables, lengths, interpret=True,
                         **scales)
    if scales:
        scales = {k: jnp.where(dead, jnp.nan, v) for k, v in scales.items()}
    else:
        kp, vp = (jnp.where(dead[..., None], jnp.nan, x) for x in (kp, vp))
    out = pallas_paged(q, kp, vp, tables, lengths, interpret=True, **scales)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_array_equal(out, clean)


def test_paged_pallas_int8_matches_ref():
    q, kp, vp, tables, lengths = _ragged_case(7)
    kq, ks = T.quantize_kv(kp)
    vq, vs = T.quantize_kv(vp)
    out = pallas_paged(q, kq, vq, tables, lengths, k_scale=ks, v_scale=vs,
                       interpret=True)
    ref = paged_decode_attention_ref(q, kq, vq, tables, lengths,
                                     k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    # and the quantized path stays close to the fp path (absmax int8)
    fp = paged_decode_attention_ref(q, kp, vp, tables, lengths)
    assert float(jnp.abs(ref - fp).max()) < 0.05


def test_property_paged_matches_dense_over_ragged_lengths():
    """Property: for any block size / table width / ragged lengths / cache
    dtype, paged attention equals the dense gather (hypothesis-driven;
    module stays collectable without hypothesis)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, strategies as st

    @given(st.integers(0, 10**6), st.sampled_from([4, 8, 16]),
           st.integers(1, 4), st.booleans())
    def prop(seed, bs, mb, quant):
        rng = np.random.default_rng(seed)
        B, K, H, D = 2, 2, 4, 8
        N = 1 + B * mb
        ks = jax.random.split(jax.random.PRNGKey(seed % (2**31)), 3)
        q = jax.random.normal(ks[0], (B, H, D))
        kp = jax.random.normal(ks[1], (N, K, bs, D))
        vp = jax.random.normal(ks[2], (N, K, bs, D))
        tables = jnp.asarray(
            1 + rng.permutation(B * mb).reshape(B, mb).astype(np.int32))
        lengths = jnp.asarray(
            rng.integers(1, mb * bs + 1, size=B).astype(np.int32))
        scales = {}
        if quant:
            kp, ksc = T.quantize_kv(kp)
            vp, vsc = T.quantize_kv(vp)
            scales = dict(k_scale=ksc, v_scale=vsc)
        out = paged_decode_attention_ref(q, kp, vp, tables, lengths,
                                         **scales)
        kd = kp[tables].swapaxes(2, 3).reshape(B, mb * bs, K, D)
        vd = vp[tables].swapaxes(2, 3).reshape(B, mb * bs, K, D)
        if quant:
            kd = (kd.astype(jnp.float32)
                  * scales["k_scale"][tables].swapaxes(2, 3).reshape(
                      B, mb * bs, K)[..., None]).astype(q.dtype)
            vd = (vd.astype(jnp.float32)
                  * scales["v_scale"][tables].swapaxes(2, 3).reshape(
                      B, mb * bs, K)[..., None]).astype(q.dtype)
        ref = decode_attention_ref(q, kd, vd, lengths)
        np.testing.assert_allclose(out, ref, atol=2e-6)

    prop()


def test_paged_trash_block_rows_never_attended():
    """Garbage in dead table entries / the trash block must not leak into
    the output of live rows."""
    q, kp, vp, tables, lengths = _ragged_case(3)
    ref = paged_decode_attention_ref(q, kp, vp, tables, lengths)
    poisoned_k = kp.at[0].set(1e4)          # trash block full of garbage
    poisoned_v = vp.at[0].set(-1e4)
    out = paged_decode_attention_ref(q, poisoned_k, poisoned_v, tables,
                                     lengths)
    np.testing.assert_allclose(out, ref, atol=1e-6)


# -- paged decode_step vs dense decode_step (model level, incl. int8) ---------

def _paged_state_from_prefill(cfg, st: T.KVCache, bs, mb, dtype):
    """Scatter a dense batch-B prefill cache into a paged cache with
    ``mb``-wide block tables (each sequence gets its own contiguous run of
    blocks; entries past the prefill hold spare blocks for decode)."""
    L, B, S, K, D = st.k.shape
    assert S % bs == 0
    nb = S // bs
    assert mb >= nb
    cache = T.make_paged_cache(cfg, 1 + B * mb, bs, B, mb, dtype)
    tables = np.zeros((B, mb), np.int32)
    nxt = 1
    for b in range(B):
        ids = np.arange(nxt, nxt + mb, dtype=np.int32)
        nxt += mb
        tables[b] = ids
        one = jax.tree_util.tree_map(lambda c: c[:, b:b + 1]
                                     if c.ndim > 1 else c, st)
        cache = T.scatter_prefill_blocks(cache, one, jnp.asarray(ids[:nb]))
    return cache._replace(block_tables=jnp.asarray(tables),
                          length=st.length)


@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
def test_paged_decode_step_matches_dense(cache_dtype):
    cfg, params = _smoke()
    fns = fns_for(cfg)
    B, S, extra, bs = 2, 16, 3, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + extra), 0,
                              cfg.vocab_size)
    _, st = fns.prefill(cfg, params, {"tokens": toks[:, :S]},
                        max_len=S + extra)
    # dense reference cache in the target dtype
    if cache_dtype == "int8":
        kq, ks = T.quantize_kv(st.k)
        vq, vs = T.quantize_kv(st.v)
        dense = T.QuantKVCache(k=kq, v=vq, k_scale=ks, v_scale=vs,
                               length=st.length)
    else:
        dense = st
    # paged cache scatters the S prefill rows; the grown tail rows of the
    # dense cache are zeros, so slicing them off loses nothing
    st_s = T.KVCache(k=st.k[:, :, :S], v=st.v[:, :, :S], length=st.length)
    paged = _paged_state_from_prefill(cfg, st_s, bs, S // bs + 1,
                                      cache_dtype)
    for t in range(S, S + extra):
        lg_d, dense = fns.decode(cfg, params, toks[:, t:t + 1], dense)
        lg_p, paged = fns.decode(cfg, params, toks[:, t:t + 1], paged)
        np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_d),
                                   atol=1e-4)
    assert int(paged.length[0]) == S + extra


# -- bucketed prefill ----------------------------------------------------------

def test_bucketed_prefill_logits_match_exact():
    """Right-padding the prompt to a bucket and reading logits at
    last_pos must equal the unpadded prefill (causality)."""
    cfg, params = _smoke()
    fns = fns_for(cfg)
    P, bucket = 9, 16
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, P), 0,
                              cfg.vocab_size)
    lg_ref, _ = fns.prefill(cfg, params, {"tokens": toks})
    padded = jnp.zeros((1, bucket), jnp.int32).at[:, :P].set(toks)
    lg_b, st = fns.prefill(cfg, params,
                           {"tokens": padded,
                            "last_pos": jnp.asarray([P - 1])})
    np.testing.assert_allclose(np.asarray(lg_b), np.asarray(lg_ref),
                               atol=1e-5)
    assert st.k.shape[2] == bucket            # cache sized to the bucket


# -- engine: equivalence, leak-freedom, capacity, admission -------------------

def test_paged_engine_matches_contiguous_and_frees_blocks():
    cfg, params = _smoke()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (9, 5, 13, 7, 11)]
    mk = lambda: [Request(i, p, max_new_tokens=3 + (i % 3),  # noqa: E731
                          sampler=greedy())
                  for i, p in enumerate(prompts)]
    paged = ServingEngine(cfg, params, max_len=24, batch_slots=2, paged=True)
    contig = ServingEngine(cfg, params, max_len=24, batch_slots=2,
                           paged=False)
    rp, rc = mk(), mk()
    sp = paged.serve(rp)
    contig.serve(rc)
    assert [r.output for r in rp] == [r.output for r in rc]
    # no leak: every block and reservation returned after serve()
    assert paged.pool.used_blocks == 0
    assert paged.pool.reserved_blocks == 0
    assert sp.kv_blocks_peak >= 1
    assert 0.0 < sp.kv_pool_util <= 1.0
    # bucketing: 5 distinct prompt lengths but only one 16-bucket compile
    assert sp.prefill_compiles == 1


def test_paged_engine_small_pool_still_serves_all():
    """A pool sized well below slots x max_len defers admission instead of
    failing, and every request still completes."""
    cfg, params = _smoke()
    rng = np.random.default_rng(6)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, size=6)
                    .astype(np.int32),
                    max_new_tokens=2 if i % 2 else 10, sampler=greedy())
            for i in range(6)]
    # worst case would be 4 slots x blocks_for(24) = 8 blocks; give it 2
    eng = ServingEngine(cfg, params, max_len=24, batch_slots=4, paged=True,
                        block_size=8, pool_blocks=2)
    stats = eng.serve(reqs)
    assert [len(r.output) for r in reqs] == [10, 2, 10, 2, 10, 2]
    assert stats.kv_blocks_peak <= 2
    assert eng.pool.used_blocks == 0 and eng.pool.reserved_blocks == 0


def test_capacity_error_paths():
    cfg, params = _smoke()
    eng = ServingEngine(cfg, params, max_len=32, batch_slots=2, paged=True,
                        block_size=8, pool_blocks=2)   # 16 KV rows total
    too_big = Request(0, np.arange(8, dtype=np.int32), max_new_tokens=12)
    with pytest.raises(CapacityError, match="KV"):
        eng.serve([too_big])                 # pool capacity, not max_len
    with pytest.raises(CapacityError):
        eng.submit(too_big)
    # the scheduler's own admission guard raises the same typed error
    with pytest.raises(CapacityError):
        eng.scheduler.submit(too_big)
    # a fitting request still serves
    ok = Request(1, np.arange(8, dtype=np.int32), max_new_tokens=6)
    assert eng.serve([ok]).tokens == 6


def test_prefix_sharing_dedups_blocks_and_matches_unshared():
    """Requests with a common full-block prompt prefix map their leading
    table entries to one refcounted copy: same outputs, strictly fewer
    peak pool blocks, balanced pool afterwards."""
    cfg, params = _smoke()
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(0, cfg.vocab_size,
                                                    size=4).astype(np.int32)])
               for _ in range(3)]                # 20 tokens: 2 full blocks
    mk = lambda: [Request(i, p, max_new_tokens=3, sampler=greedy())  # noqa
                  for i, p in enumerate(prompts)]
    kw = dict(max_len=24, batch_slots=3, paged=True, block_size=8)
    shared = ServingEngine(cfg, params, **kw)
    plain = ServingEngine(cfg, params, prefix_sharing=False, **kw)
    rs, rp = mk(), mk()
    ss = shared.serve(rs)
    sp = plain.serve(rp)
    assert [r.output for r in rs] == [r.output for r in rp]
    # 2 shared prefix blocks counted once + 1 own tail block each
    assert ss.prefix_shared_blocks == 4          # 2 sharers x 2 blocks
    assert sp.prefix_shared_blocks == 0
    assert ss.kv_blocks_peak < sp.kv_blocks_peak
    assert ss.kv_blocks_peak < 3 * 2             # < N x prefix-blocks
    # refcounted release: nothing leaks once every sharer is done
    assert shared.pool.used_blocks == 0
    assert shared.pool.reserved_blocks == 0
    # pool churn invalidated every index entry (blocks freed); a second
    # round with the same prefix must re-publish over the dead entries and
    # recover full sharing immediately, not one block per admission
    ss2 = shared.serve(mk())
    assert ss2.prefix_shared_blocks == 4         # same as the first round


def test_paged_engine_int8_cache_top1_stable():
    """End-to-end paged serving with the int8 pool: greedy streams match
    the bf16 paged engine up to at most one top-1 flip *event* (paper's
    top-1-stability criterion, cascade-aware: once one token differs, the
    continuations decode different contexts, so only the first divergence
    per request is an int8-noise event).  Since the cache-seeded prefill,
    prompt attention reads the int8 pool too — consistent with the decode
    path, and required for seeded/recompute bit-equality — so the flip
    can now also land on the first token."""
    cfg, params = _smoke()
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, size=10).astype(np.int32)
               for _ in range(2)]
    mk = lambda: [Request(i, p, max_new_tokens=4, sampler=greedy())  # noqa
                  for i, p in enumerate(prompts)]
    bf = ServingEngine(cfg, params, max_len=16, batch_slots=2, paged=True)
    q8 = ServingEngine(cfg, params, max_len=16, batch_slots=2, paged=True,
                       cache_dtype="int8")
    rb, rq = mk(), mk()
    bf.serve(rb)
    q8.serve(rq)
    flips = sum(any(a != b for a, b in zip(ra.output, rb_.output))
                for ra, rb_ in zip(rb, rq))
    assert flips <= 1
    assert q8._state.k.dtype == jnp.int8


# -- latent pools: one entry per token, values its first lanes ----------------

def _latent_case(seed=0, B=4, H=16, W=128, Dv=96, bs=16, mb=9):
    """A latent pool (N, 1, bs, W) with shuffled tables and lengths at the
    kernel's group edges, and G = H query heads against it."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    N = 1 + B * mb
    pool = jax.random.normal(ks[0], (N, 1, bs, W)).astype(jnp.bfloat16)
    q = jax.random.normal(ks[1], (B, H, W)).astype(jnp.bfloat16)
    rng = np.random.default_rng(seed)
    tables = jnp.asarray((1 + rng.permutation(N - 1)).reshape(B, mb),
                         jnp.int32)
    lengths = jnp.asarray([1, bs, 8 * bs + 1, mb * bs][:B], jnp.int32)
    return q, pool, tables, lengths, Dv


def _bf16_tol(pool):
    """The kernels round the probabilities to bf16 before the value
    product and the output to bf16: each a relative 2**-8, against values
    no larger than the pool's largest."""
    return 2 * 2.0 ** -8 * float(jnp.abs(pool).max())


def _plain_latent(q, pool, tables, lengths, Dv, scale, q_pos=None):
    """Straightforward jnp in float32: gather each sequence's entries,
    score every query head against every entry, mask past the length
    (and, for a chunk, after the query's position), read values from the
    first Dv lanes."""
    B = tables.shape[0]
    e = pool[tables][:, :, 0].reshape(B, -1, pool.shape[-1])
    e = e.astype(jnp.float32)
    s = jnp.einsum("bqhw,bkw->bqhk", q.astype(jnp.float32), e) * scale
    k = jnp.arange(e.shape[1])
    ok = k[None, None, :] < lengths[:, None, None]
    if q_pos is not None:
        ok = ok & (k[None, None, :] <= q_pos[:, :, None])
    s = jnp.where(ok[:, :, None, :], s, -jnp.inf)
    return jnp.einsum("bqhk,bkv->bqhv", jax.nn.softmax(s, -1), e[..., :Dv])


def test_latent_decode_kernel_matches_plain_jnp():
    q, pool, tables, lengths, Dv = _latent_case()
    scale = 24 ** -0.5
    want = _plain_latent(q[:, None], pool, tables, lengths, Dv, scale)[:, 0]
    out = pallas_paged(q, pool, None, tables, lengths, scale=scale, v_dim=Dv,
                       interpret=True)
    ref = paged_decode_attention_ref(q, pool, None, tables, lengths,
                                     scale=scale, v_dim=Dv)
    assert out.shape == (4, 16, Dv)
    tol = _bf16_tol(pool)
    np.testing.assert_allclose(np.asarray(out, np.float32), want, atol=tol)
    np.testing.assert_allclose(np.asarray(ref, np.float32), want, atol=tol)


def test_latent_prefill_kernel_matches_plain_jnp():
    from repro.kernels.prefill_attention.kernel import \
        paged_prefill_attention as pallas_prefill
    from repro.kernels.prefill_attention.ref import \
        paged_prefill_attention_ref
    q1, pool, tables, lengths, Dv = _latent_case(seed=1)
    C, start = 32, jnp.asarray([0, 16, 100, 112], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(5),
                          (4, C, 16, pool.shape[-1])).astype(jnp.bfloat16)
    kv_len = jnp.minimum(start + C, tables.shape[1] * 16)
    scale = 24 ** -0.5
    q_pos = start[:, None] + jnp.arange(C)[None]
    want = _plain_latent(q, pool, tables, kv_len, Dv, scale, q_pos)
    out = pallas_prefill(q, pool, None, tables, start, kv_len, scale=scale,
                         v_dim=Dv, interpret=True)
    ref = paged_prefill_attention_ref(q, pool, None, tables, start, kv_len,
                                      scale=scale, v_dim=Dv)
    assert out.shape == (4, C, 16, Dv)
    tol = _bf16_tol(pool)
    np.testing.assert_allclose(np.asarray(out, np.float32), want, atol=tol)
    np.testing.assert_allclose(np.asarray(ref, np.float32), want, atol=tol)


def test_dense_pools_unchanged_by_the_value_options():
    """With a value pool and no scale given, the kernel is the dense one:
    same output bits as an explicit 1/sqrt(D) scale."""
    q, kp, vp, tables, lengths, _ = _group_case(2, 8, "bfloat16")
    plain = pallas_paged(q, kp, vp, tables, lengths, interpret=True)
    given = pallas_paged(q, kp, vp, tables, lengths, interpret=True,
                         scale=1.0 / (kp.shape[-1] ** 0.5))
    np.testing.assert_array_equal(plain, given)


def test_host_tier_round_trips_a_latent_block():
    """A latent pool has only ``k``: a block read for the host tier holds
    that leaf alone, and writing it back into another block reproduces it
    exactly."""
    import deepseek_tiny as DT
    cfg = DT.config(dtype="bfloat16")
    params = fns_for(cfg).init(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                        prefix_sharing=True, host_blocks=4)
    eng._state = eng._init_state()
    k = eng._state.k
    filled = k.at[:, 3].set(
        jax.random.normal(jax.random.PRNGKey(1), k[:, 3].shape, k.dtype))
    eng._state = eng._state._replace(k=filled)
    payload = {n: np.asarray(a) for n, a in
               eng._read_block_slices(3).items()}
    assert set(payload) == {"k"}
    eng._write_block(5, payload)
    np.testing.assert_array_equal(eng._state.k[:, 5], filled[:, 3])
    assert eng._state.v is None
    eng.stop()
