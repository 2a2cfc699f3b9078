"""Cache-seeded chunked prefill: paged prefill-attention kernel vs oracle,
model-level chunked-vs-dense equivalence, engine-level seeded-vs-recompute
greedy equality (incl. int8 pools), block/bucket boundary prompt lengths,
preemption-resume with zero recomputed prefix tokens, prefill/decode
interleaving, and the prefix-index trim order."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry as R
from repro.kernels.prefill_attention.kernel import \
    paged_prefill_attention as pallas_prefill
from repro.kernels.prefill_attention.ref import paged_prefill_attention_ref
from repro.models import transformer as T
from repro.models.layers.attention import chunked_attention
from repro.models.registry import fns_for
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampler import greedy


def _smoke():
    cfg = R.smoke("qwen2.5-3b")
    params = fns_for(cfg).init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _direct_greedy(cfg, params, prompt, n_new, max_len):
    fns = fns_for(cfg)
    batch = {"tokens": jnp.asarray(prompt, jnp.int32)[None]}
    lg, st = fns.prefill(cfg, params, batch, max_len=max_len)
    out = []
    for _ in range(n_new):
        tok = int(jnp.argmax(lg[0]))
        out.append(tok)
        lg, st = fns.decode(cfg, params, jnp.asarray([[tok]], jnp.int32), st)
    return out


# -- kernel vs oracle ----------------------------------------------------------

def _chunk_case(seed, B=2, C=8, mb=5, bs=8, K=2, H=4, D=16):
    """Random pool + disjoint tables + per-sequence chunk offsets."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    N = 1 + B * mb
    q = jax.random.normal(ks[0], (B, C, H, D))
    k_pool = jax.random.normal(ks[1], (N, K, bs, D))
    v_pool = jax.random.normal(ks[2], (N, K, bs, D))
    rng = np.random.default_rng(seed)
    tables = 1 + rng.permutation(B * mb).reshape(B, mb).astype(np.int32)
    # chunk origin anywhere a block-aligned chunk fits (seeded rows before)
    q_start = rng.integers(0, mb * bs - C + 1, size=B) // bs * bs
    lengths = q_start + C
    return (q, k_pool, v_pool, jnp.asarray(tables),
            jnp.asarray(q_start.astype(np.int32)),
            jnp.asarray(lengths.astype(np.int32)))


@pytest.mark.parametrize("seed", range(3))
def test_prefill_ref_matches_dense_causal(seed):
    """The paged oracle equals dense causal attention over the gathered
    cache with query positions offset to the chunk origin."""
    q, kp, vp, tables, q_start, lengths = _chunk_case(seed)
    B, C = q.shape[:2]
    mb = tables.shape[1]
    N, K, bs, D = kp.shape
    kd = kp[tables].swapaxes(2, 3).reshape(B, mb * bs, K, D)
    vd = vp[tables].swapaxes(2, 3).reshape(B, mb * bs, K, D)
    qpos = q_start[:, None] + jnp.arange(C)[None]
    dense = chunked_attention(q, kd, vd, causal=True, q_positions=qpos,
                              kv_positions=jnp.arange(mb * bs),
                              kv_len=lengths)
    out = paged_prefill_attention_ref(q, kp, vp, tables, q_start, lengths)
    np.testing.assert_allclose(out, dense, atol=1e-6)


@pytest.mark.parametrize("seed", range(2))
def test_prefill_pallas_matches_ref(seed):
    q, kp, vp, tables, q_start, lengths = _chunk_case(seed)
    out = pallas_prefill(q, kp, vp, tables, q_start, lengths, interpret=True)
    ref = paged_prefill_attention_ref(q, kp, vp, tables, q_start, lengths)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_prefill_pallas_int8_matches_ref():
    q, kp, vp, tables, q_start, lengths = _chunk_case(5)
    kq, ks = T.quantize_kv(kp)
    vq, vs = T.quantize_kv(vp)
    out = pallas_prefill(q, kq, vq, tables, q_start, lengths,
                         k_scale=ks, v_scale=vs, interpret=True)
    ref = paged_prefill_attention_ref(q, kq, vq, tables, q_start, lengths,
                                      k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(out, ref, atol=2e-5)


@pytest.mark.parametrize("quant", [False, True])
def test_prefill_pallas_query_tiles_match_ref(monkeypatch, quant):
    """A chunk whose rows span several query tiles (each tile masked
    against its own absolute positions, and skipping blocks wholly after
    its last query) equals the oracle."""
    from repro.kernels.prefill_attention import kernel as PK
    monkeypatch.setattr(PK, "_TILE_ROWS", 16)   # 8 positions x G=2 rows
    q, kp, vp, tables, q_start, lengths = _chunk_case(11, C=32, mb=6)
    assert PK._query_tile(32, 2) == 8
    scales = {}
    if quant:
        kp, ks = T.quantize_kv(kp)
        vp, vs = T.quantize_kv(vp)
        scales = dict(k_scale=ks, v_scale=vs)
    out = pallas_prefill(q, kp, vp, tables, q_start, lengths, interpret=True,
                         **scales)
    ref = paged_prefill_attention_ref(q, kp, vp, tables, q_start, lengths,
                                      **scales)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_prefill_trash_and_future_blocks_never_attended():
    """Garbage in the trash block and in table entries past the valid
    length must not leak into the chunk's outputs."""
    q, kp, vp, tables, q_start, lengths = _chunk_case(7)
    ref = paged_prefill_attention_ref(q, kp, vp, tables, q_start, lengths)
    poisoned_k = kp.at[0].set(1e4)
    poisoned_v = vp.at[0].set(-1e4)
    out = paged_prefill_attention_ref(q, poisoned_k, poisoned_v, tables,
                                      q_start, lengths)
    np.testing.assert_allclose(out, ref, atol=1e-6)


# -- model level: chunked paged prefill vs dense prefill ----------------------

def test_prefill_paged_chunked_matches_dense():
    """Writing a prompt into pool blocks chunk by chunk and reading logits
    at the last real token equals the dense full-prompt prefill."""
    cfg, params = _smoke()
    fns = fns_for(cfg)
    bs, mb, P = 8, 4, 20
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (P,), 0,
                                         cfg.vocab_size), np.int32)
    lg_ref, _ = fns.prefill(cfg, params, {"tokens": jnp.asarray(toks)[None]},
                            max_len=P)
    cache = T.make_paged_cache(cfg, 1 + 8, bs, 1, mb, "bfloat16")
    block_ids = [1, 2, 3]
    tbl = np.zeros((1, mb), np.int32)
    tbl[0, :3] = block_ids
    pos, last = 0, None
    for real, cpad in ((8, 8), (12, 16)):    # final chunk bucket-padded
        ct = np.zeros((1, cpad), np.int32)
        ct[0, :real] = toks[pos:pos + real]
        wids = np.zeros((cpad // bs,), np.int32)
        for j in range(cpad // bs):
            lb = pos // bs + j
            if lb < 3:
                wids[j] = block_ids[lb]
        last, cache = fns.prefill_paged(
            cfg, params, jnp.asarray(ct), cache, jnp.asarray(wids),
            jnp.asarray(tbl), q_start=jnp.asarray([pos], jnp.int32),
            kv_len=jnp.asarray([pos + real], jnp.int32),
            last_idx=jnp.int32(real - 1))
        pos += real
    np.testing.assert_allclose(np.asarray(last), np.asarray(lg_ref),
                               atol=1e-5)


# -- engine: seeded prefill vs full recompute ---------------------------------

def _prefix_workload(cfg, n=4, prefix_tokens=32, block=8, seed=11):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size,
                          size=prefix_tokens).astype(np.int32)
    return [Request(i, np.concatenate(
                    [prefix, rng.integers(0, cfg.vocab_size, size=5)
                     .astype(np.int32)]),
                    max_new_tokens=4, sampler=greedy())
            for i in range(n)]


@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
def test_seeded_prefill_matches_recompute_exactly(cache_dtype):
    """The acceptance invariant: a seeded prefill (shared prefix read from
    the pool, never re-run) must produce greedy continuations identical
    token for token to the full-recompute baseline — including int8
    pools, where both paths read the same quantized prefix rows."""
    cfg, params = _smoke()
    kw = dict(max_len=48, batch_slots=4, paged=True, block_size=8,
              cache_dtype=cache_dtype)
    seeded = ServingEngine(cfg, params, **kw)
    recomp = ServingEngine(cfg, params, seeded_prefill=False, **kw)
    rs = _prefix_workload(cfg)
    rr = _prefix_workload(cfg)
    ss = seeded.serve(rs)
    sr = recomp.serve(rr)
    assert [r.output for r in rs] == [r.output for r in rr]
    # the recompute baseline runs every prompt token; the seeded engine
    # skips the shared prefix (3 of 4 requests seed 4 prefix blocks)
    assert sr.prefill_tokens_computed == sr.prefill_tokens_total
    assert ss.prefill_tokens_total == sr.prefill_tokens_total
    saved = 3 * 32                       # 3 sharers x 4 blocks x 8 tokens
    assert ss.prefill_tokens_computed == ss.prefill_tokens_total - saved
    # both engines still map shared blocks (storage dedup is independent)
    assert ss.prefix_shared_blocks == sr.prefix_shared_blocks == 12
    assert seeded.pool.used_blocks == 0
    assert seeded.pool.reserved_blocks == 0


def test_seeded_prefill_matches_contiguous_engine():
    """Seeded paged serving equals the contiguous (dense-prefill) engine's
    greedy outputs — the cross-layout ground truth."""
    cfg, params = _smoke()
    rs = _prefix_workload(cfg)
    rc = _prefix_workload(cfg)
    seeded = ServingEngine(cfg, params, max_len=48, batch_slots=4,
                           paged=True, block_size=8)
    contig = ServingEngine(cfg, params, max_len=48, batch_slots=4,
                           paged=False)
    seeded.serve(rs)
    contig.serve(rc)
    assert [r.output for r in rs] == [r.output for r in rc]


@pytest.mark.parametrize("P", [7, 8, 9, 15, 16, 17])
def test_boundary_prompt_lengths_seed_and_match(P):
    """Prompt lengths exactly at (and around) block and bucket boundaries:
    two identical co-resident prompts — the second seeds every *sharable*
    block (capped one token short of the prompt, since the last token's
    logits must be computed) — and both match the contiguous engine."""
    cfg, params = _smoke()
    bs = 8
    prompt = (np.arange(P, dtype=np.int32) * 7 + 3) % cfg.vocab_size
    mk = lambda: [Request(i, prompt.copy().astype(np.int32),  # noqa: E731
                          max_new_tokens=3, sampler=greedy())
                  for i in range(2)]
    paged = ServingEngine(cfg, params, max_len=P + 4, batch_slots=2,
                          paged=True, block_size=bs)
    contig = ServingEngine(cfg, params, max_len=P + 4, batch_slots=2,
                           paged=False)
    rp, rc = mk(), mk()
    sp = paged.serve(rp)
    contig.serve(rc)
    assert [r.output for r in rp] == [r.output for r in rc]
    seeded_tokens = ((P - 1) // bs) * bs      # full blocks short of the end
    assert sp.prefill_tokens_total == 2 * P
    assert sp.prefill_tokens_computed == 2 * P - seeded_tokens
    assert paged.pool.used_blocks == 0 and paged.pool.reserved_blocks == 0


# -- preemption resume: surviving history is seeded, not recomputed -----------

def test_preemption_resume_recomputes_zero_prefix_tokens():
    """A preempted decode whose prompt prefix survives in the pool (via a
    co-holder) resumes by seeding those blocks: the re-admission computes
    exactly prompt+generated minus the seeded prefix — zero prefix tokens
    re-run — and still finishes with the un-preempted greedy output."""
    cfg, params = _smoke()
    bs = 8
    rng = np.random.default_rng(17)
    prefix = rng.integers(0, cfg.vocab_size, size=2 * bs).astype(np.int32)
    mk_tail = lambda s: rng.integers(0, cfg.vocab_size,  # noqa: E731
                                     size=4).astype(np.int32)
    anchor = Request(0, np.concatenate([prefix, mk_tail(1)]),
                     max_new_tokens=24, sampler=greedy(), priority=1)
    victim = Request(1, np.concatenate([prefix, mk_tail(2)]),
                     max_new_tokens=8, sampler=greedy(), priority=0)
    expect = _direct_greedy(cfg, params, victim.prompt, 8, 32)
    eng = ServingEngine(cfg, params, max_len=44, batch_slots=2, paged=True,
                        block_size=bs, pool_blocks=10)
    admissions = []                      # (rid, prefill_len, seeded_rows)
    orig_mat = eng._materialize_blocks

    def spy(job):
        orig_mat(job)
        admissions.append((job.req.rid, len(job.tokens), job.pos))
    eng._materialize_blocks = spy

    eng.scheduler.submit(anchor)
    eng.scheduler.submit(victim)
    for _ in range(3):                   # both decoding, a few tokens out
        eng._step()
    assert victim.first_token_at is not None
    high = Request(2, np.arange(8, dtype=np.int32), max_new_tokens=2,
                   sampler=greedy(), priority=2)
    eng.scheduler.submit(high)           # no free slot -> preempts victim
    while eng.scheduler.has_work():
        eng._step()
    assert victim.preempted_count >= 1
    assert len(anchor.output) == 24 and len(high.output) == 2
    assert victim.output == expect       # seeded resume is exact
    resume = [a for a in admissions if a[0] == 1][-1]
    _, prefill_len, seeded_rows = resume
    assert prefill_len > len(victim.prompt)       # history folded in
    assert seeded_rows == len(prefix)             # whole prefix seeded...
    # ...so the resume computed zero prefix tokens: only the tail and the
    # generated history went through the prefill
    assert eng.pool.used_blocks == 0 and eng.pool.reserved_blocks == 0


# -- chunked prefill interleaves with decode steps ----------------------------

def test_chunked_prefill_interleaves_decode_steps():
    cfg, params = _smoke()
    rng = np.random.default_rng(23)
    dec = Request(0, rng.integers(0, cfg.vocab_size, size=6)
                  .astype(np.int32), max_new_tokens=24, sampler=greedy())
    big_prompt = rng.integers(0, cfg.vocab_size, size=64).astype(np.int32)
    big = Request(1, big_prompt.copy(), max_new_tokens=3, sampler=greedy())
    eng = ServingEngine(cfg, params, max_len=80, batch_slots=2, paged=True,
                        block_size=8, prefill_chunk=16)
    eng.scheduler.submit(dec)
    for _ in range(4):
        eng._step()
    eng.scheduler.submit(big)
    interleaved = 0
    while eng.scheduler.has_work():
        before = eng.totals.decode_steps
        had_prefill = bool(eng._prefilling)
        eng._step()
        if had_prefill and eng.totals.decode_steps > before:
            interleaved += 1
    # 64 tokens / 16-token chunks = 4 executor steps with a decode between
    assert interleaved >= 3
    assert dec.output == _direct_greedy(cfg, params, dec.prompt, 24, 80)
    assert big.output == _direct_greedy(cfg, params, big_prompt, 3, 80)
    assert eng.pool.used_blocks == 0 and eng.pool.reserved_blocks == 0


def test_chunked_prefill_still_seeds_shared_prefixes():
    """Chunked mode composes with seeding: block materialization is
    deferred to a job's first chunk, and jobs advance oldest-first, so a
    request admitted in the same batch as an identical-prefix
    predecessor still seeds the predecessor's published blocks — and the
    per-step budget is never overspent across jobs."""
    cfg, params = _smoke()
    eng = ServingEngine(cfg, params, max_len=48, batch_slots=4, paged=True,
                        block_size=8, prefill_chunk=16)
    spent = []
    orig = eng._advance_prefill

    def spy(slot, budget=None):
        real = orig(slot, budget)
        if spent and spent[-1] is not None:
            spent[-1] += real
        return real

    orig_step = eng._step

    def step_spy():
        spent.append(0 if eng._prefilling else None)
        return orig_step()
    eng._advance_prefill = spy
    eng._step = step_spy
    reqs = _prefix_workload(cfg)         # 4 x (32-token prefix + 5 tail)
    stats = eng.serve(reqs)
    rc = _prefix_workload(cfg)
    contig = ServingEngine(cfg, params, max_len=48, batch_slots=4,
                           paged=False)
    contig.serve(rc)
    assert [r.output for r in reqs] == [r.output for r in rc]
    # 3 of 4 requests seeded the full 4-block prefix despite same-step
    # admission (the first computes everything)
    assert stats.prefill_tokens_computed == stats.prefill_tokens_total \
        - 3 * 32
    # the chunked budget held: no executor step computed > prefill_chunk
    assert max((s for s in spent if s is not None), default=0) <= 16


def test_prefill_chunk_validation():
    cfg, params = _smoke()
    with pytest.raises(ValueError, match="multiple of block_size"):
        ServingEngine(cfg, params, paged=True, block_size=16,
                      prefill_chunk=24)
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(cfg, params, paged=False, prefill_chunk=16)


def test_paged_engine_rejects_sliding_window():
    """The paged attention paths are full-causal: a sliding-window arch
    must be refused rather than silently served with the wrong mask."""
    cfg, params = _smoke()
    sw = cfg.replace(sliding_window=4)
    with pytest.raises(ValueError, match="sliding_window"):
        ServingEngine(sw, params, paged=True)
    ServingEngine(sw, params, paged=False)   # contiguous path still fine


# -- prefix-index trim: stale entries first, then oldest live -----------------

def test_prefix_index_trim_drops_stale_before_live():
    cfg, params = _smoke()
    eng = ServingEngine(cfg, params, max_len=32, batch_slots=2, paged=True,
                        block_size=8, pool_blocks=8)
    pool = eng.pool
    pool.reserve(4)
    live_ids = pool.alloc_reserved(3)
    for i, b in enumerate(live_ids):     # live entries, oldest first
        eng._prefix_index[b"live%d" % i] = (b, pool.generation(b))
    [dead] = pool.alloc_reserved(1)
    gen = pool.generation(dead)
    pool.free([dead])
    eng._prefix_index[b"dead-freed"] = (dead, gen)
    eng._prefix_index[b"dead-stale"] = (live_ids[0],
                                        pool.generation(live_ids[0]) - 1)
    dummy = Request(9, np.zeros(1, np.int32))
    eng._prefix_cap = 3
    eng._register_prefix([], dummy)      # 5 entries > cap -> trim
    # dead entries went first; every live one survived
    assert set(eng._prefix_index) == {b"live0", b"live1", b"live2"}
    eng._prefix_cap = 2
    eng._register_prefix([], dummy)      # still over cap -> oldest live out
    assert set(eng._prefix_index) == {b"live1", b"live2"}
    pool.free(live_ids)
    pool.unreserve(0)
