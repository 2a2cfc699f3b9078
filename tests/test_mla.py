"""Latent attention (MLA): the absorbed form the serving path uses equals
the published form; paged prefill then decode through the latent pool
reproduces the full forward's logits; the engine serves it through the
paged path; an int8 latent pool is refused."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepseek_tiny as DT
from repro.models import transformer as T
from repro.models.layers import attention as A
from repro.models.registry import fns_for
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampler import greedy

# float32 throughout: the two forms differ only in the order of the
# contractions (the latent read out before or after attending), so they
# agree to float32 rounding over sums of a few hundred terms
F32_TOL = 1e-5


def _layer(cfg, params, S=40, seed=3):
    p = jax.tree_util.tree_map(lambda x: x[0], params["blocks"])["attn"]
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (2, S))
    return p, x, pos


def test_absorbed_equals_published_in_float32():
    cfg = DT.config()
    p, x, pos = _layer(cfg, DT.params(cfg))
    published = A.mla_attention(cfg, p, x, pos, chunk=16)
    q, e = A.mla_absorb(cfg, p, *A.mla_project(cfg, p, x, pos))
    r = cfg.mla.kv_lora_rank
    o_lat = A.chunked_attention(q, e, e[..., :r], causal=True,
                                q_positions=pos, kv_positions=pos, chunk=16,
                                scale=A.mla_scale(cfg))
    absorbed = A.mla_values(cfg, p, o_lat)
    assert published.shape == absorbed.shape == (2, 40, 4, 16)
    scale = float(jnp.abs(published).max())
    np.testing.assert_allclose(absorbed, published, atol=F32_TOL * scale)


def test_entry_is_latent_and_rope_key_padded_to_lanes():
    cfg = DT.config()
    p, x, pos = _layer(cfg, DT.params(cfg), S=8)
    _, _, c, k_pe = A.mla_project(cfg, p, x, pos)
    q, e = A.mla_absorb(cfg, p, *A.mla_project(cfg, p, x, pos))
    assert e.shape == (2, 8, 1, A.mla_entry_dim(cfg)) == (2, 8, 1, 128)
    np.testing.assert_array_equal(e[..., :32], c[:, :, None])
    np.testing.assert_array_equal(e[..., 32:40], k_pe)
    assert not bool(jnp.any(e[..., 40:])) and not bool(jnp.any(q[..., 40:]))


def _paged_logits(cfg, params, toks, chunk_rows, bs=16):
    """Prefill ``toks[:, :n]`` chunk by chunk into the latent pool, then
    decode the rest one token at a time; logits at every position after
    the first chunk's last."""
    S = toks.shape[1]
    mb = -(-S // bs) + 1
    cache = T.make_paged_cache(cfg, 1 + mb, bs, 1, mb, "float32")
    table = jnp.arange(1, mb + 1, dtype=jnp.int32)[None]
    cache = cache._replace(block_tables=table)
    out = {}
    n = 2 * chunk_rows
    for c0 in range(0, n, chunk_rows):
        ids = table[0, c0 // bs:(c0 + chunk_rows) // bs]
        lg, cache = T.prefill_paged(
            cfg, params, toks[:, c0:c0 + chunk_rows], cache, ids, table,
            q_start=jnp.array([c0]), kv_len=jnp.array([c0 + chunk_rows]),
            last_idx=jnp.int32(chunk_rows - 1))
        out[c0 + chunk_rows - 1] = lg[0]
    cache = cache._replace(length=jnp.array([n], jnp.int32))
    for t in range(n, S):
        lg, cache = T.decode_step(cfg, params, toks[:, t:t + 1], cache)
        out[t] = lg[0]
    return out, cache


@pytest.mark.parametrize("chunk_rows", [16, 32])
def test_paged_prefill_then_decode_matches_full_forward(chunk_rows):
    """The serving path (absorbed form, latent pool, chunked prefill and
    decode) against the published form's full forward, on logits."""
    cfg = DT.config()
    params = DT.params(cfg)
    toks = DT.tokens(2 * chunk_rows + 9)
    full, _ = T.forward(cfg, params, toks, remat=False)
    got, cache = _paged_logits(cfg, params, toks, chunk_rows)
    scale = float(jnp.abs(full).max())
    for t, lg in got.items():
        np.testing.assert_allclose(lg, full[0, t], atol=F32_TOL * scale,
                                   err_msg=f"position {t}")
    assert cache.v is None and cache.k.shape[2:] == (1, 16, 128)
    (dec_rows, dec_hits), (pre_rows, pre_hits) = np.asarray(
        cache.moe).tolist()
    assert 0 < pre_hits <= pre_rows and 0 < dec_hits <= dec_rows


def test_paged_path_through_the_pallas_kernels_matches_full_forward():
    """The same serving path with the Pallas kernels (interpret mode): the
    latent decode and prefill kernels and the grouped expert kernel, which
    reads each layer's experts inside the stacked weights."""
    from repro.kernels.dispatch import pallas_enabled
    cfg = DT.config()
    params = DT.params(cfg)
    toks = DT.tokens(2 * 16 + 3, seed=2)
    full, _ = T.forward(cfg, params, toks, remat=False)
    with pallas_enabled(True):
        got, _ = _paged_logits(cfg, params, toks, 16)
    scale = float(jnp.abs(full).max())
    for t, lg in got.items():
        np.testing.assert_allclose(lg, full[0, t], atol=F32_TOL * scale,
                                   err_msg=f"position {t}")


def test_verify_pass_scores_candidates_like_the_full_forward():
    """The speculative verify pass (several candidate rows per slot,
    row-scattered into the latent pool from mid-block) gives the full
    forward's logits at every candidate position."""
    cfg = DT.config()
    params = DT.params(cfg)
    toks = DT.tokens(44, seed=4)
    full, _ = T.forward(cfg, params, toks, remat=False)
    _, cache = _paged_logits(cfg, params, toks[:, :32], 16)
    table = cache.block_tables
    lg, cache = T.verify_paged(cfg, params, toks[:, 32:37], cache, table,
                               q_start=jnp.array([32]),
                               kv_len=jnp.array([37]))
    scale = float(jnp.abs(full).max())
    np.testing.assert_allclose(lg[0], full[0, 32:37], atol=F32_TOL * scale)
    assert int(np.asarray(cache.moe)[1, 0]) > 0     # counted as prefill


def test_engine_serves_mla_through_the_paged_path():
    """Greedy tokens served by the engine (chunked prefill, decode, latent
    pool) are the argmax of the full forward over prompt and output."""
    cfg = DT.config()
    params = DT.params(cfg)
    eng = ServingEngine(cfg, params, max_len=64, batch_slots=2,
                        prefill_chunk=16)
    assert eng.paged
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, 128, n).astype(np.int32),
                    max_new_tokens=6, sampler=greedy())
            for i, n in enumerate((21, 9, 40))]
    stats = eng.serve(reqs)
    assert stats.moe_rows > 0 and stats.moe_expert_hits > 0
    for r in reqs:
        seq = np.concatenate([r.prompt, r.output[:-1]]).astype(np.int32)
        lg, _ = T.forward(cfg, params, jnp.asarray(seq)[None], remat=False)
        lg = np.asarray(lg[0, len(r.prompt) - 1:])
        # each served token's logit within float32 rounding of the best
        gaps = lg.max(-1) - lg[np.arange(len(r.output)), r.output]
        assert gaps.max() < 1e-4 * np.abs(lg).max(), (r.rid, gaps)
    assert not any(eng.pool.leak_report().values())


def test_mla_int8_pool_is_refused():
    cfg = DT.config(dtype="bfloat16")
    params = fns_for(cfg).init(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="int8"):
        ServingEngine(cfg, params, max_len=32, batch_slots=1,
                      cache_dtype="int8")


def test_mla_has_no_contiguous_cache():
    cfg = DT.config()
    with pytest.raises(NotImplementedError):
        T.make_cache(cfg, 1, 32)
