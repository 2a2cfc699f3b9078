"""The executor's host-time, queue-wait and decode-page counters:
``step_host_s``, ``queue_wait_s``, ``admissions``, ``decode_pages_live``
and ``decode_pages_table`` in ``ServeStats``, the scheduler's
``admitted_at`` stamp, their window scoping and their fleet merge."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry as R
from repro.models.registry import fns_for
from repro.serving.engine import Request, ServeStats, ServingEngine
from repro.serving.sampler import greedy


@pytest.fixture(scope="module")
def smoke():
    cfg = R.smoke("qwen2.5-3b")
    return cfg, fns_for(cfg).init(cfg, jax.random.PRNGKey(0))


def _reqs(cfg, rids, n_new=3, size=6):
    rng = np.random.default_rng(rids[0])
    return [Request(rid, rng.integers(0, cfg.vocab_size, size=size)
                    .astype(np.int32), max_new_tokens=n_new,
                    sampler=greedy()) for rid in rids]


def _engine(cfg, params, slots=1):
    return ServingEngine(cfg, params, max_len=32, batch_slots=slots,
                         paged=True, block_size=4)


def test_queue_wait_sums_first_admissions(smoke):
    """One slot, four requests: each but the first waits for the one
    before it; the counter is the sum of their stamps' differences."""
    cfg, params = smoke
    reqs = _reqs(cfg, [0, 1, 2, 3])
    stats = _engine(cfg, params).serve(reqs)
    assert stats.admissions == len(reqs)
    for r in reqs:
        assert r.submitted_at <= r.admitted_at <= r.first_token_at
    assert stats.queue_wait_s == pytest.approx(
        sum(r.admitted_at - r.submitted_at for r in reqs))
    assert reqs[-1].admitted_at - reqs[-1].submitted_at > 0


def test_step_host_time_is_inside_the_window(smoke):
    cfg, params = smoke
    stats = _engine(cfg, params, slots=2).serve(_reqs(cfg, [0, 1, 2]))
    assert stats.decode_steps > 0
    assert 0 < stats.step_host_s < stats.wall_s


def test_windows_scope_the_counters(smoke):
    """A second window counts only its own requests and steps."""
    cfg, params = smoke
    eng = _engine(cfg, params, slots=2)
    first = eng.serve(_reqs(cfg, [0, 1, 2]))
    reqs = _reqs(cfg, [10, 11])
    second = eng.serve(reqs)
    assert (first.admissions, second.admissions) == (3, 2)
    assert second.queue_wait_s == pytest.approx(
        sum(r.admitted_at - r.submitted_at for r in reqs))
    assert first.queue_wait_s + second.queue_wait_s == pytest.approx(
        eng.scheduler.queue_wait_s)
    assert 0 < second.step_host_s < second.wall_s
    assert first.step_host_s + second.step_host_s == pytest.approx(
        eng.totals.step_host_s)


def test_a_preemption_resume_is_not_a_new_admission(smoke):
    """A high-priority arrival evicts the only decode; the victim resumes
    under its first ``admitted_at`` and is counted once."""
    cfg, params = smoke
    eng = ServingEngine(cfg, params, max_len=33, batch_slots=1, paged=True,
                        block_size=4, pool_blocks=8)
    low = Request(0, np.arange(8, dtype=np.int32), max_new_tokens=24,
                  sampler=greedy())
    high = Request(1, np.arange(4, dtype=np.int32), max_new_tokens=2,
                   sampler=greedy(), priority=1)
    ev_low, ev_high = threading.Event(), threading.Event()
    base = eng.begin_window()
    eng.start()
    try:
        eng.submit(low, on_finish=lambda r: ev_low.set())
        deadline = time.monotonic() + 60
        while low.first_token_at is None:
            assert time.monotonic() < deadline, "low request never started"
            time.sleep(0.005)
        first = low.admitted_at
        eng.submit(high, on_finish=lambda r: ev_high.set())
        assert ev_high.wait(60) and ev_low.wait(60)
    finally:
        eng.stop()
    stats = eng.collect_window(base, [low, high], 1.0)
    assert low.preempted_count >= 1 and stats.prefills >= 3
    assert low.admitted_at == first
    assert stats.admissions == 2
    assert stats.queue_wait_s == pytest.approx(
        (low.admitted_at - low.submitted_at)
        + (high.admitted_at - high.submitted_at))


def test_merge_sums_the_counters():
    a = ServeStats(step_host_s=1.25, queue_wait_s=2.0, admissions=3)
    a.merge_from(ServeStats(step_host_s=0.5, queue_wait_s=1.5, admissions=2))
    assert (a.step_host_s, a.queue_wait_s, a.admissions) == (1.75, 3.5, 5)


def _pages_read(reqs, bs):
    """Pages the decode kernel reads for these requests on one slot: the
    step that yields output j >= 1 attends prompt + j rows."""
    return sum((len(r.prompt) + j - 1) // bs + 1
               for r in reqs for j in range(1, r.max_new_tokens))


def test_decode_pages_follow_the_slots_lengths(smoke):
    """One slot, requests in turn: every decode step reads cdiv(rows, bs)
    pages of a max_blocks-wide table; two windows add up to the engine's
    lifetime totals, and their stats merge by sum."""
    cfg, params = smoke
    eng = _engine(cfg, params)
    first_reqs = _reqs(cfg, [0, 1], n_new=5, size=6)
    second_reqs = _reqs(cfg, [2], n_new=7, size=9)
    first, second = eng.serve(first_reqs), eng.serve(second_reqs)
    for stats, reqs in ((first, first_reqs), (second, second_reqs)):
        assert stats.decode_steps == sum(r.max_new_tokens - 1 for r in reqs)
        assert stats.decode_pages_live == _pages_read(reqs, eng.block_size)
        assert stats.decode_pages_table == (stats.decode_steps
                                            * eng.max_blocks)
    merged = ServeStats().merge_from(first).merge_from(second)
    assert (merged.decode_pages_live, merged.decode_pages_table) == (
        eng.totals.decode_pages_live, eng.totals.decode_pages_table)
    assert 0 < merged.decode_pages_live < merged.decode_pages_table


def test_held_expert_counters_window_merge_and_dense_zero(smoke):
    """``moe_rows`` / ``moe_expert_hits`` and their prefill parts: a model
    with held experts counts its routed work in each window, the windows
    add up to the running totals the step programs keep on the device,
    and merge by sum; a dense model counts nothing."""
    import deepseek_tiny as DT
    cfg = DT.config(dtype="bfloat16")
    eng = ServingEngine(cfg, DT.params(cfg), max_len=64, batch_slots=2,
                        prefill_chunk=16)
    first = eng.serve(_reqs(cfg, [0, 1], n_new=4, size=20))
    second = eng.serve(_reqs(cfg, [2], n_new=6, size=9))
    for stats in (first, second):
        assert 0 < stats.moe_expert_hits <= stats.moe_rows
    for stats in (first, second):
        assert 0 < stats.moe_rows_prefill < stats.moe_rows
        assert 0 < stats.moe_expert_hits_prefill < stats.moe_expert_hits
    merged = ServeStats().merge_from(first).merge_from(second)
    (dec_rows, dec_hits), (pre_rows, pre_hits) = np.asarray(
        eng._state.moe).tolist()
    assert (merged.moe_rows, merged.moe_expert_hits) == (
        eng.totals.moe_rows, eng.totals.moe_expert_hits) == (
        dec_rows + pre_rows, dec_hits + pre_hits)
    assert (merged.moe_rows_prefill, merged.moe_expert_hits_prefill) == (
        pre_rows, pre_hits)
    cfg, params = smoke
    dense = _engine(cfg, params).serve(_reqs(cfg, [3], n_new=3))
    assert (dense.moe_rows, dense.moe_expert_hits) == (0, 0)


def test_held_expert_counters_land_in_the_window_that_dispatched_them():
    """A window's snapshot pulls the routed work dispatched before it,
    though no logits have been read since: a prefill chunk dispatched
    before ``begin_window`` counts before it and not in the window."""
    import deepseek_tiny as DT
    cfg = DT.config(dtype="bfloat16")
    eng = ServingEngine(cfg, DT.params(cfg), max_len=64, batch_slots=2,
                        prefill_chunk=16)
    eng._state = eng._init_state()
    C = eng.block_size
    toks = np.asarray(DT.tokens(C, vocab=cfg.vocab_size))
    _, eng._state = eng._prefill_paged(
        eng.params, eng._put(toks), eng._state,
        eng._put(np.zeros((1,), np.int32)), eng._put(np.zeros((1, 1), np.int32)),
        eng._put(np.asarray([0], np.int32)),
        eng._put(np.asarray([C], np.int32)), jnp.int32(C - 1))
    base = eng.begin_window()
    assert 0 < base.moe_rows_prefill == base.moe_rows
    window = eng.collect_window(base, [], 0.0)
    assert (window.moe_rows, window.moe_expert_hits) == (0, 0)


def test_held_expert_counters_with_an_int8_pool():
    """Held experts behind an int8 K/V pool count their routed work as
    behind a bf16 one: the quantized cache carries the totals too."""
    import dataclasses
    import deepseek_tiny as DT
    from repro.models.transformer import QuantPagedKVCache
    cfg = dataclasses.replace(DT.config(dtype="bfloat16"), mla=None)
    eng = ServingEngine(cfg, DT.params(cfg), max_len=64, batch_slots=2,
                        prefill_chunk=16, cache_dtype="int8")
    stats = eng.serve(_reqs(cfg, [0, 1], n_new=4, size=20))
    assert isinstance(eng._state, QuantPagedKVCache)
    assert 0 < stats.moe_rows_prefill < stats.moe_rows
    assert 0 < stats.moe_expert_hits_prefill < stats.moe_expert_hits
