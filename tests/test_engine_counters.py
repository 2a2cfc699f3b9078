"""The executor's host-time and queue-wait counters: ``step_host_s``,
``queue_wait_s`` and ``admissions`` in ``ServeStats``, the scheduler's
``admitted_at`` stamp, their window scoping and their fleet merge."""
import threading
import time

import jax
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
