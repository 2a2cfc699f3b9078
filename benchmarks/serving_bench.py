"""LM serving benchmark: continuous batching vs the legacy wave decode.

Three scenarios, all real compute on this host, emitted as one JSON
artifact (`artifacts/bench/serving_bench.json`) with stable keys so runs
are comparable across PRs:

  1. `replicas_{1,2}` — replica scaling with least-loaded request pull
     (the paper's multi-NCS protocol at LM scale).
  2. `mixed_wave` / `mixed_continuous` — mixed-length requests (prompts
     6..19 tokens, max_new_tokens drawn from {4, 64}) on one replica with
     4 decode slots.  The wave path lock-steps every wave to its slowest
     member; continuous batching refills a slot the moment its request
     finishes.  `mixed_continuous` runs the paged KV engine with a block
     pool sized <= 50% of the worst-case contiguous footprint;
     `mixed_continuous_contig` is the contiguous A/B twin.
     `continuous_speedup` (paged vs wave) and `paged_vs_contiguous`
     (tokens/s ratio at half the KV memory) are the headline numbers, with
     `kv_pool_frac` / `prefill_compiles` showing where the win comes from
     (paging + prompt-length bucketing vs per-length recompiles).
  3. `arrival` — a seeded arrival process submitted against a running
     engine (service mode): requests admitted mid-stream, the scenario a
     batch-offline API cannot express.
  4. `priority_fifo` / `priority_slo` — the same pressure workload (long
     low-priority decodes wedging the pool, short high-priority requests
     arriving mid-stream) served without and with SLO-aware scheduling;
     `priority_hipri_ttft_p99_speedup` (high-priority p99 TTFT, FIFO /
     SLO) and `priority_tokens_cost_frac` (aggregate tokens/s given up to
     preemption recompute) are the headline pair.
  5. `shared_prefix` / `shared_prefix_nosharing` — N requests over one
     long common prompt prefix with refcounted prefix sharing on and off;
     with sharing the pool peaks below N x prefix-blocks
     (`shared_prefix_nominal_prefix_blocks`) because every request's
     leading table entries point at one shared copy.
  6. `seeded_prefill` / `seeded_prefill_recompute` — the cache-seeded
     prefill A/B: N co-resident requests over one long common prefix,
     served with seeding on (prefill computation starts at the first
     unseeded token) and off (PR-3 behaviour: shared blocks mapped but
     every prompt token re-run into the trash block).
     `prefill_tokens_computed` vs `prefill_tokens_total` is the headline
     pair — seeded compute must drop proportionally to the shared
     fraction — with `seeded_outputs_match` asserting the greedy streams
     are identical token for token.
  7. `chunked_interleave` / `chunked_interleave_off` — a 1024-token
     prompt arriving mid-decode, prefilled in 64-token chunks interleaved
     with decode steps vs all at once; `decode_stall_p99_ms` (the p99 gap
     between consecutive decode steps) is the headline — un-chunked, the
     whole prefill shows up as one giant stall for every active decode.
  8. `router_affinity` / `router_least_loaded` — a shared-prefix workload
     across 2 replicas, routed with fleet-wide prefix-affinity dispatch vs
     the PR-1 request-count least-loaded baseline.  Affinity lands every
     same-prefix request on the replica already holding the blocks, so the
     fleet `prefill_compute_frac` approaches the single-replica seeded
     number (`router_single_replica` is the reference) instead of paying
     the prefix once *per replica*; greedy outputs are asserted identical
     to single-replica serving.
  9. `router_steal` / `router_no_steal` — skewed arrivals: two long
     decodes over a shared prefix pin the affinity owner's slots and pool
     while short same-prefix requests queue behind them and the peer
     idles; with work stealing the idle replica pulls the shorts off the
     backlog, repairing `ttft_p99_ms` (queue position, not CPU
     parallelism, so the win survives this 1-core host) at equal
     deterministic token counts — the relief valve the affinity policy
     relies on.
 11. `tiered_churn` / `tiered_churn_recompute` — distinct shared prefixes
     cycle through a device pool capped at <= 50% of the working set, so
     every prefix is evicted before its revisit.  Tiered, eviction demotes
     the published prefix to the host tier and the revisit *restores* it
     over the async split-phase offload protocol; untiered, the revisit
     recomputes the prompt.  `prefill_compute_frac` is the headline pair
     (asserted lower tiered), greedy outputs asserted bit-identical.
 12. `tiered_longctx` / `tiered_longctx_recompute` — N long-prompt
     requests whose combined logical KV footprint is ~3x the device pool;
     the workload physically cannot keep its KV resident, and the tiered
     engine completes it by riding the demoted history in host memory
     (spills/fetches asserted > 0) instead of re-running the long prefill
     per request.  Plus `pool_microbench`: KVBlockPool hot-path block-ops/s
     across pool sizes spanning 64x (O(1)-per-block audit evidence).
 13. `chaos` — fault-tolerance under a deterministic FaultPlan: one of 2
     tiered replicas has its executor killed mid-serve, a decode commit is
     poisoned on the survivor, and KV fetch transfers are dropped.  The
     recovery contract is *asserted*: every request completes, retried
     requests regenerate bit-identically on the survivor (a retry restarts
     from the bare prompt), the dead replica is quarantined, and both
     block pools drain leak-free.

Wall-clock A/Bs run median-of-`--repeats` (default 3) on a warm engine
via one shared `_median_of` harness (this single-core host's clock
jitters ~25%, so the median policy lives in exactly one place).  Each
scenario reports tokens/s, TTFT p50/p99 (ms), mean TPOT (ms), slot
occupancy, prefill jit compiles, prefill tokens computed vs total,
decode-stall p99, preemptions, prefix-shared table entries, router
affinity hits / steals, SLO miss rate, and (paged) peak KV-pool blocks
and utilization plus the tiering counters (spills, fetches, host prefix
hits, spill bytes, hit rate), plus the fault-tolerance counters
(requests failed/retried, replica failures, shed rejections, faults
injected), plus the disaggregation counters (KV migrations, migrated
blocks).  The headline numbers are also written to repo-root
`BENCH_{5,6,7,9,10}.json` trajectory artifacts via one shared
`_write_headline` writer (stable key order, mandatory `method` string).
`--smoke` runs a tiny 2-replica affinity + steal + spec + tiered-churn
+ disagg + chaos subset in seconds for CI (JSON artifact uploaded by
the tier-1 workflow).
"""
from __future__ import annotations

import argparse
import json
import os
import threading
import time

import jax
import numpy as np

from repro.configs import registry as arch_registry
from repro.core.power import serving_power_report
from repro.models.registry import fns_for
from repro.serving.engine import Request, ServeStats, ServingEngine
from repro.serving.faults import FaultPlan, FaultSpec
from repro.serving.kv_pool import KVBlockPool
from repro.serving.router import (MultiReplicaEngine, ReplicaHealth,
                                  ReplicaRouter)
from repro.serving.sampler import greedy
from repro.serving.scheduler import RequestState

from benchmarks.common import save_artifact


def _median_run(runs: list):
    """THE median-of-N selection policy for wall-clock A/Bs, in one
    place: given ``(wall_s, *rest)`` tuples, return the run with the
    median wall clock.  Token counts must be deterministic across repeats
    so the reported run is output-comparable between A/B arms."""
    return sorted(runs, key=lambda r: r[0])[len(runs) // 2]


def _median_of(repeats: int, run_once):
    """Run ``run_once(rep)`` ``repeats`` times on the caller's (warm)
    engine and report the :func:`_median_run` — this single-core host's
    wall clock jitters ~25%; every scenario that used to hand-roll this
    loop now shares it (multi-arm scenarios that interleave their repeats
    collect runs themselves and call :func:`_median_run` directly)."""
    return _median_run([run_once(rep) for rep in range(repeats)])


def _requests(cfg, n, prompt_len=12, new_tokens=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size,
                                    size=prompt_len).astype(np.int32),
                    max_new_tokens=new_tokens, sampler=greedy())
            for i in range(n)]


def _mixed_requests(cfg, n=16, seed=0):
    """Alternating short/long decodes over *varied* prompt lengths: the
    stressor for both continuous batching (ragged finish times) and the
    prefill compile cache (ragged prompt shapes)."""
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size,
                                    size=int(rng.integers(6, 20)))
                    .astype(np.int32),
                    max_new_tokens=4 if i % 2 else 64, sampler=greedy())
            for i in range(n)]


def _shared_prefix_requests(cfg, n=6, prefix_blocks=2, block=16, seed=4,
                            new_tokens=4, tail=8):
    """N prompts sharing a ``prefix_blocks``-block common prefix with
    distinct ``tail``-token tails: with refcounted prefix sharing the pool
    holds ONE copy of the prefix instead of N.  Everything (prefix and
    tails) derives from ``seed``, so two arms built with the same seed get
    token-identical workloads."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size,
                          size=prefix_blocks * block).astype(np.int32)
    return [Request(i, np.concatenate(
                    [prefix, rng.integers(0, cfg.vocab_size, size=tail)
                     .astype(np.int32)]),
                    max_new_tokens=new_tokens, sampler=greedy())
            for i in range(n)]


def _run_pressure(cfg, params, *, slo_aware: bool, repeats: int = 3):
    """Queue-pressure A/B arm: 8 long low-priority decodes wedge every
    slot and pool block; 4 short requests arrive mid-stream.
    ``slo_aware=True`` marks the late arrivals priority-2 with a TTFT SLO
    (they preempt); ``False`` leaves everything priority-0 (the old FIFO
    behaviour: late arrivals wait behind every queued long decode).

    The median-wall run of ``repeats`` (see :func:`_median_of`) is
    reported: the wall-clock noise would swamp the few-percent
    preemption-recompute cost the A/B is trying to measure."""
    slots, block, low_new = 4, 16, 192
    rows = 8 + low_new - 1
    pool = slots * -(-rows // block)     # lows wedge the pool exactly
    eng = ServingEngine(cfg, params, max_len=8 + low_new + 1,
                        batch_slots=slots, paged=True, block_size=block,
                        pool_blocks=pool)
    # warm the (slots, 1) decode signature and the 16..128 prefill buckets
    # this run can hit (preemption re-prefills prompt + generated tokens)
    eng.serve(_requests(cfg, slots, prompt_len=8, new_tokens=2, seed=99))
    for n, plen in ((2, 20), (2, 33), (2, 65)):
        eng.serve(_requests(cfg, n, prompt_len=plen, new_tokens=2,
                            seed=90 + plen))

    def run_once(rep):
        rng = np.random.default_rng(3 + rep)
        lows = [Request(i, rng.integers(0, cfg.vocab_size, size=8)
                        .astype(np.int32), max_new_tokens=low_new,
                        sampler=greedy())
                for i in range(8)]
        highs = [Request(100 + i, rng.integers(0, cfg.vocab_size, size=8)
                         .astype(np.int32), max_new_tokens=4,
                         sampler=greedy(),
                         priority=2 if slo_aware else 0,
                         slo_ttft_s=0.5 if slo_aware else None)
                 for i in range(4)]
        done = threading.Event()
        remaining = [len(lows) + len(highs)]

        def fin(_, remaining=remaining, done=done):
            remaining[0] -= 1
            if remaining[0] == 0:
                done.set()

        base = eng.begin_window()
        eng.start()
        t0 = time.monotonic()
        for r in lows:
            eng.submit(r, on_finish=fin)
        time.sleep(0.1)              # lows now hold every pool block
        for r in highs:
            eng.submit(r, on_finish=fin)
        done.wait(timeout=180)
        wall = time.monotonic() - t0
        eng.stop()
        stats = eng.collect_window(base, lows + highs, wall)
        # censor a never-served request's TTFT at the window wall so a
        # timeout degrades the number instead of crashing the percentile
        ttfts = [r.ttft_s if r.ttft_s is not None else wall for r in highs]
        p99_ms = round(float(np.percentile(ttfts, 99)) * 1e3, 2)
        return wall, stats, p99_ms

    _, stats, p99_ms = _median_of(repeats, run_once)
    return stats, p99_ms


def _run_seeded(cfg, params, *, seeded: bool, repeats: int = 3):
    """Cache-seeded prefill A/B arm: 6 co-resident requests over one
    64-token (4-block) common prefix with 8-token tails.  ``seeded=True``
    starts prefill computation at the first unseeded token; ``False`` is
    the PR-3 recompute baseline (shared blocks mapped, every prompt token
    re-run into the trash block).  Median-wall run of ``repeats`` on a
    warm engine (:func:`_median_of`); token counts are deterministic, wall
    clock is not."""
    n = 6
    eng = ServingEngine(cfg, params, max_len=64 + 8 + 4 + 1, batch_slots=n,
                        paged=True, block_size=16, seeded_prefill=seeded)
    mk = lambda: _shared_prefix_requests(cfg, n=n, prefix_blocks=4,  # noqa
                                         block=16, seed=21)
    eng.serve(mk())                     # warm: compiles + prefix publish

    def run_once(_rep):
        reqs = mk()
        stats = eng.serve(reqs)
        return stats.wall_s, stats, [r.output for r in reqs]

    _, stats, outputs = _median_of(repeats, run_once)
    return stats, outputs


def _run_spec(cfg, params, *, spec: bool, cache_dtype: str = "bfloat16",
              repeats: int = 3, n: int = 6, slots: int = 4,
              new_tokens: int = 16):
    """Speculative decoding A/B arm: the drafter shares the target's
    weights (self-speculation), so the accept rate is high without a
    second trained model and the step-count win is reproducible on this
    host.  Greedy requests only; the ``spec=False`` baseline must emit
    bit-identical streams — the caller asserts it.  Wall clock is
    *reported*, not asserted: off-TPU the drafter contends for the same
    single core, so the headline here is target-model steps per token."""
    kw = dict(max_len=48, batch_slots=slots, paged=True, block_size=16,
              cache_dtype=cache_dtype)
    if spec:
        kw.update(draft_cfg=cfg, draft_params=params, spec_k=3)
    eng = ServingEngine(cfg, params, **kw)
    mk = lambda: _requests(cfg, n, prompt_len=12,  # noqa: E731
                           new_tokens=new_tokens, seed=33)
    eng.serve(mk())                     # warm: compiles verify + drafter

    def run_once(_rep):
        reqs = mk()
        stats = eng.serve(reqs)
        return stats.wall_s, stats, [r.output for r in reqs]

    _, stats, outputs = _median_of(repeats, run_once)
    return stats, outputs


def _run_chunked(cfg, params, *, chunk: int | None, repeats: int = 3):
    """Chunked-interleave A/B arm: 3 short-prompt decodes are mid-stream
    when a 1024-token prompt arrives.  With ``chunk`` set its prefill runs
    in chunk-token slices between decode steps; with ``None`` it stalls
    every active decode for the whole prefill (the stall is the window's
    ``decode_stall_p99``).  Driven synchronously through the executor
    step so arrival timing is identical across arms, and the workload
    tokens are fixed across repeats so the reported (median-wall) run is
    output-comparable between arms; median-of-``repeats`` on a warm
    engine (:func:`_median_of`)."""
    P = 1024
    eng = ServingEngine(cfg, params, max_len=P + 16, batch_slots=4,
                        paged=True, block_size=16, prefill_chunk=chunk)
    # warm every jitted signature both arms can hit: the (4, 1) decode,
    # short-prompt buckets, and the long prompt's chunk/bucket shapes
    eng.serve(_requests(cfg, 4, prompt_len=8, new_tokens=2, seed=98))
    eng.serve(_requests(cfg, 1, prompt_len=P, new_tokens=2, seed=97))
    rng = np.random.default_rng(31)
    dec_prompts = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
                   for _ in range(3)]
    big_prompt = rng.integers(0, cfg.vocab_size, size=P).astype(np.int32)

    def run_once(rep):
        decs = [Request(10 * rep + i, p, max_new_tokens=48,
                        sampler=greedy())
                for i, p in enumerate(dec_prompts)]
        big = Request(10 * rep + 9, big_prompt, max_new_tokens=4,
                      sampler=greedy())
        base = eng.begin_window()
        t0 = time.monotonic()
        for r in decs:
            eng.scheduler.submit(r)
        for _ in range(8):              # decodes are cruising...
            eng._step()
        eng.scheduler.submit(big)       # ...when the long prompt lands
        while eng.scheduler.has_work():
            eng._step()
        wall = time.monotonic() - t0
        stats = eng.collect_window(base, decs + [big], wall)
        return wall, stats, [r.output for r in decs + [big]]

    _, stats, outputs = _median_of(repeats, run_once)
    return stats, outputs


def _warm_prefix_fleet(cfg, params, n_replicas, *, slots, max_len, block,
                       prefix_blocks):
    """2-to-N warm replicas for the router A/Bs: every replica compiles
    the same prefill/decode signatures *directly* (a routed warmup would
    leave the affinity arm's idle replica cold), using an unrelated warm
    prefix so the measured runs' prefixes are cold in every index."""
    replicas = [ServingEngine(cfg, params, max_len=max_len,
                              batch_slots=slots, paged=True,
                              block_size=block)
                for _ in range(n_replicas)]
    for e in replicas:
        e.serve(_shared_prefix_requests(cfg, n=min(slots, 3),
                                        prefix_blocks=prefix_blocks,
                                        block=block, seed=77,
                                        new_tokens=2))
    return replicas


def _run_router_prefix(cfg, params, *, repeats: int = 3, n: int = 6,
                       prefix_blocks: int = 4, new_tokens: int = 4):
    """Fleet prefix-affinity A/B: ``n`` requests over one fresh common
    prefix, routed across 2 replicas with prefix-affinity dispatch vs the
    PR-1 request-count least-loaded baseline, plus a warm single-replica
    reference.  Affinity lands every same-prefix request on the replica
    that computed the prefix, so the *fleet* ``prefill_compute_frac``
    matches the single-replica seeded number; least-loaded spreads the
    burst and pays the prefix once per replica.  A fresh prefix per repeat
    keeps each measurement first-contact (a warm index would let both
    arms seed everything); greedy outputs are compared per-repeat against
    single-replica serving of the identical workload."""
    block, tail = 16, 8
    max_len = prefix_blocks * block + tail + new_tokens + 1
    arms = {}
    for key, affinity in (("router_affinity", True),
                          ("router_least_loaded", False)):
        replicas = _warm_prefix_fleet(cfg, params, 2, slots=n,
                                      max_len=max_len, block=block,
                                      prefix_blocks=prefix_blocks)
        arms[key] = (ReplicaRouter(replicas, affinity=True, steal=False)
                     if affinity else MultiReplicaEngine(replicas))
    [ref_eng] = _warm_prefix_fleet(cfg, params, 1, slots=n,
                                   max_len=max_len, block=block,
                                   prefix_blocks=prefix_blocks)
    runs = {key: [] for key in arms}
    ref_runs = []
    match = True
    for rep in range(repeats):
        mk = lambda: _shared_prefix_requests(  # noqa: E731
            cfg, n=n, prefix_blocks=prefix_blocks, block=block,
            seed=210 + rep, new_tokens=new_tokens)
        ref_reqs = mk()
        ref_stats = ref_eng.serve(ref_reqs)
        ref_runs.append((ref_stats.wall_s, ref_stats))
        ref_out = [r.output for r in ref_reqs]
        for key, router in arms.items():
            reqs = mk()
            stats = router.serve(reqs)
            runs[key].append((stats.wall_s, stats))
            match = match and [r.output for r in reqs] == ref_out
    return ({key: _median_run(rs)[1] for key, rs in runs.items()},
            _median_run(ref_runs)[1], match)


def _run_router_steal(cfg, params, *, repeats: int = 3, n_short: int = 6,
                      long_tokens: int = 192, short_tokens: int = 8):
    """Skewed-arrivals work-stealing A/B: two *long* decodes over a
    shared prefix pin the affinity owner's both slots — and, by
    construction, its entire block pool — while ``n_short`` short
    same-prefix requests queue behind them and the peer replica idles.
    Without stealing, a short request's first token waits for a long
    decode to finish; with stealing, the idle replica pulls the shorts
    off the backlog and serves them immediately.  TTFT p99 (the shorts'
    wait) is the headline; it is *structural* — queue position, not CPU
    parallelism — so it survives this 1-core host, *provided* the longs
    far outlast the migration: the thief serves every short while the
    longs still run, so no short is left waiting on the (now contended)
    donor.  Token counts are deterministic and equal across arms (greedy
    outputs asserted identical).  The stolen shorts recompute the prefix
    on the thief (its pool does not hold the blocks): that
    prefill-compute cost, visible in ``prefill_tokens_computed``, is the
    price of the latency repair."""
    block, prefix_blocks, tail, slots = 16, 2, 8, 2
    max_len = prefix_blocks * block + tail + long_tokens + 1
    routers = {}
    for key, steal in (("router_steal", True), ("router_no_steal", False)):
        replicas = _warm_prefix_fleet(cfg, params, 2, slots=slots,
                                      max_len=max_len, block=block,
                                      prefix_blocks=prefix_blocks)
        routers[key] = ReplicaRouter(replicas, affinity=True, steal=steal,
                                     steal_interval_s=0.002)
    runs = {key: [] for key in routers}
    match = True
    for rep in range(repeats):
        outs = {}
        for key, router in routers.items():
            reqs = _shared_prefix_requests(
                cfg, n=2 + n_short, prefix_blocks=prefix_blocks,
                block=block, seed=230 + rep, new_tokens=short_tokens)
            for r in reqs[:2]:          # first-arrived pair pins the owner
                r.max_new_tokens = long_tokens
            stats = router.serve(reqs)
            runs[key].append((stats.wall_s, stats))
            outs[key] = [r.output for r in reqs]
        match = match and outs["router_steal"] == outs["router_no_steal"]
    return {key: _median_run(rs)[1] for key, rs in runs.items()}, match


def _run_disagg(cfg, params, *, repeats: int = 3, n_dec: int = 4,
                dec_tokens: int = 64, n_big: int = 1, big_len: int = 1024,
                big_tokens: int = 4, chunk: int = 32):
    """Disaggregated prefill/decode A/B: a burst of ``n_big`` long
    prompts lands on a fleet already decoding ``n_dec`` short requests.
    The ``interleaved_single_pool`` arm is 2 mixed replicas with chunked
    prefill — every long prompt shares a replica (and its step loop)
    with live decodes, so each prefill chunk is a decode stall and each
    interleaved decode step stretches the long prompt's TTFT.  The
    ``disagg`` arm is 1 prefill-role + 1 decode-role replica with the
    same chunk: prompts prefill at full budget with zero decode slots
    contending, then their KV blocks migrate to the decode replica,
    which never computes a prompt token.  Both arms serve identical
    token workloads (median-of-``repeats``, greedy outputs compared
    against a warm single-replica reference) and the migration
    invariants — zero decode-side prompt recompute, leak-free pools on
    both ends after draining — are asserted here, per repeat, not just
    reported."""
    block, slots = 16, n_dec + n_big
    kw = dict(max_len=big_len + big_tokens + block, batch_slots=slots,
              paged=True, block_size=block, prefill_chunk=chunk)
    rng = np.random.default_rng(41)
    dec_prompts = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
                   for _ in range(n_dec)]
    big_prompts = [rng.integers(0, cfg.vocab_size,
                                size=big_len).astype(np.int32)
                   for _ in range(n_big)]

    def mk_reqs(rep):
        shorts = [Request(100 * rep + i, p, max_new_tokens=dec_tokens,
                          sampler=greedy())
                  for i, p in enumerate(dec_prompts)]
        bigs = [Request(100 * rep + 50 + i, p, max_new_tokens=big_tokens,
                        sampler=greedy())
                for i, p in enumerate(big_prompts)]
        return shorts + bigs

    def warm(e):
        # roles are routing policy, not capability: a prefill- or
        # decode-role engine warms standalone like any other, hitting
        # the short-prompt, chunked-long-prompt and decode signatures
        e.serve(_requests(cfg, min(4, slots), prompt_len=8, new_tokens=2,
                          seed=96))
        e.serve([Request(0, big_prompts[0], max_new_tokens=2,
                         sampler=greedy())])

    ref = ServingEngine(cfg, params, **kw)
    warm(ref)
    ref_reqs = mk_reqs(9)
    ref.serve(ref_reqs)
    ref_out = [r.output for r in ref_reqs]

    arms = {}
    for key, roles in (("interleaved_single_pool", ("mixed", "mixed")),
                       ("disagg", ("prefill", "decode"))):
        replicas = [ServingEngine(cfg, params, name=f"{key}-{i}",
                                  role=role, **kw)
                    for i, role in enumerate(roles)]
        for e in replicas:
            warm(e)
        router = ReplicaRouter(replicas, affinity=False, steal=False)
        # warm the *fleet* path too: the disagg arm's adoption scatter
        # compiles per pow-2 block-count bucket, and an unwarmed compile
        # inside the measured window would read as a ~200ms decode stall
        router.serve([Request(9001, dec_prompts[0], max_new_tokens=2,
                              sampler=greedy()),
                      Request(9002, big_prompts[0], max_new_tokens=2,
                              sampler=greedy())])
        arms[key] = (router, replicas)

    runs = {key: [] for key in arms}
    match = True
    windows = []
    for rep in range(repeats):
        for key, (router, replicas) in arms.items():
            reqs = mk_reqs(rep)
            base = (replicas[1].begin_window() if key == "disagg"
                    else None)
            stats = router.serve(reqs)
            match = match and [r.output for r in reqs] == ref_out
            if key == "disagg":
                # the decode replica's own window is the zero-recompute
                # evidence: every prompt token it serves arrived by
                # migration, none were recomputed
                w = replicas[1].collect_window(base, [], stats.wall_s)
                assert w.prefill_tokens_computed == 0, (
                    f"decode replica recomputed "
                    f"{w.prefill_tokens_computed} prompt tokens")
                assert w.kv_migrations == len(reqs), \
                    f"{w.kv_migrations} adoptions for {len(reqs)} requests"
                windows.append(w)
            # serve() drains in-flight migrations before returning, so
            # the export pins must be gone right here, every repeat
            for e in replicas:
                e.pool.assert_leak_free()
            runs[key].append((stats.wall_s, stats))
    for _, (router, _) in arms.items():
        router.stop()
    # the A/B direction is asserted on per-metric medians across
    # repeats, not on the median-wall run's values: a single OS
    # scheduling outlier inside one repeat must not decide the verdict
    med = {key: {"decode_stall_p99_ms": round(float(np.median(
                     [s.decode_stall_p99_s for _, s in rs])) * 1e3, 2),
                 "ttft_p99_ms": round(float(np.median(
                     [s.ttft_p99_s for _, s in rs])) * 1e3, 2)}
           for key, rs in runs.items()}
    return ({key: _median_run(rs)[1] for key, rs in runs.items()},
            med, windows[len(windows) // 2], match)


def _run_migrate_chaos(cfg, params, *, n_dec: int = 3, n_big: int = 1,
                       big_len: int = 64, chunk: int = 32) -> dict:
    """kv.migrate chaos companion: same disaggregated shape, but a
    deterministic :class:`FaultPlan` drops the first two migration
    transfers in flight.  A dropped handoff loses the KV copies — the
    request fails on the source, the router retries it from its bare
    prompt, and greedy regeneration stays bit-identical to an unfaulted
    reference.  Completion, output equality, a nonzero retry count and
    leak-free pools on BOTH ends are asserted."""
    block = 16
    kw = dict(max_len=big_len + 4 + block, batch_slots=n_dec + n_big,
              paged=True, block_size=block, prefill_chunk=chunk)
    rng = np.random.default_rng(43)
    prompts = ([rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
                for _ in range(n_dec)]
               + [rng.integers(0, cfg.vocab_size,
                               size=big_len).astype(np.int32)
                  for _ in range(n_big)])
    mk_reqs = lambda: [Request(i, p, max_new_tokens=4,  # noqa: E731
                               sampler=greedy())
                       for i, p in enumerate(prompts)]
    ref = mk_reqs()
    ServingEngine(cfg, params, name="ref", **kw).serve(ref)
    plan = FaultPlan([FaultSpec("kv.migrate", "drop", count=2)])
    replicas = [ServingEngine(cfg, params, name="pre0", role="prefill",
                              fault_plan=plan, **kw),
                ServingEngine(cfg, params, name="dec0", role="decode",
                              fault_plan=plan, **kw)]
    router = ReplicaRouter(replicas, affinity=False, steal=False,
                           max_retries=3)
    reqs = mk_reqs()
    stats = router.serve(reqs)
    router.stop()
    assert all(r.state is RequestState.DONE for r in reqs), \
        [(r.rid, r.state, r.error) for r in reqs]
    assert [r.output for r in reqs] == [r.output for r in ref], \
        "post-retry outputs diverged from the unfaulted reference"
    assert stats.requests_retried >= 1, \
        "dropped migrations forced no retry"
    leaks = {}
    for e in replicas:
        leaks[e.name] = e.pool.leak_report()
        e.pool.assert_leak_free()
    return {"migrate_chaos": _summary(stats),
            "migrate_chaos_faults_fired": plan.fired,
            "migrate_chaos_outputs_match_reference": True,
            "migrate_chaos_leak_report": leaks}


def _tiered_churn_requests(cfg, *, groups, visits, prefix_blocks, block,
                           tail, new_tokens, seed):
    """``groups`` distinct shared prefixes revisited ``visits`` times with
    fresh tails per visit, in round-robin order — so by the time a prefix
    is revisited, the intervening groups have churned it out of a small
    device pool.  Everything derives from ``seed``: two arms built with
    the same seed get token-identical workloads."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, cfg.vocab_size,
                             size=prefix_blocks * block).astype(np.int32)
                for _ in range(groups)]
    reqs = []
    for v in range(visits):
        for g, prefix in enumerate(prefixes):
            t = rng.integers(0, cfg.vocab_size, size=tail).astype(np.int32)
            reqs.append(Request(v * groups + g, np.concatenate([prefix, t]),
                                max_new_tokens=new_tokens, sampler=greedy()))
    return reqs


def _run_tiered_churn(cfg, params, *, tiered: bool, repeats: int = 3,
                      groups: int = 4, visits: int = 2,
                      prefix_blocks: int = 3, new_tokens: int = 4):
    """Tiered-KV churn A/B arm: ``groups`` distinct multi-block prefixes
    cycle through a 1-slot engine whose device pool holds <= 50% of the
    working set, so every prefix is evicted before its revisit.  Tiered,
    eviction *demotes* the published prefix to the host tier and the
    revisit restores it over the split-phase offload protocol (prefetch
    issued at admission, overlapped with the decode in flight); untiered,
    the revisit recomputes the whole prompt.  Prefill tokens computed is
    the headline pair; greedy outputs are asserted identical because a
    restored block is the exact bytes that were spilled."""
    block, tail = 16, 8
    # per-request demand: prefix + tail + decode rows
    per_req = (prefix_blocks * block + tail + new_tokens + block - 1) // block
    pool_blocks = per_req + 2           # room to keep SOME prefixes resident
    working_set = groups * per_req
    assert pool_blocks * 2 <= working_set, "churn needs pool <= 50% of set"
    eng = ServingEngine(cfg, params,
                        max_len=prefix_blocks * block + tail + new_tokens + 1,
                        batch_slots=1, paged=True, block_size=block,
                        pool_blocks=pool_blocks,
                        host_blocks=8 * groups * per_req if tiered else 0)
    eng.serve(_tiered_churn_requests(cfg, groups=2, visits=1,
                                     prefix_blocks=prefix_blocks, block=block,
                                     tail=tail, new_tokens=2, seed=9_900))

    def run_once(rep):
        reqs = _tiered_churn_requests(cfg, groups=groups, visits=visits,
                                      prefix_blocks=prefix_blocks,
                                      block=block, tail=tail,
                                      new_tokens=new_tokens, seed=700 + rep)
        t = eng.serve(reqs)
        return t.wall_s, t, [r.output for r in reqs]

    wall, stats, outs = _median_of(repeats, run_once)
    return stats, outs, {"pool_blocks": pool_blocks,
                         "working_set_blocks": working_set}


def _run_tiered_longctx(cfg, params, *, tiered: bool, n: int = 4,
                        prefix_blocks: int = 10, new_tokens: int = 4):
    """Long-context tiering arm: ``n`` requests over one long shared
    prefix whose combined logical KV footprint is several times the
    device pool, served through 1 slot so each request churns its
    predecessor's history out of the pool.  The workload physically
    cannot keep its KV resident — tiered, the demoted prefix rides in the
    host tier and each successor *restores* it instead of re-running the
    long prompt; untiered, every request pays the full prefill again.
    Deterministic (no repeats needed for the headline token counts)."""
    block, tail = 16, 8
    P = prefix_blocks * block + tail
    per_req = (P + new_tokens + block - 1) // block
    pool_blocks = per_req + 2
    logical_blocks = n * per_req
    assert pool_blocks < logical_blocks, "long-context must outsize the pool"
    eng = ServingEngine(cfg, params, max_len=P + new_tokens + 1,
                        batch_slots=1, paged=True, block_size=block,
                        pool_blocks=pool_blocks,
                        host_blocks=4 * logical_blocks if tiered else 0)
    reqs = _tiered_churn_requests(cfg, groups=1, visits=n,
                                  prefix_blocks=prefix_blocks, block=block,
                                  tail=tail, new_tokens=new_tokens, seed=810)
    stats = eng.serve(reqs)
    completed = all(len(r.output) == new_tokens for r in reqs)
    return stats, [r.output for r in reqs], {
        "pool_blocks": pool_blocks, "logical_blocks": logical_blocks,
        "completed": completed}


def _run_chaos(cfg, params, *, n: int = 6, new_tokens: int = 4) -> dict:
    """Fault-tolerance chaos scenario: 2 tiered replicas serve a
    shared-prefix workload while one deterministic :class:`FaultPlan`
    kills replica0's executor mid-stream, poisons one decode commit on
    the survivor, and drops KV fetch transfers.  The router quarantines
    the dead replica and reissues its queued + in-flight requests to the
    survivor; a retried request restarts from its bare prompt, so greedy
    regeneration is *bit-identical* to an unfaulted single-replica
    reference.  The recovery properties are **asserted**, not just
    reported — every request completes, fleet-merged ``requests_retried``
    and ``replica_failures`` are nonzero, and after draining in-flight
    tier IO both pools are leak-free (the tentpole invariant: any fault
    sequence leaves zero leaked blocks)."""
    block, prefix_blocks, tail = 8, 2, 8
    kw = dict(max_len=prefix_blocks * block + tail + new_tokens + 1,
              batch_slots=2, paged=True, block_size=block,
              pool_blocks=10, host_blocks=32)
    mk_reqs = lambda: _shared_prefix_requests(  # noqa: E731
        cfg, n=n, prefix_blocks=prefix_blocks, block=block, seed=61,
        new_tokens=new_tokens)
    ref = mk_reqs()
    ServingEngine(cfg, params, name="ref", **kw).serve(ref)
    plan = FaultPlan([
        FaultSpec("replica.executor", "raise", after=2, replica="replica0"),
        FaultSpec("engine.decode", "raise", after=6, count=1,
                  replica="replica1"),
        FaultSpec("kv.fetch", "drop", count=2),
    ])
    replicas = [ServingEngine(cfg, params, name=f"replica{i}",
                              fault_plan=plan, **kw) for i in range(2)]
    router = ReplicaRouter(replicas, affinity=False, steal=True,
                           steal_interval_s=0.001, max_retries=2)
    reqs = mk_reqs()
    stats = router.serve(reqs)
    router.stop()
    assert all(r.state is RequestState.DONE for r in reqs), \
        [(r.rid, r.state, r.error) for r in reqs]
    assert [r.output for r in reqs] == [r.output for r in ref], \
        "survivor outputs diverged from the unfaulted reference"
    assert stats.requests_failed == 0, "a request ended FAILED"
    assert stats.requests_retried >= 1, "the replica kill forced no retry"
    assert stats.replica_failures >= 1, "the dead replica went unnoticed"
    assert router.health()[0] is ReplicaHealth.DEAD, \
        "the crashed replica was not quarantined"
    leaks = {}
    for e in replicas:
        e.drain_tier_io()
        leaks[e.name] = e.pool.leak_report()
        e.pool.assert_leak_free()
    out = {"chaos": _summary(stats),
           "chaos_faults_fired": plan.fired,
           "chaos_replica_health": [h.value for h in router.health()],
           "chaos_outputs_match_reference": True,
           "chaos_all_requests_completed": True,
           "chaos_leak_report": leaks}
    return out


def _pool_microbench(sizes=(1 << 10, 1 << 14, 1 << 16), batch: int = 8,
                     cycles: int = 400) -> dict:
    """KVBlockPool hot-path audit evidence: time the full
    reserve -> alloc_reserved -> share -> free -> free block lifecycle at
    pool sizes spanning 64x and report block-ops/s per size.  Every hot
    path is deque/dict based, so ops/s must hold roughly flat as the pool
    grows — a path that scanned the pool would collapse here."""
    out = {}
    for size in sizes:
        pool = KVBlockPool(size, block_size=16)
        t0 = time.perf_counter()
        for _ in range(cycles):
            pool.reserve(batch)
            ids = pool.alloc_reserved(batch)
            pool.share(ids)
            pool.free(ids)
            pool.free(ids)
        dt = time.perf_counter() - t0
        # 5 refcount transitions per block per cycle
        out[f"pool_ops_per_s_{size}_blocks"] = round(cycles * batch * 5 / dt)
    return out


def _summary(stats: ServeStats) -> dict:
    ms = lambda v: round(v * 1e3, 2) if v is not None else None  # noqa: E731
    return {
        "requests": stats.requests, "tokens": stats.tokens,
        "wall_s": round(stats.wall_s, 3),
        "tokens_per_s": round(stats.tokens_per_s, 2),
        "ttft_p50_ms": ms(stats.ttft_p50_s),
        "ttft_p99_ms": ms(stats.ttft_p99_s),
        "tpot_ms": ms(stats.mean_tpot_s),
        "slot_occupancy": round(stats.slot_occupancy, 3),
        "prefills": stats.prefills, "decode_steps": stats.decode_steps,
        "verify_steps": stats.verify_steps,
        "steps_per_token": (round(stats.steps_per_token, 3)
                            if stats.steps_per_token is not None else None),
        "accept_rate": (round(stats.accept_rate, 3)
                        if stats.accept_rate is not None else None),
        "prefill_compiles": stats.prefill_compiles,
        "prefill_tokens_total": stats.prefill_tokens_total,
        "prefill_tokens_computed": stats.prefill_tokens_computed,
        "prefill_compute_frac": (round(stats.prefill_compute_frac, 3)
                                 if stats.prefill_compute_frac is not None
                                 else None),
        "decode_stall_p99_ms": ms(stats.decode_stall_p99_s),
        "preemptions": stats.preemptions,
        "prefix_shared_blocks": stats.prefix_shared_blocks,
        "router_steals": stats.router_steals,
        "router_affinity_hits": stats.router_affinity_hits,
        "slo_miss_rate": (round(stats.slo_miss_rate, 3)
                          if stats.slo_miss_rate is not None else None),
        "kv_blocks_peak": stats.kv_blocks_peak,
        "kv_pool_util": (round(stats.kv_pool_util, 3)
                         if stats.kv_pool_util is not None else None),
        "kv_spills": stats.kv_spills, "kv_fetches": stats.kv_fetches,
        "prefix_hits_host": stats.prefix_hits_host,
        "spill_bytes": stats.spill_bytes,
        "kv_hit_rate": (round(stats.kv_hit_rate, 3)
                        if stats.kv_hit_rate is not None else None),
        "requests_failed": stats.requests_failed,
        "requests_retried": stats.requests_retried,
        "replica_failures": stats.replica_failures,
        "shed_rejections": stats.shed_rejections,
        "faults_injected": stats.faults_injected,
        "kv_migrations": stats.kv_migrations,
        "migrated_blocks": stats.migrated_blocks,
    }


def _kv_state_bytes(eng: ServingEngine) -> int:
    """Device bytes of the engine's batched KV decode state."""
    if eng._state is None:
        eng._state = eng._init_state()
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(eng._state))


def _warmup(eng: ServingEngine, cfg) -> None:
    """Compile prefill/decode outside the timed region.  Uses a full wave
    (= batch_slots requests) so both paths hit the same jitted (slots, 1)
    decode signature before timing starts."""
    eng.serve(_requests(cfg, eng.slots, new_tokens=2, seed=99))
    eng.serve_wave(_requests(cfg, eng.slots, new_tokens=2, seed=99))


def run(verbose: bool = True, repeats: int = 3) -> dict:
    cfg = arch_registry.smoke("qwen2.5-3b")
    fns = fns_for(cfg)
    params = fns.init(cfg, jax.random.PRNGKey(0))
    out = {"repeats": repeats}

    # -- scenario 1: replica scaling --------------------------------------
    for n_rep in (1, 2):
        replicas = [ServingEngine(cfg, params, max_len=24, batch_slots=4)
                    for _ in range(n_rep)]
        if n_rep == 1:
            stats = replicas[0].serve(_requests(cfg, 16))
        else:
            stats = MultiReplicaEngine(replicas).serve(_requests(cfg, 16))
        # None off TPU: a CPU run's power is not measured
        rep = serving_power_report(stats.tokens_per_s,
                                   [jax.devices()[0]] * n_rep)
        out[f"replicas_{n_rep}"] = dict(
            _summary(stats),
            tokens_per_s_per_w=rep.items_per_watt if rep else None)
        if verbose:
            print(f"serving x{n_rep}: {stats.tokens_per_s:.1f} tok/s  "
                  f"occ={stats.slot_occupancy:.2f}")
    out["replica_scaling_2x"] = (out["replicas_2"]["tokens_per_s"]
                                 / out["replicas_1"]["tokens_per_s"])
    out["note"] = ("this host has ONE CPU core, so two real replicas "
                   "contend for it; protocol-level replica scaling is "
                   "demonstrated with calibrated targets in fig6b (7.7x/8)")

    # -- scenario 2: mixed-length — wave vs continuous, paged vs contiguous
    slots, block = 4, 16
    max_len = 19 + 64 + 1                     # longest prompt + budget
    # paged pool sized <= 50% of the worst-case contiguous footprint
    pool_blocks = (slots * max_len) // (2 * block) - 1
    contig = ServingEngine(cfg, params, max_len=max_len, batch_slots=slots,
                           paged=False)
    paged = ServingEngine(cfg, params, max_len=max_len, batch_slots=slots,
                          paged=True, block_size=block,
                          pool_blocks=pool_blocks)
    _warmup(contig, cfg)
    _warmup(paged, cfg)
    out["mixed_wave"] = _summary(contig.serve_wave(_mixed_requests(cfg)))
    out["mixed_continuous_contig"] = _summary(
        contig.serve(_mixed_requests(cfg)))
    out["mixed_continuous"] = _summary(paged.serve(_mixed_requests(cfg)))
    out["continuous_speedup"] = round(
        out["mixed_continuous"]["tokens_per_s"]
        / out["mixed_wave"]["tokens_per_s"], 3)
    out["paged_vs_contiguous"] = round(
        out["mixed_continuous"]["tokens_per_s"]
        / out["mixed_continuous_contig"]["tokens_per_s"], 3)
    out["kv_bytes_contiguous"] = _kv_state_bytes(contig)
    out["kv_bytes_paged"] = _kv_state_bytes(paged)
    out["kv_pool_frac"] = round(out["kv_bytes_paged"]
                                / out["kv_bytes_contiguous"], 3)
    if verbose:
        for k in ("mixed_wave", "mixed_continuous_contig",
                  "mixed_continuous"):
            s = out[k]
            print(f"{k}: {s['tokens_per_s']:.1f} tok/s  "
                  f"ttft p50={s['ttft_p50_ms']}ms p99={s['ttft_p99_ms']}ms  "
                  f"occ={s['slot_occupancy']}  "
                  f"compiles={s['prefill_compiles']}")
        print(f"continuous vs wave speedup: {out['continuous_speedup']:.2f}x")
        print(f"paged vs contiguous: {out['paged_vs_contiguous']:.2f}x "
              f"tok/s at {out['kv_pool_frac']:.0%} of the KV footprint "
              f"(peak util {out['mixed_continuous']['kv_pool_util']})")

    # -- scenario 3: arrival process against a running engine --------------
    eng2 = ServingEngine(cfg, params, max_len=12 + 16, batch_slots=4)
    _warmup(eng2, cfg)
    reqs = _requests(cfg, 12, new_tokens=6, seed=1)
    for i, r in enumerate(reqs):
        r.max_new_tokens = 4 if i % 2 else 16
    rng = np.random.default_rng(2)
    gaps = rng.exponential(0.01, size=len(reqs))
    done = threading.Event()
    remaining = [len(reqs)]

    def fin(_):
        remaining[0] -= 1
        if remaining[0] == 0:
            done.set()

    base = eng2.begin_window()
    eng2.start()
    t0 = time.monotonic()
    for r, gap in zip(reqs, gaps):
        time.sleep(gap)
        # scheduler.submit stamps submitted_at at true submission time
        eng2.submit(r, on_finish=fin)
    done.wait(timeout=120)
    wall = time.monotonic() - t0
    eng2.stop()
    out["arrival"] = _summary(eng2.collect_window(base, reqs, wall))
    if verbose:
        s = out["arrival"]
        print(f"arrival: {s['tokens_per_s']:.1f} tok/s  "
              f"ttft p50={s['ttft_p50_ms']}ms p99={s['ttft_p99_ms']}ms  "
              f"occ={s['slot_occupancy']}")

    # -- scenario 4: priority under pressure (SLO-aware vs FIFO) -----------
    for key, slo_aware in (("priority_fifo", False), ("priority_slo", True)):
        stats, hipri_p99_ms = _run_pressure(cfg, params, slo_aware=slo_aware,
                                            repeats=repeats)
        s = _summary(stats)
        s["hipri_ttft_p99_ms"] = hipri_p99_ms
        out[key] = s
    out["priority_hipri_ttft_p99_speedup"] = round(
        out["priority_fifo"]["hipri_ttft_p99_ms"]
        / out["priority_slo"]["hipri_ttft_p99_ms"], 3)
    out["priority_tokens_cost_frac"] = round(
        1.0 - (out["priority_slo"]["tokens_per_s"]
               / out["priority_fifo"]["tokens_per_s"]), 3)
    if verbose:
        print(f"priority: hi-pri ttft p99 "
              f"{out['priority_fifo']['hipri_ttft_p99_ms']}ms (fifo) -> "
              f"{out['priority_slo']['hipri_ttft_p99_ms']}ms (slo), "
              f"{out['priority_hipri_ttft_p99_speedup']:.1f}x better at "
              f"{out['priority_tokens_cost_frac']:.1%} tok/s cost "
              f"({out['priority_slo']['preemptions']} preemptions, "
              f"slo miss {out['priority_slo']['slo_miss_rate']})")

    # -- scenario 5: shared prompt prefix (refcounted blocks) --------------
    n_share, prefix_blocks = 6, 2
    for key, sharing in (("shared_prefix", True),
                         ("shared_prefix_nosharing", False)):
        eng = ServingEngine(cfg, params, max_len=2 * 16 + 8 + 4 + 1,
                            batch_slots=n_share, prefix_sharing=sharing)
        _warmup(eng, cfg)
        out[key] = _summary(eng.serve(_shared_prefix_requests(
            cfg, n=n_share, prefix_blocks=prefix_blocks)))
    out["shared_prefix_nominal_prefix_blocks"] = n_share * prefix_blocks
    if verbose:
        s = out["shared_prefix"]
        print(f"shared_prefix: peak {s['kv_blocks_peak']} blocks "
              f"(unshared {out['shared_prefix_nosharing']['kv_blocks_peak']},"
              f" nominal prefix demand "
              f"{out['shared_prefix_nominal_prefix_blocks']}) — "
              f"{s['prefix_shared_blocks']} table entries shared")

    # -- scenario 6: cache-seeded prefill vs full recompute ----------------
    seeded_out = {}
    for key, seeded in (("seeded_prefill", True),
                        ("seeded_prefill_recompute", False)):
        stats, seeded_out[key] = _run_seeded(cfg, params, seeded=seeded,
                                             repeats=repeats)
        out[key] = _summary(stats)
    out["seeded_outputs_match"] = (
        seeded_out["seeded_prefill"] == seeded_out["seeded_prefill_recompute"])
    out["seeded_prefill_compute_frac"] = round(
        out["seeded_prefill"]["prefill_tokens_computed"]
        / out["seeded_prefill_recompute"]["prefill_tokens_computed"], 3)
    if verbose:
        s, r = out["seeded_prefill"], out["seeded_prefill_recompute"]
        print(f"seeded_prefill: {s['prefill_tokens_computed']}"
              f"/{s['prefill_tokens_total']} prompt tokens computed vs "
              f"{r['prefill_tokens_computed']} recomputed "
              f"({out['seeded_prefill_compute_frac']:.0%} of baseline), "
              f"outputs match: {out['seeded_outputs_match']}")

    # -- scenario 7: chunked prefill interleaved with decode ---------------
    chunk_out = {}
    for key, chunk in (("chunked_interleave", 64),
                       ("chunked_interleave_off", None)):
        stats, chunk_out[key] = _run_chunked(cfg, params, chunk=chunk,
                                             repeats=repeats)
        out[key] = _summary(stats)
    out["chunked_outputs_match"] = (
        chunk_out["chunked_interleave"] == chunk_out["chunked_interleave_off"])
    out["chunked_stall_p99_improvement"] = round(
        out["chunked_interleave_off"]["decode_stall_p99_ms"]
        / out["chunked_interleave"]["decode_stall_p99_ms"], 3)
    if verbose:
        c, u = out["chunked_interleave"], out["chunked_interleave_off"]
        print(f"chunked_interleave: decode stall p99 "
              f"{u['decode_stall_p99_ms']}ms (off) -> "
              f"{c['decode_stall_p99_ms']}ms (chunk 64), "
              f"{out['chunked_stall_p99_improvement']:.1f}x better, "
              f"outputs match: {out['chunked_outputs_match']}")

    # -- scenario 8: fleet prefix affinity vs least-loaded dispatch --------
    router_stats, ref_stats, router_match = _run_router_prefix(
        cfg, params, repeats=repeats)
    for key, stats in router_stats.items():
        out[key] = _summary(stats)
    out["router_single_replica"] = _summary(ref_stats)
    out["router_outputs_match_single"] = router_match
    if verbose:
        a = out["router_affinity"]
        b = out["router_least_loaded"]
        s = out["router_single_replica"]
        print(f"router_affinity: fleet prefill frac "
              f"{a['prefill_compute_frac']} vs {b['prefill_compute_frac']} "
              f"least-loaded (single-replica seeded "
              f"{s['prefill_compute_frac']}), "
              f"{a['router_affinity_hits']} affinity hits, outputs match "
              f"single-replica: {router_match}")

    # -- scenario 9: work stealing under an affinity-skewed backlog --------
    steal_stats, steal_match = _run_router_steal(cfg, params,
                                                 repeats=repeats)
    for key, stats in steal_stats.items():
        out[key] = _summary(stats)
    out["router_steal_outputs_match"] = steal_match
    out["router_steal_ttft_p99_improvement"] = round(
        out["router_no_steal"]["ttft_p99_ms"]
        / out["router_steal"]["ttft_p99_ms"], 3)
    if verbose:
        st, ns = out["router_steal"], out["router_no_steal"]
        print(f"router_steal: ttft p99 {ns['ttft_p99_ms']}ms (no steal) -> "
              f"{st['ttft_p99_ms']}ms "
              f"({out['router_steal_ttft_p99_improvement']:.1f}x better, "
              f"{st['router_steals']} steals, tokens {st['tokens']} vs "
              f"{ns['tokens']}, outputs match: {steal_match})")

    # -- scenario 10: speculative decoding (draft/verify on the paged pool)
    spec_out = {}
    for key, spec in (("spec_decode", True), ("spec_decode_off", False)):
        stats, spec_out[key] = _run_spec(cfg, params, spec=spec,
                                         repeats=repeats)
        out[key] = _summary(stats)
    out["spec_outputs_match"] = (
        spec_out["spec_decode"] == spec_out["spec_decode_off"])
    assert out["spec_outputs_match"], \
        "speculative greedy streams diverged from the vanilla baseline"
    out["spec_target_steps"] = (out["spec_decode"]["decode_steps"]
                                + out["spec_decode"]["verify_steps"])
    out["spec_baseline_steps"] = out["spec_decode_off"]["decode_steps"]
    assert out["spec_target_steps"] < out["spec_baseline_steps"], (
        f"speculation must cut target-model steps "
        f"({out['spec_target_steps']} vs {out['spec_baseline_steps']})")
    if verbose:
        s, b = out["spec_decode"], out["spec_decode_off"]
        print(f"spec_decode: {out['spec_baseline_steps']} -> "
              f"{out['spec_target_steps']} target steps "
              f"(accept rate {s['accept_rate']}, "
              f"{b['steps_per_token']} -> {s['steps_per_token']} "
              f"steps/token), wall {b['wall_s']}s -> {s['wall_s']}s, "
              f"outputs match: {out['spec_outputs_match']}")

    # -- scenario 11: tiered KV churn vs recompute (host-offloaded blocks)
    tier_out = {}
    for key, tiered in (("tiered_churn", True),
                        ("tiered_churn_recompute", False)):
        stats, tier_out[key], shape = _run_tiered_churn(
            cfg, params, tiered=tiered, repeats=repeats)
        out[key] = _summary(stats)
    out["tiered_pool_blocks"] = shape["pool_blocks"]
    out["tiered_working_set_blocks"] = shape["working_set_blocks"]
    out["tiered_outputs_match"] = (
        tier_out["tiered_churn"] == tier_out["tiered_churn_recompute"])
    assert out["tiered_outputs_match"], \
        "tiered greedy streams diverged from the recompute baseline"
    assert out["tiered_churn"]["prefix_hits_host"] > 0, \
        "churn never restored a prefix block from the host tier"
    assert (out["tiered_churn"]["prefill_compute_frac"]
            < out["tiered_churn_recompute"]["prefill_compute_frac"]), (
        f"tiering must cut the prefill compute fraction "
        f"({out['tiered_churn']['prefill_compute_frac']} vs "
        f"{out['tiered_churn_recompute']['prefill_compute_frac']})")
    if verbose:
        t, r = out["tiered_churn"], out["tiered_churn_recompute"]
        print(f"tiered_churn: prefill frac {t['prefill_compute_frac']} vs "
              f"{r['prefill_compute_frac']} recompute (pool "
              f"{out['tiered_pool_blocks']}/{out['tiered_working_set_blocks']}"
              f" working-set blocks), {t['kv_spills']} spills "
              f"{t['kv_fetches']} fetches {t['prefix_hits_host']} host hits "
              f"(hit rate {t['kv_hit_rate']}), outputs match: "
              f"{out['tiered_outputs_match']}")

    # -- scenario 12: long-context KV footprint >> device pool -------------
    lc_out = {}
    for key, tiered in (("tiered_longctx", True),
                        ("tiered_longctx_recompute", False)):
        stats, lc_out[key], shape = _run_tiered_longctx(cfg, params,
                                                        tiered=tiered)
        out[key] = _summary(stats)
        out[f"{key}_completed"] = shape["completed"]
        assert shape["completed"], f"{key}: long-context serve incomplete"
    out["longctx_pool_blocks"] = shape["pool_blocks"]
    out["longctx_logical_blocks"] = shape["logical_blocks"]
    out["longctx_outputs_match"] = (
        lc_out["tiered_longctx"] == lc_out["tiered_longctx_recompute"])
    assert out["longctx_outputs_match"], \
        "long-context tiered streams diverged from the recompute baseline"
    assert out["tiered_longctx"]["kv_spills"] > 0 \
        and out["tiered_longctx"]["kv_fetches"] > 0, \
        "long-context run never exercised the spill/fetch path"
    assert (out["tiered_longctx"]["prefill_tokens_computed"]
            < out["tiered_longctx_recompute"]["prefill_tokens_computed"])
    if verbose:
        t = out["tiered_longctx"]
        r = out["tiered_longctx_recompute"]
        print(f"tiered_longctx: {out['longctx_logical_blocks']} logical KV "
              f"blocks through a {out['longctx_pool_blocks']}-block device "
              f"pool; prefill {t['prefill_tokens_computed']}"
              f"/{t['prefill_tokens_total']} computed vs "
              f"{r['prefill_tokens_computed']} recomputed, outputs match: "
              f"{out['longctx_outputs_match']}")

    # -- scenario 13: chaos — replica kill + poison decode + KV-fetch drop -
    out.update(_run_chaos(cfg, params))
    if verbose:
        c = out["chaos"]
        print(f"chaos: {c['requests']} requests completed through "
              f"{out['chaos_faults_fired']} injected faults "
              f"({c['requests_retried']} retried, "
              f"{c['replica_failures']} replica failures, health "
              f"{out['chaos_replica_health']}), outputs match reference: "
              f"{out['chaos_outputs_match_reference']}, leak-free pools")

    # -- scenario 14: disaggregated prefill/decode fleet (KV migration) ----
    disagg_stats, disagg_med, dec_window, disagg_match = _run_disagg(
        cfg, params, repeats=max(repeats, 5))
    for key, stats in disagg_stats.items():
        out[key] = _summary(stats)
        out[key].update(disagg_med[key])   # asserted per-metric medians
    out["disagg_outputs_match"] = disagg_match
    assert disagg_match, \
        "disaggregated greedy outputs diverged from single-replica serving"
    out["disagg_migrations"] = out["disagg"]["kv_migrations"]
    out["disagg_migrated_blocks"] = out["disagg"]["migrated_blocks"]
    out["disagg_decode_replica_prefill_tokens_computed"] = \
        dec_window.prefill_tokens_computed
    out["disagg_stall_p99_improvement"] = round(
        disagg_med["interleaved_single_pool"]["decode_stall_p99_ms"]
        / disagg_med["disagg"]["decode_stall_p99_ms"], 3)
    out["disagg_ttft_p99_improvement"] = round(
        disagg_med["interleaved_single_pool"]["ttft_p99_ms"]
        / disagg_med["disagg"]["ttft_p99_ms"], 3)
    assert out["disagg_stall_p99_improvement"] > 1.0, (
        f"disaggregation must cut decode-stall p99 "
        f"({out['disagg']['decode_stall_p99_ms']}ms vs interleaved "
        f"{out['interleaved_single_pool']['decode_stall_p99_ms']}ms)")
    assert out["disagg_ttft_p99_improvement"] > 1.0, (
        f"disaggregation must cut TTFT p99 "
        f"({out['disagg']['ttft_p99_ms']}ms vs interleaved "
        f"{out['interleaved_single_pool']['ttft_p99_ms']}ms)")
    if verbose:
        d, i = out["disagg"], out["interleaved_single_pool"]
        print(f"disagg: decode stall p99 {i['decode_stall_p99_ms']}ms "
              f"(interleaved) -> {d['decode_stall_p99_ms']}ms "
              f"({out['disagg_stall_p99_improvement']:.1f}x better), "
              f"ttft p99 {i['ttft_p99_ms']}ms -> {d['ttft_p99_ms']}ms "
              f"({out['disagg_ttft_p99_improvement']:.1f}x better), "
              f"{d['kv_migrations']} migrations "
              f"({d['migrated_blocks']} blocks), decode-side prompt "
              f"recompute {out['disagg_decode_replica_prefill_tokens_computed']}"
              f" tokens, outputs match: {disagg_match}")

    out.update(_run_migrate_chaos(cfg, params))
    if verbose:
        m = out["migrate_chaos"]
        print(f"migrate_chaos: {m['requests']} requests completed through "
              f"{out['migrate_chaos_faults_fired']} dropped migrations "
              f"({m['requests_retried']} retried), outputs match "
              f"reference: {out['migrate_chaos_outputs_match_reference']}, "
              f"leak-free pools")

    # -- KV pool hot-path micro-bench --------------------------------------
    out["pool_microbench"] = _pool_microbench()
    if verbose:
        print(f"pool_microbench: {out['pool_microbench']}")

    save_artifact("serving_bench", out)
    _save_bench5(out)
    _save_bench6(out)
    _save_bench7(out)
    _save_bench9(out)
    _save_bench10(out)
    return out


def run_smoke(verbose: bool = True) -> dict:
    """CI-sized subset: 2 replicas, one affinity case and one steal case,
    seconds not minutes, with the A/B directions *asserted* — a routing
    regression fails the build instead of drifting a JSON number.  The
    summary lands in `artifacts/bench/serving_bench_smoke.json` (uploaded
    as a build artifact by the tier-1 workflow)."""
    cfg = arch_registry.smoke("qwen2.5-3b")
    params = fns_for(cfg).init(cfg, jax.random.PRNGKey(0))
    out = {"smoke": True}

    router_stats, ref_stats, match = _run_router_prefix(
        cfg, params, repeats=1, n=4, prefix_blocks=2, new_tokens=2)
    for key, stats in router_stats.items():
        out[key] = _summary(stats)
    out["router_single_replica"] = _summary(ref_stats)
    out["router_outputs_match_single"] = match
    aff = out["router_affinity"]["prefill_compute_frac"]
    base = out["router_least_loaded"]["prefill_compute_frac"]
    assert match, "routed greedy outputs diverged from single-replica"
    assert aff < base, (
        f"affinity routing must cut the fleet prefill compute fraction "
        f"(affinity {aff} vs least-loaded {base})")
    if verbose:
        print(f"smoke affinity: fleet prefill frac {aff} vs {base} "
              f"least-loaded, outputs match: {match}")

    steal_stats, steal_match = _run_router_steal(cfg, params, repeats=1,
                                                 n_short=4, long_tokens=96,
                                                 short_tokens=4)
    for key, stats in steal_stats.items():
        out[key] = _summary(stats)
    out["router_steal_outputs_match"] = steal_match
    assert steal_match, "stealing changed greedy outputs"
    assert out["router_steal"]["router_steals"] >= 1, \
        "idle replica never stole from the backlogged peer"
    assert out["router_steal"]["tokens"] == out["router_no_steal"]["tokens"]
    if verbose:
        print(f"smoke steal: {out['router_steal']['router_steals']} steals, "
              f"ttft p99 {out['router_no_steal']['ttft_p99_ms']}ms -> "
              f"{out['router_steal']['ttft_p99_ms']}ms, outputs match: "
              f"{steal_match}")

    # speculative decoding: tiny self-speculation case, bf16 and int8 —
    # bit-identicality and the step cut are the PR-6 acceptance criteria,
    # so both are *asserted* here, not just reported
    for dtype, tag in (("bfloat16", "spec_decode"), ("int8",
                                                     "spec_decode_int8")):
        s_on, o_on = _run_spec(cfg, params, spec=True, cache_dtype=dtype,
                               repeats=1, n=2, slots=2, new_tokens=8)
        s_off, o_off = _run_spec(cfg, params, spec=False, cache_dtype=dtype,
                                 repeats=1, n=2, slots=2, new_tokens=8)
        out[tag] = _summary(s_on)
        out[f"{tag}_off"] = _summary(s_off)
        assert o_on == o_off, \
            f"speculative {dtype} streams diverged from vanilla greedy"
        assert s_on.accept_rate is not None and s_on.accept_rate > 0, \
            f"self-speculation accepted nothing ({dtype})"
        assert s_on.decode_steps + s_on.verify_steps < s_off.decode_steps, (
            f"speculation must cut target steps ({dtype}: "
            f"{s_on.decode_steps + s_on.verify_steps} vs "
            f"{s_off.decode_steps})")
        if verbose:
            print(f"smoke {tag}: {s_off.decode_steps} -> "
                  f"{s_on.decode_steps + s_on.verify_steps} target steps, "
                  f"accept rate {s_on.accept_rate:.2f}, outputs match: "
                  f"{o_on == o_off}")

    # tiered KV cache: tiny churn A/B — bit-identical restore and a lower
    # prefill compute fraction are the PR-7 acceptance criteria, asserted
    tier_out = {}
    for tag, tiered in (("tiered_churn", True),
                        ("tiered_churn_recompute", False)):
        stats, tier_out[tag], _shape = _run_tiered_churn(
            cfg, params, tiered=tiered, repeats=1, groups=4, visits=2,
            prefix_blocks=2, new_tokens=2)
        out[tag] = _summary(stats)
    assert tier_out["tiered_churn"] == tier_out["tiered_churn_recompute"], \
        "tiered greedy streams diverged from the recompute baseline"
    assert out["tiered_churn"]["prefix_hits_host"] > 0, \
        "churn never restored a prefix block from the host tier"
    assert (out["tiered_churn"]["prefill_tokens_computed"]
            < out["tiered_churn_recompute"]["prefill_tokens_computed"]), (
        "tiering must cut prefill compute "
        f"({out['tiered_churn']['prefill_tokens_computed']} vs "
        f"{out['tiered_churn_recompute']['prefill_tokens_computed']})")
    # disaggregated prefill/decode smoke: 1 prefill-role + 1 decode-role
    # replica vs 2 interleaved mixed replicas — zero decode-side prompt
    # recompute and leak-free pools are asserted inside _run_disagg per
    # repeat; the decode-stall direction is asserted here (TTFT p99 is
    # reported, not asserted: at smoke scale it sits inside this 1-core
    # host's wall-clock jitter — the full run asserts it)
    disagg_stats, _, dec_window, disagg_match = _run_disagg(
        cfg, params, repeats=1, n_dec=3, dec_tokens=24, n_big=1,
        big_len=128, chunk=32)
    for key, stats in disagg_stats.items():
        out[key] = _summary(stats)
    out["disagg_outputs_match"] = disagg_match
    out["disagg_decode_replica_prefill_tokens_computed"] = \
        dec_window.prefill_tokens_computed
    assert disagg_match, \
        "disaggregated greedy outputs diverged from single-replica serving"
    assert out["disagg"]["kv_migrations"] == 4, \
        f"expected 4 migrations, saw {out['disagg']['kv_migrations']}"
    assert (out["disagg"]["decode_stall_p99_ms"]
            < out["interleaved_single_pool"]["decode_stall_p99_ms"]), (
        f"disaggregation must cut decode-stall p99 "
        f"({out['disagg']['decode_stall_p99_ms']}ms vs interleaved "
        f"{out['interleaved_single_pool']['decode_stall_p99_ms']}ms)")
    if verbose:
        d, i = out["disagg"], out["interleaved_single_pool"]
        print(f"smoke disagg: decode stall p99 {i['decode_stall_p99_ms']}ms "
              f"(interleaved) -> {d['decode_stall_p99_ms']}ms, ttft p99 "
              f"{i['ttft_p99_ms']}ms -> {d['ttft_p99_ms']}ms, "
              f"{d['kv_migrations']} migrations, decode-side recompute "
              f"{out['disagg_decode_replica_prefill_tokens_computed']} "
              f"tokens, outputs match: {disagg_match}")

    # fault-tolerance chaos smoke: kill 1 of 2 replicas mid-serve, poison a
    # decode on the survivor, drop KV fetches — completion, bit-identical
    # survivor outputs, quarantine, and leak-free pools are asserted inside
    out.update(_run_chaos(cfg, params))
    if verbose:
        c = out["chaos"]
        print(f"smoke chaos: {c['requests']} requests completed through "
              f"{out['chaos_faults_fired']} injected faults "
              f"({c['requests_retried']} retried, "
              f"{c['replica_failures']} replica failures, health "
              f"{out['chaos_replica_health']})")

    out["pool_microbench"] = _pool_microbench(sizes=(1 << 10, 1 << 14),
                                              cycles=100)
    if verbose:
        t = out["tiered_churn"]
        print(f"smoke tiered: prefill "
              f"{t['prefill_tokens_computed']}/{t['prefill_tokens_total']} "
              f"computed vs "
              f"{out['tiered_churn_recompute']['prefill_tokens_computed']} "
              f"recomputed, {t['kv_spills']} spills {t['kv_fetches']} "
              f"fetches {t['prefix_hits_host']} host hits, outputs match: "
              f"{tier_out['tiered_churn'] == tier_out['tiered_churn_recompute']}")
        print(f"smoke pool_microbench: {out['pool_microbench']}")

    save_artifact("serving_bench_smoke", out)
    return out


def _write_headline(pr: int, title: str, **metrics) -> str:
    """THE writer for the repo-root ``BENCH_{pr}.json`` trajectory
    artifacts: the payload is ``{"pr", "title", *metrics, "method"}``
    in the call site's insertion order with ``method`` forced last, so
    regenerated artifacts diff cleanly.  Every headline must say how it
    was measured — a missing or empty ``method`` is an error here, not
    a silent omission in one hand-rolled writer."""
    method = metrics.pop("method", "")
    if not str(method).strip():
        raise ValueError(f"BENCH_{pr}.json needs a non-empty 'method' "
                         f"describing how the headline was measured")
    path = os.path.join(os.path.dirname(__file__), "..", f"BENCH_{pr}.json")
    with open(path, "w") as f:
        json.dump({"pr": pr, "title": title, **metrics, "method": method},
                  f, indent=1)
    return path


def _save_bench5(out: dict) -> str:
    return _write_headline(
        5,
        "replica router: prefix-affinity dispatch, block-aware "
        "load, work stealing",
        router_affinity_prefill_compute_frac=(
            out["router_affinity"]["prefill_compute_frac"]),
        router_least_loaded_prefill_compute_frac=(
            out["router_least_loaded"]["prefill_compute_frac"]),
        single_replica_seeded_prefill_compute_frac=(
            out["router_single_replica"]["prefill_compute_frac"]),
        router_affinity_hits=out["router_affinity"]["router_affinity_hits"],
        router_outputs_match_single=out["router_outputs_match_single"],
        router_steal_ttft_p99_ms=out["router_steal"]["ttft_p99_ms"],
        router_no_steal_ttft_p99_ms=out["router_no_steal"]["ttft_p99_ms"],
        router_steal_ttft_p99_improvement=(
            out["router_steal_ttft_p99_improvement"]),
        router_steals=out["router_steal"]["router_steals"],
        router_steal_outputs_match=out["router_steal_outputs_match"],
        method=f"median-of-{out.get('repeats', 3)} repeats on warm "
               f"engines (single-core host wall clock jitters ~25%); "
               f"token counts and output equality are deterministic; "
               f"fresh prefix per repeat so every measurement is "
               f"first-contact",
    )


def _save_bench6(out: dict) -> str:
    return _write_headline(
        6,
        "speculative decoding on the paged pool: draft/verify "
        "slots, batched multi-token verify, bit-identical greedy "
        "acceptance",
        spec_accept_rate=out["spec_decode"]["accept_rate"],
        spec_target_steps=out["spec_target_steps"],
        baseline_target_steps=out["spec_baseline_steps"],
        spec_steps_per_token=out["spec_decode"]["steps_per_token"],
        baseline_steps_per_token=out["spec_decode_off"]["steps_per_token"],
        spec_tokens_per_s=out["spec_decode"]["tokens_per_s"],
        baseline_tokens_per_s=out["spec_decode_off"]["tokens_per_s"],
        spec_wall_s=out["spec_decode"]["wall_s"],
        baseline_wall_s=out["spec_decode_off"]["wall_s"],
        spec_outputs_match=out["spec_outputs_match"],
        method="self-speculation (drafter = target weights, k=3) over "
               "greedy requests on a warm engine; streams asserted "
               "bit-identical to the non-speculative baseline and "
               "target-model steps asserted strictly fewer; wall clock "
               "reported, not asserted — off-TPU the drafter shares "
               "this host's single core, so step reduction is the "
               "headline",
    )


def _save_bench7(out: dict) -> str:
    return _write_headline(
        7,
        "tiered KV cache: host-offloaded blocks with async "
        "spill/prefetch over the split-phase offload protocol",
        churn_tiered_prefill_compute_frac=(
            out["tiered_churn"]["prefill_compute_frac"]),
        churn_recompute_prefill_compute_frac=(
            out["tiered_churn_recompute"]["prefill_compute_frac"]),
        churn_prefix_hits_host=out["tiered_churn"]["prefix_hits_host"],
        churn_kv_spills=out["tiered_churn"]["kv_spills"],
        churn_kv_fetches=out["tiered_churn"]["kv_fetches"],
        churn_spill_bytes=out["tiered_churn"]["spill_bytes"],
        churn_kv_hit_rate=out["tiered_churn"]["kv_hit_rate"],
        churn_pool_blocks=out["tiered_pool_blocks"],
        churn_working_set_blocks=out["tiered_working_set_blocks"],
        churn_outputs_match=out["tiered_outputs_match"],
        longctx_logical_blocks=out["longctx_logical_blocks"],
        longctx_pool_blocks=out["longctx_pool_blocks"],
        longctx_tiered_prefill_tokens_computed=(
            out["tiered_longctx"]["prefill_tokens_computed"]),
        longctx_recompute_prefill_tokens_computed=(
            out["tiered_longctx_recompute"]["prefill_tokens_computed"]),
        longctx_completed=out["tiered_longctx_completed"],
        longctx_outputs_match=out["longctx_outputs_match"],
        pool_microbench=out["pool_microbench"],
        method=f"median-of-{out.get('repeats', 3)} repeats on warm "
               f"engines; device pool capped below the working set so "
               f"eviction demotes published prefixes to the host tier "
               f"and revisits restore them over the async offload "
               f"protocol; greedy outputs asserted bit-identical to the "
               f"untiered recompute baseline and prefill compute "
               f"asserted strictly lower — token counts deterministic, "
               f"wall clock reported not asserted (1-core host)",
    )


def _save_bench9(out: dict) -> str:
    c = out["chaos"]
    return _write_headline(
        9,
        "fault-tolerant serving: deterministic fault injection, "
        "poison isolation, replica quarantine, leak-free retry",
        chaos_requests_completed=c["requests"],
        chaos_requests_failed=c["requests_failed"],
        chaos_requests_retried=c["requests_retried"],
        chaos_replica_failures=c["replica_failures"],
        chaos_faults_fired=out["chaos_faults_fired"],
        chaos_replica_health=out["chaos_replica_health"],
        chaos_outputs_match_reference=out["chaos_outputs_match_reference"],
        chaos_leak_report=out["chaos_leak_report"],
        method="2 tiered replicas under a deterministic FaultPlan "
               "(replica0 executor killed mid-serve, one decode commit "
               "poisoned on the survivor, KV fetch transfers dropped); "
               "every request must complete, retried requests restart "
               "from the bare prompt so greedy outputs are asserted "
               "bit-identical to an unfaulted single-replica "
               "reference, the dead replica is asserted quarantined, "
               "and both block pools are asserted leak-free after "
               "draining in-flight tier IO",
    )


def _save_bench10(out: dict) -> str:
    d, i = out["disagg"], out["interleaved_single_pool"]
    return _write_headline(
        10,
        "disaggregated prefill/decode fleet with live KV-block "
        "migration",
        disagg_decode_stall_p99_ms=d["decode_stall_p99_ms"],
        interleaved_decode_stall_p99_ms=i["decode_stall_p99_ms"],
        disagg_stall_p99_improvement=out["disagg_stall_p99_improvement"],
        disagg_ttft_p99_ms=d["ttft_p99_ms"],
        interleaved_ttft_p99_ms=i["ttft_p99_ms"],
        disagg_ttft_p99_improvement=out["disagg_ttft_p99_improvement"],
        disagg_migrations=out["disagg_migrations"],
        disagg_migrated_blocks=out["disagg_migrated_blocks"],
        disagg_decode_replica_prefill_tokens_computed=(
            out["disagg_decode_replica_prefill_tokens_computed"]),
        disagg_outputs_match=out["disagg_outputs_match"],
        migrate_chaos_requests_retried=(
            out["migrate_chaos"]["requests_retried"]),
        migrate_chaos_outputs_match_reference=(
            out["migrate_chaos_outputs_match_reference"]),
        migrate_chaos_leak_report=out["migrate_chaos_leak_report"],
        method=f"per-metric medians across max({out.get('repeats', 3)}, "
               f"5) repeats on warm fleets: a 1024-token prompt lands "
               f"on a fleet already decoding short requests; the disagg "
               f"arm "
               f"(1 prefill-role + 1 decode-role replica, KV blocks "
               f"migrated at prefill completion) is compared against "
               f"an interleaved arm (2 mixed replicas, same chunked "
               f"prefill) — decode-stall p99 and TTFT p99 asserted "
               f"better, greedy outputs asserted bit-identical to a "
               f"single-replica reference, the decode replica's "
               f"measurement window asserted to compute zero prompt "
               f"tokens, and both pools asserted leak-free after "
               f"draining migrations; the chaos companion drops "
               f"kv.migrate transfers mid-flight and asserts "
               f"retry-to-completion with leak-free pools on both ends",
    )


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: tiny 2-replica affinity + steal "
                         "cases with asserted A/B directions, seconds "
                         "not minutes")
    ap.add_argument("--repeats", type=int, default=3,
                    help="median-of-N repeats for wall-clock A/Bs "
                         "(token counts are deterministic; the wall "
                         "clock on this 1-core host is not)")
    args = ap.parse_args()
    if args.smoke:
        run_smoke()
    else:
        run(repeats=args.repeats)
