"""Continuous-batching LM serving across replica groups (the paper's
multi-NCS pattern at LM scale) + tokens/s/W reporting.

Each replica keeps a fixed-slot decode batch saturated: a finished slot is
refilled by a chunked prefill of the next queued request (QUEUED -> PREFILL
-> DECODE -> DONE lifecycle in `repro.serving.scheduler`).  With more than
one replica, the `repro.serving.router.ReplicaRouter` dispatches requests
individually — to the replica already holding the prompt's longest prefix
(so cache-seeded prefill fires fleet-wide), falling back to block-aware
load (free KV blocks + queued prefill tokens, not raw request count) —
through `repro.core.offload`'s split-phase protocol, collected out of
order; an idle replica steals queued requests off a backlogged peer
(`--no-affinity` / `--no-steal` switch either mechanism off).  Admission is
SLO-aware: every third request here carries `priority=1` and a TTFT SLO,
so it is admitted ahead of the backlog (and, under KV-block pressure, may
preempt a lower-priority decode).  Stats include TTFT p50/p99, TPOT, slot
occupancy, SLO miss rate, and (paged) KV-pool peaks.

`--draft-model ARCH` turns on speculative decoding (paged KV only): a
drafter model proposes `--spec-k` tokens per slot per step and the target
scores all of them in one batched verify pass, committing the longest
prefix that matches its own greedy argmax — so greedy outputs stay
bit-identical while the target runs fewer steps.  Only greedy requests
speculate; the temperature-sampled ones here keep using vanilla decode in
the same batch.  Passing the target arch itself is self-speculation
(drafter shares the target's weights — no second model needed to demo).

`--host-blocks N` turns on the tiered KV cache: cold pool blocks (idle
shared prefixes, preemption victims' histories) spill to an N-block host
tier over the split-phase offload protocol and are restored — not
recomputed — when a later request (or the victim's resume) needs them;
`--kv-pool-blocks` shrinks the device pool so the tier actually engages.

`--replica-roles prefill,decode` disaggregates the fleet: prefill-role
replicas run chunked prefill at full budget (no decode steps contending)
and sample the first output token at handoff; the finished prompt's KV
blocks then migrate over the split-phase offload protocol to a
decode-role replica, which adopts them and decodes with zero prompt
recompute.  Greedy outputs stay bit-identical to a single mixed replica.

`--inject-faults PLAN` runs the same workload under deterministic chaos
(`site[:action[:after[:count]]]` specs or `seed=<int>`): a killed replica
is quarantined and its requests retried on survivors (`--max-retries`),
restarting from the bare prompt so greedy outputs are unchanged;
`--deadline-s` cancels any request that overstays with a typed
DeadlineExceeded and reclaims its KV blocks.

  PYTHONPATH=src python examples/serve_lm.py [--replicas 2] [--no-affinity]
      [--no-steal] [--draft-model qwen2.5-3b] [--spec-k 3] [--no-spec]
      [--host-blocks 32 --kv-pool-blocks 8]
      [--replica-roles prefill,decode]
      [--inject-faults replica.executor:raise:4 --max-retries 2]
      [--deadline-s 30]
"""
import argparse

import jax
import numpy as np

from repro.configs import registry as arch_registry
from repro.core.power import power_row, serving_power_report
from repro.models.registry import fns_for
from repro.serving.engine import Request, ServingEngine
from repro.serving.faults import FaultPlan
from repro.serving.router import ReplicaRouter
from repro.serving.sampler import greedy, temperature


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--no-affinity", action="store_true",
                    help="route by block-aware load alone (no fleet-wide "
                         "prefix-affinity dispatch)")
    ap.add_argument("--no-steal", action="store_true",
                    help="idle replicas no longer steal queued requests "
                         "from backlogged peers")
    ap.add_argument("--draft-model", default=None, metavar="ARCH",
                    help="speculative decoding drafter arch (same arch as "
                         "--arch = self-speculation); greedy requests "
                         "commit multiple tokens per target step, outputs "
                         "stay bit-identical")
    ap.add_argument("--spec-k", type=int, default=3, metavar="K",
                    help="drafter tokens proposed per speculative round")
    ap.add_argument("--no-spec", action="store_true",
                    help="ignore --draft-model (vanilla-decode baseline)")
    ap.add_argument("--host-blocks", type=int, default=0, metavar="N",
                    help="tiered KV: N-block host tier for spilled cold "
                         "blocks (0 = untiered)")
    ap.add_argument("--kv-pool-blocks", type=int, default=None,
                    help="device pool size in blocks (shrink it to make "
                         "the host tier earn its keep)")
    ap.add_argument("--replica-roles", default=None, metavar="R1,R2,...",
                    help="disaggregated fleet: comma-separated per-replica "
                         "roles (prefill/decode/mixed, one per --replicas); "
                         "prefill replicas migrate finished prompts' KV "
                         "blocks to decode replicas instead of decoding "
                         "locally")
    ap.add_argument("--prefill-chunk", type=int, default=None, metavar="C",
                    help="prefill prompts in C-token chunks interleaved "
                         "with decode steps (C must be a multiple of the "
                         "16-token block size)")
    ap.add_argument("--inject-faults", default=None, metavar="PLAN",
                    help="deterministic chaos: comma-separated "
                         "site[:action[:after[:count]]] fault specs or "
                         "seed=<int> (e.g. replica.executor:raise:4)")
    ap.add_argument("--max-retries", type=int, default=2, metavar="N",
                    help="multi-replica only: reissue a failed request to "
                         "surviving replicas up to N times before FAILED")
    ap.add_argument("--deadline-s", type=float, default=None, metavar="S",
                    help="cancel any request still unfinished after S "
                         "seconds (typed DeadlineExceeded, KV reclaimed)")
    args = ap.parse_args()

    cfg = arch_registry.smoke(args.arch)
    params = fns_for(cfg).init(cfg, jax.random.PRNGKey(0))
    spec_kw = {}
    if args.draft_model and not args.no_spec:
        if args.draft_model == args.arch:
            draft_cfg, draft_params = cfg, params
        else:
            draft_cfg = arch_registry.smoke(args.draft_model)
            draft_params = fns_for(draft_cfg).init(draft_cfg,
                                                   jax.random.PRNGKey(1))
        spec_kw = dict(draft_cfg=draft_cfg, draft_params=draft_params,
                       spec_k=args.spec_k)
    rng = np.random.default_rng(0)
    # mixed lengths on purpose: short requests finish early and their slots
    # are refilled immediately (no lock-step waves)
    reqs = [Request(i,
                    rng.integers(0, cfg.vocab_size, size=12).astype(np.int32),
                    max_new_tokens=3 if i % 3 else 9,
                    sampler=greedy() if i % 2 else temperature(0.7, top_k=20,
                                                               seed=i),
                    # interactive tier: jumps the queue, 2s TTFT target
                    priority=1 if i % 3 == 0 else 0,
                    slo_ttft_s=2.0 if i % 3 == 0 else None,
                    deadline_s=args.deadline_s)
            for i in range(args.requests)]

    plan = (FaultPlan.parse(args.inject_faults)
            if args.inject_faults else None)
    roles = (args.replica_roles.split(",") if args.replica_roles
             else ["mixed"] * args.replicas)
    if len(roles) != args.replicas:
        ap.error(f"--replica-roles names {len(roles)} roles for "
                 f"--replicas {args.replicas}")
    replicas = [ServingEngine(cfg, params, max_len=24, batch_slots=4,
                              pool_blocks=args.kv_pool_blocks,
                              host_blocks=args.host_blocks,
                              prefill_chunk=args.prefill_chunk,
                              name=f"replica{i}", fault_plan=plan,
                              role=roles[i], **spec_kw)
                for i in range(args.replicas)]
    if args.replicas == 1:
        stats = replicas[0].serve(reqs)
    else:
        stats = ReplicaRouter(replicas, affinity=not args.no_affinity,
                              steal=not args.no_steal,
                              max_retries=args.max_retries).serve(reqs)
    print(f"{stats.requests} requests -> {stats.tokens} tokens in "
          f"{stats.wall_s:.2f}s  ({stats.tokens_per_s:.1f} tok/s, "
          f"slot occupancy {stats.slot_occupancy:.2f})")
    if args.replicas > 1:
        print(f"router: affinity_hits={stats.router_affinity_hits}  "
              f"steals={stats.router_steals}")
    if stats.spec_proposed:
        print(f"spec: accept_rate={stats.accept_rate:.2f}  "
              f"verify_steps={stats.verify_steps}  "
              f"decode_steps={stats.decode_steps}")
    if stats.kv_spills or stats.kv_fetches:
        print(f"tiering: spills={stats.kv_spills}  "
              f"fetches={stats.kv_fetches}  "
              f"host_hits={stats.prefix_hits_host}")
    if stats.kv_migrations:
        print(f"disagg: migrations={stats.kv_migrations}  "
              f"migrated_blocks={stats.migrated_blocks}")
    if stats.slo_miss_rate is not None:
        print(f"slo miss rate {stats.slo_miss_rate:.2f}  "
              f"preemptions {stats.preemptions}  "
              f"kv_blocks_peak {stats.kv_blocks_peak}")
    if stats.faults_injected or stats.requests_failed or stats.requests_retried:
        print(f"faults: injected={stats.faults_injected}  "
              f"failed={stats.requests_failed}  "
              f"retried={stats.requests_retried}  "
              f"replica_failures={stats.replica_failures}")
    print(power_row(serving_power_report(stats.tokens_per_s,
                                         [e.device or jax.devices()[0]
                                          for e in replicas])))
    for r in reqs[:3]:
        ttft = f"{r.ttft_s:.2f}s" if r.ttft_s is not None else "n/a"
        print(f"  req {r.rid} [{r.state.value}]: {r.output}  ttft={ttft}")


if __name__ == "__main__":
    main()
