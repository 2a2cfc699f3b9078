"""Serving launcher: continuous-batching engine with the paper's
throughput / throughput-per-watt reporting plus serving-quality metrics
(TTFT p50/p99, TPOT, slot occupancy).

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --requests 16 --new-tokens 8 --replicas 2
  # A/B against the legacy lock-step wave decode:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --mode wave
  # chunked prefill (long prompts interleave with decode steps) and the
  # seeded-prefill recompute baseline:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --prompt-len 96 --prefill-chunk 32
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --no-seeded-prefill
  # replica-router policy A/B (multi-replica only): strip prefix-affinity
  # routing and idle-replica work stealing back to least-loaded dispatch:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --replicas 2 --no-affinity --no-steal
  # speculative decoding: a drafter proposes k tokens per step, the target
  # verifies them in one batched pass — greedy outputs stay bit-identical:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --draft-model qwen2.5-3b --spec-k 3
  # disaggregated fleet: one replica prefills at full chunk budget and
  # migrates each finished prompt's KV blocks to the other, which only
  # decodes — zero prompt recompute on the decode side:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --replicas 2 --replica-roles prefill,decode --prefill-chunk 32
  # chaos run: kill one of two replicas mid-serve; its requests retry on
  # the survivor (bit-identical greedy regeneration), with per-request
  # deadlines cancelling anything that overstays:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --replicas 2 --inject-faults replica.executor:raise:4 \
      --max-retries 2 --deadline-s 30

Weights are initialised from ``PRNGKey(0)`` in the model's compute dtype
(bf16: qwen2.5-3b's 3.1B parameters take 6.2 GB, so one 16 GB chip holds
them with room for KV).  Replica ``i`` is placed on ``jax.devices()[i]``
(round-robin when there are more replicas than devices).  The exit code
is non-zero when any request ends FAILED.  `chip_smoke.py` at the repo
root builds its fleets through :func:`build`.
"""
from __future__ import annotations

import argparse
import sys
from dataclasses import dataclass

import jax
import numpy as np

from repro.configs import registry as arch_registry
from repro.core.power import power_row, serving_power_report
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import fns_for
from repro.serving.engine import Request, ServeStats, ServingEngine
from repro.serving.faults import FaultPlan
from repro.serving.router import ReplicaRouter
from repro.serving.sampler import greedy, temperature
from repro.serving.scheduler import RequestState


def _fmt_ms(v: float | None) -> str:
    return f"{v * 1e3:.1f}ms" if v is not None else "n/a"


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica count; >1 routes individual requests "
                         "through the ReplicaRouter (prefix-affinity + "
                         "block-aware placement, idle replicas steal "
                         "queued work)")
    ap.add_argument("--no-affinity", action="store_true",
                    help="multi-replica only: disable prefix-affinity "
                         "routing (requests place by block-aware load "
                         "alone, so identical prefixes land on arbitrary "
                         "replicas and seeded prefill only fires locally)")
    ap.add_argument("--no-steal", action="store_true",
                    help="multi-replica only: disable work stealing (an "
                         "idle replica no longer pulls queued requests "
                         "off a backlogged peer)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--contiguous-kv", action="store_true",
                    help="disable the paged KV pool (worst-case per-slot "
                         "cache, per-prompt-length prefill compiles)")
    ap.add_argument("--kv-pool-blocks", type=int, default=None,
                    help="paged KV pool size in blocks (default: worst "
                         "case = slots x ceil(max_len / block_size))")
    ap.add_argument("--no-preemption", action="store_true",
                    help="disable decode preemption (paged KV only): a "
                         "high-priority request waits for a slot/blocks "
                         "instead of evicting a lower-priority decode")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable refcounted prompt-prefix block sharing")
    ap.add_argument("--prefill-chunk", type=int, default=None, metavar="C",
                    help="paged KV only: prefill prompts in C-token chunks "
                         "interleaved with decode steps (C must be a "
                         "multiple of the 16-token block size; default: "
                         "whole prompt in one go, stalling active decodes "
                         "for its full prefill)")
    ap.add_argument("--host-blocks", type=int, default=0, metavar="N",
                    help="tiered KV cache: spill cold pool blocks (idle "
                         "shared prefixes, preemption victims' histories) "
                         "to an N-block host tier and restore them "
                         "asynchronously through the split-phase offload "
                         "protocol instead of recomputing (0 = untiered)")
    ap.add_argument("--no-kv-tiering", action="store_true",
                    help="ignore --host-blocks: run the untiered pool "
                         "(the recompute A/B baseline for tiering)")
    ap.add_argument("--no-seeded-prefill", action="store_true",
                    help="recompute baseline: shared prefix blocks are "
                         "still mapped, but every prompt token is re-run "
                         "and its rows discarded into the trash block "
                         "(compare prefill_tokens_computed)")
    ap.add_argument("--hipri-every", type=int, default=0, metavar="N",
                    help="mark every Nth request priority 1 (0 = all "
                         "requests priority 0); exercises SLO-aware "
                         "admission and preemption")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="TTFT SLO attached to the high-priority requests "
                         "(reported as slo_miss_rate)")
    ap.add_argument("--draft-model", default=None, metavar="ARCH",
                    help="enable speculative decoding with this arch as "
                         "the drafter (paged KV only); greedy requests "
                         "propose --spec-k tokens per step and the target "
                         "verifies them in one batched pass — outputs are "
                         "bit-identical to vanilla greedy.  Same arch as "
                         "--arch = self-speculation (shares the target's "
                         "weights)")
    ap.add_argument("--spec-k", type=int, default=3, metavar="K",
                    help="drafter tokens proposed per speculative round "
                         "(each verify pass scores K+1 positions and "
                         "commits 1..K+1 tokens)")
    ap.add_argument("--no-spec", action="store_true",
                    help="ignore --draft-model: run vanilla decode (the "
                         "A/B baseline for speculative decoding)")
    ap.add_argument("--deadline-s", type=float, default=None, metavar="S",
                    help="per-request completion deadline: a request "
                         "still queued or mid-decode after S seconds is "
                         "cancelled with a typed DeadlineExceeded and its "
                         "KV blocks reclaimed")
    ap.add_argument("--max-retries", type=int, default=2, metavar="N",
                    help="multi-replica only: reissue a request that "
                         "failed on one replica (poison fault, replica "
                         "crash) to a surviving replica up to N times "
                         "before marking it FAILED; retries restart from "
                         "the bare prompt, so greedy outputs stay "
                         "bit-identical")
    ap.add_argument("--replica-roles", default=None, metavar="R1,R2,...",
                    help="disaggregated fleet: comma-separated per-replica "
                         "roles (prefill/decode/mixed, one per --replicas); "
                         "prefill-role replicas migrate each finished "
                         "prompt's KV blocks to a decode-capable replica "
                         "instead of decoding locally")
    ap.add_argument("--inject-faults", default=None, metavar="PLAN",
                    help="deterministic fault injection for chaos runs: "
                         "comma-separated site[:action[:after[:count]]] "
                         "specs (sites: target.compute engine.prefill "
                         "engine.decode kv.spill kv.fetch "
                         "replica.executor; actions: raise drop delay) or "
                         "seed=<int> for a random seeded plan — e.g. "
                         "'replica.executor:raise:4,kv.fetch:drop'")
    ap.add_argument("--mode", choices=("continuous", "wave"),
                    default="continuous",
                    help="wave = legacy lock-step decode (single replica "
                         "only), for A/B comparison")
    return ap


@dataclass
class Fleet:
    """The replicas a launch built, and the router in front of them when
    there are several."""
    cfg: object
    engines: list[ServingEngine]
    router: ReplicaRouter | None

    @property
    def devices(self) -> list:
        return [e.device for e in self.engines]

    def serve(self, reqs: list[Request], mode: str = "continuous"
              ) -> ServeStats:
        if self.router is not None:
            return self.router.serve(reqs)
        if mode == "wave":
            return self.engines[0].serve_wave(reqs)
        return self.engines[0].serve(reqs)


def check_args(ap: argparse.ArgumentParser, args) -> None:
    """Reject flag combinations the fleet cannot serve."""
    if args.mode == "wave" and args.replicas > 1:
        ap.error("--mode wave is the single-replica legacy baseline; "
                 "drop --replicas or use --mode continuous")
    if args.draft_model and not args.no_spec and args.contiguous_kv:
        ap.error("--draft-model needs the paged KV pool; drop --contiguous-kv")
    roles = _roles(args)
    if len(roles) != args.replicas:
        ap.error(f"--replica-roles names {len(roles)} roles for "
                 f"--replicas {args.replicas}")
    if args.replicas == 1 and roles != ["mixed"]:
        ap.error("--replica-roles needs --replicas > 1 (a lone prefill "
                 "replica has nowhere to migrate blocks)")


def _roles(args) -> list[str]:
    return (args.replica_roles.split(",") if args.replica_roles
            else ["mixed"] * args.replicas)


def init_model(arch: str, smoke: bool, seed: int = 0):
    """Config and seeded weights, initialised in the compute dtype by one
    jitted program: run op by op, each float32 draw would sit in device
    memory beside its cast (15.3 GB peak for qwen2.5-3b on a 16 GB v5e)."""
    cfg = arch_registry.smoke(arch) if smoke else arch_registry.config(arch)
    cfg = cfg.replace(param_dtype=cfg.compute_dtype)
    init = jax.jit(fns_for(cfg).init, static_argnums=0)
    return cfg, init(cfg, jax.random.PRNGKey(seed))


def build(args, model=None) -> Fleet:
    """Replicas (one per device) and router for parsed ``args``, serving
    ``model`` — a ``(cfg, params)`` pair from :func:`init_model`, made here
    when not given.  Params are initialised once; each replica commits its
    own copy and its KV state to its device."""
    cfg, params = model or init_model(args.arch, args.smoke)
    fault_plan = (FaultPlan.parse(args.inject_faults)
                  if args.inject_faults else None)
    kw = dict(max_len=args.prompt_len + args.new_tokens + 1,
              batch_slots=args.slots,
              paged=False if args.contiguous_kv else None,
              pool_blocks=args.kv_pool_blocks,
              preemption=not args.no_preemption,
              prefix_sharing=not args.no_prefix_sharing,
              prefill_chunk=args.prefill_chunk,
              seeded_prefill=not args.no_seeded_prefill,
              host_blocks=0 if args.no_kv_tiering else args.host_blocks,
              fault_plan=fault_plan)
    if args.draft_model and not args.no_spec:
        if args.draft_model == args.arch:
            draft_cfg, draft_params = cfg, params   # self-speculation
        else:
            draft_cfg, draft_params = init_model(args.draft_model, args.smoke,
                                                 seed=1)
        kw.update(draft_cfg=draft_cfg, draft_params=draft_params,
                  spec_k=args.spec_k)
    devices = jax.devices()
    roles = _roles(args)
    if args.replicas == 1:
        return Fleet(cfg, [ServingEngine(cfg, params, device=devices[0], **kw)],
                     None)
    engines = [ServingEngine(cfg, params, name=f"replica{i}", role=roles[i],
                             device=devices[i % len(devices)], **kw)
               for i in range(args.replicas)]
    router = ReplicaRouter(engines, affinity=not args.no_affinity,
                           steal=not args.no_steal,
                           max_retries=args.max_retries)
    return Fleet(cfg, engines, router)


def make_requests(args, cfg) -> list[Request]:
    rng = np.random.default_rng(0)
    mk_sampler = (greedy if args.temperature == 0
                  else lambda: temperature(args.temperature, top_k=40))
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    size=args.prompt_len).astype(np.int32),
                    max_new_tokens=args.new_tokens, sampler=mk_sampler())
            for i in range(args.requests)]
    if args.hipri_every:
        for r in reqs[::args.hipri_every]:
            r.priority = 1
            if args.slo_ttft_ms is not None:
                r.slo_ttft_s = args.slo_ttft_ms / 1e3
    if args.deadline_s is not None:
        for r in reqs:
            r.deadline_s = args.deadline_s
    return reqs


def main(argv: list[str] | None = None) -> int:
    ap = build_parser()
    args = ap.parse_args(argv)
    check_args(ap, args)
    enable_compile_cache()
    fleet = build(args)
    reqs = make_requests(args, fleet.cfg)
    stats = fleet.serve(reqs, args.mode)
    print(f"requests={stats.requests} tokens={stats.tokens} "
          f"wall={stats.wall_s:.2f}s tok/s={stats.tokens_per_s:.2f}")
    print(f"ttft p50={_fmt_ms(stats.ttft_p50_s)} "
          f"p99={_fmt_ms(stats.ttft_p99_s)}  "
          f"tpot={_fmt_ms(stats.mean_tpot_s)}  "
          f"slot_occupancy={stats.slot_occupancy:.2f}")
    if stats.kv_blocks_peak is not None:
        print(f"prefill_compiles={stats.prefill_compiles}  "
              f"kv_blocks_peak={stats.kv_blocks_peak}  "
              f"kv_pool_util={stats.kv_pool_util:.2f}")
    if stats.prefill_tokens_total:
        stall = (f"{stats.decode_stall_p99_s * 1e3:.1f}ms"
                 if stats.decode_stall_p99_s is not None else "n/a")
        print(f"prefill_tokens={stats.prefill_tokens_computed}"
              f"/{stats.prefill_tokens_total} computed "
              f"({stats.prefill_compute_frac:.0%})  "
              f"decode_stall_p99={stall}")
    if args.replicas > 1:
        print(f"router: affinity_hits={stats.router_affinity_hits}  "
              f"steals={stats.router_steals}")
    if stats.spec_proposed:
        spt = (f"{stats.steps_per_token:.2f}"
               if stats.steps_per_token is not None else "n/a")
        print(f"spec: accept_rate={stats.accept_rate:.2f}  "
              f"verify_steps={stats.verify_steps}  "
              f"decode_steps={stats.decode_steps}  steps/token={spt}")
    if stats.kv_migrations:
        print(f"disagg: migrations={stats.kv_migrations}  "
              f"migrated_blocks={stats.migrated_blocks}")
    if stats.kv_spills or stats.kv_fetches:
        hit = (f"{stats.kv_hit_rate:.2f}"
               if stats.kv_hit_rate is not None else "n/a")
        print(f"tiering: spills={stats.kv_spills}  "
              f"fetches={stats.kv_fetches}  "
              f"host_hits={stats.prefix_hits_host}  "
              f"spill_bytes={stats.spill_bytes}  kv_hit_rate={hit}")
    if (stats.requests_failed or stats.requests_retried
            or stats.replica_failures or stats.shed_rejections
            or stats.faults_injected):
        print(f"faults: injected={stats.faults_injected}  "
              f"failed={stats.requests_failed}  "
              f"retried={stats.requests_retried}  "
              f"replica_failures={stats.replica_failures}  "
              f"shed={stats.shed_rejections}")
    if stats.preemptions or stats.prefix_shared_blocks or stats.slo_tracked:
        miss = (f"{stats.slo_miss_rate:.2f}"
                if stats.slo_miss_rate is not None else "n/a")
        print(f"preemptions={stats.preemptions}  "
              f"prefix_shared_blocks={stats.prefix_shared_blocks}  "
              f"slo_miss_rate={miss}")
    print(power_row(serving_power_report(stats.tokens_per_s, fleet.devices)))
    failed = [r.rid for r in reqs if r.state is RequestState.FAILED]
    if failed:
        print(f"FAILED requests: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
