"""Smoke run of the serving path on a TPU at qwen2.5-3b's published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # four one-chip replicas on one host

One chip: print the device; check both paged attention kernels against
their jnp oracles at the model's widths and serving shapes; then build the
fleet exactly as ``python -m repro.launch.serve`` does (random bf16
weights from ``PRNGKey(0)``) and serve 8 greedy requests of 32 new tokens
on 4 slots, two of them sharing a 128-token prefix so the second prefills
cache-seeded.  A warm-up pass with the same shapes and other tokens
compiles every program first, so the measured pass compiles nothing.

Four chips: serve the same requests on 4 mixed replicas and on a
prefill,decode,decode,decode fleet, whose KV migrations cross chips, and
compare both with one replica: greedy outputs must be bit-identical, and
each replica's arrays must sit on a device of its own.

Everything runs in this one process, which holds the chips; it starts no
other.  The compile cache is JAX_COMPILATION_CACHE_DIR when set, else
.jax_cache/ at the repo root.  Timings printed here are smoke readings,
not benchmark results.  The last line of stdout is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``; any failure makes
it ``"ok": false`` and the exit code non-zero, as does finding no TPU.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SERVE_ARGS = ["--arch", "qwen2.5-3b", "--slots", "4", "--prompt-len", "400",
              "--new-tokens", "32"]
NEW_TOKENS = 32
PREFIX = 128
# prompt lengths: the first two share a PREFIX-token prefix; five prefill
# shapes in all, the second prompt's seeded one included
LENGTHS = (300, 256, 64, 100, 150, 200, 256, 400)
# max |kernel - oracle| on bf16 inputs against the float32 oracle: bf16
# rounding of the probabilities and of the output (~2**-9 each) on
# outputs of magnitude <= 1
KERNEL_TOL = 2e-2
BS = 16


class _Compiles:
    """Compile time and program count, from JAX's monitoring events
    (tracing, lowering, and backend compiles or persistent-cache reads)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.programs += event.endswith("backend_compile_duration")

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def mark(self):
        return self.seconds, self.programs, self.cache_hits


def _device_info(jax) -> dict:
    d = jax.devices()[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}")
    return info


def _require(info: dict, count: int) -> None:
    if info["platform"] != "tpu":
        raise RuntimeError(f"no TPU: JAX found {info['platform']}")
    if info["count"] < count:
        raise RuntimeError(f"needs {count} chips, JAX found {info['count']}")


def _pool_case(key, cfg, nb, quant):
    """A random ``(N, K, bs, D)`` pool pair in bf16, or in int8 with its
    scales."""
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import quantize_kv
    K, D = cfg.num_kv_heads, cfg.resolved_head_dim
    kk, kv = jax.random.split(key)
    kp = jax.random.normal(kk, (nb, K, BS, D), jnp.float32)
    vp = jax.random.normal(kv, (nb, K, BS, D), jnp.float32)
    if not quant:
        return kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16), {}
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    return kq, vq, dict(k_scale=ks, v_scale=vs)


def check_kernels(cfg) -> None:
    """Both paged kernels, compiled for the chip, against their oracles
    run in float32 on the same (bf16 or int8) inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention.kernel import paged_decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.kernels.prefill_attention.kernel import \
        paged_prefill_attention
    from repro.kernels.prefill_attention.ref import \
        paged_prefill_attention_ref
    H, D = cfg.num_heads, cfg.resolved_head_dim
    slots, mb = 4, 28                     # the serving run's slots/table
    nb = 1 + slots * mb
    tables = jnp.asarray(1 + np.random.default_rng(0).permutation(
        slots * mb).reshape(slots, mb).astype(np.int32))
    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda x: x.astype(jnp.float32))
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    for quant in (False, True):
        kp, vp, sc = _pool_case(keys[0], cfg, nb, quant)
        kp_ref, vp_ref = ((kp, vp) if quant else f32((kp, vp)))
        q = jax.random.normal(keys[1], (slots, H, D), jnp.bfloat16)
        lengths = jnp.asarray([433, 300, 64, 17], jnp.int32)
        out = paged_decode_attention(q, kp, vp, tables, lengths, **sc)
        ref = paged_decode_attention_ref(f32(q), kp_ref, vp_ref, tables,
                                         lengths, **sc)
        _report_err(f"paged_decode {'int8' if quant else 'bf16'} "
                    f"B={slots} H={H} D={D}", out, ref)
        # a seeded chunk: 256 rows at q_start=128 (two query tiles)
        C, q0 = 256, PREFIX
        qc = jax.random.normal(keys[2], (1, C, H, D), jnp.bfloat16)
        qs = jnp.asarray([q0], jnp.int32)
        kl = jnp.asarray([q0 + C], jnp.int32)
        out = paged_prefill_attention(qc, kp, vp, tables[:1], qs, kl, **sc)
        ref = paged_prefill_attention_ref(f32(qc), kp_ref, vp_ref,
                                          tables[:1], qs, kl, **sc)
        _report_err(f"paged_prefill {'int8' if quant else 'bf16'} "
                    f"C={C} q_start={q0}", out, ref)


def _report_err(name, out, ref) -> None:
    import numpy as np
    out = np.asarray(out, np.float32)
    err = float(np.abs(out - np.asarray(ref, np.float32)).max())
    print(f"kernel {name}: max_abs_err={err:.3e} (tol {KERNEL_TOL})")
    if not np.isfinite(out).all() or not err <= KERNEL_TOL:
        raise AssertionError(f"{name}: kernel disagrees with its oracle")


def make_requests(cfg, seed: int):
    import numpy as np
    from repro.serving.engine import Request
    from repro.serving.sampler import greedy
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, PREFIX)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in LENGTHS]
    for p in prompts[:2]:
        p[:PREFIX] = prefix
    return [Request(i, p.astype(np.int32), max_new_tokens=NEW_TOKENS,
                    sampler=greedy()) for i, p in enumerate(prompts)]


def _check_served(reqs, stats, vocab: int) -> None:
    from repro.serving.scheduler import RequestState
    done = sum(r.state is RequestState.DONE for r in reqs)
    lens = [len(r.output) for r in reqs]
    print(f"served: {done}/{len(reqs)} done  failed={stats.requests_failed} "
          f"tokens_per_request={lens}")
    if done != len(reqs) or stats.requests_failed:
        raise AssertionError("requests did not all finish")
    if any(n != NEW_TOKENS for n in lens):
        raise AssertionError(f"expected {NEW_TOKENS} tokens per request")
    if any(not 0 <= t < vocab for r in reqs for t in r.output):
        raise AssertionError("token id outside the vocabulary")


def _step_texts(eng):
    """Compiled text of the engine's decode step and of one prefill
    signature it served (same shapes and dtypes as served, so JAX's
    caches answer)."""
    import jax.numpy as jnp
    import numpy as np
    decode = eng._decode.lower(
        eng.params, eng._put(np.zeros((eng.slots, 1), np.int32)),
        eng._state).compile().as_text()
    _, C, mb = max(eng._prefill_shapes)
    prefill = eng._prefill_paged.lower(
        eng.params, eng._put(np.zeros((1, C), np.int32)), eng._state,
        eng._put(np.zeros((C // eng.block_size,), np.int32)),
        eng._put(np.zeros((1, mb), np.int32)),
        eng._put(np.asarray([0], np.int32)),
        eng._put(np.asarray([C], np.int32)),
        jnp.int32(C - 1)).compile().as_text()
    return {"decode": decode, f"prefill C={C} mb={mb}": prefill}


def check_reference_token(fleet, req) -> None:
    """The engine's first token for ``req`` against a dense, Pallas-free
    prefill of the same prompt (jnp attention): it must be the reference's
    argmax up to bf16 noise, 1% of the logit range."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.registry import fns_for
    eng = fleet.engines[0]
    cfg = fleet.cfg
    dense = jax.jit(functools.partial(fns_for(cfg).prefill, cfg))
    lg = np.asarray(dense(eng.params, {"tokens": jnp.asarray(
        req.prompt)[None]})[0][0], np.float32)
    tok = req.output[0]
    gap = float(lg.max() - lg[tok])
    span = float(lg.max() - lg.min())
    print(f"reference: request {req.rid} first token {tok}, dense argmax "
          f"{int(lg.argmax())}, logit gap {gap:.4f} of range {span:.4f}")
    if not np.isfinite(lg).all() or gap > 0.01 * span:
        raise AssertionError("served token disagrees with the dense "
                             "reference")


def one_chip(jax, compiles: _Compiles) -> None:
    from repro.launch import serve
    args = serve.build_parser().parse_args(SERVE_ARGS)
    t0 = time.monotonic()
    fleet = serve.build(args)
    jax.block_until_ready(fleet.engines[0].params)
    print(f"setup: weights and fleet {time.monotonic() - t0:.2f}s")
    check_kernels(fleet.cfg)

    eng = fleet.engines[0]
    c0 = compiles.mark()
    t0 = time.monotonic()
    warm = make_requests(fleet.cfg, seed=1)
    fleet.serve(warm)
    c1 = compiles.mark()
    print(f"warm-up: wall={time.monotonic() - t0:.2f}s "
          f"compile={c1[0] - c0[0]:.2f}s programs={c1[1] - c0[1]} "
          f"cache_hits={c1[2] - c0[2]}")
    print(f"compiled step shapes: decode=1 prefill={eng.prefill_compiles} "
          f"{sorted(eng._prefill_shapes)}")

    reqs = make_requests(fleet.cfg, seed=2)
    stats = fleet.serve(reqs)
    c2 = compiles.mark()
    print(f"smoke reading (not a benchmark): tok/s={stats.tokens_per_s:.2f} "
          f"ttft_p50={stats.ttft_p50_s * 1e3:.1f}ms "
          f"ttft_p99={stats.ttft_p99_s * 1e3:.1f}ms "
          f"tpot={stats.mean_tpot_s * 1e3:.2f}ms wall={stats.wall_s:.2f}s "
          f"compiles_in_window={c2[1] - c1[1]}")
    print(f"seeded prefill: prefix_shared_blocks={stats.prefix_shared_blocks}"
          f" prefill_tokens={stats.prefill_tokens_computed}"
          f"/{stats.prefill_tokens_total} computed")
    _check_served(reqs, stats, fleet.cfg.vocab_size)
    if stats.prefix_shared_blocks < PREFIX // BS:
        raise AssertionError("the shared prefix was not seeded")
    check_reference_token(fleet, reqs[1])

    for name, text in _step_texts(eng).items():
        has = "tpu_custom_call" in text
        print(f"compiled {name}: tpu_custom_call={has}")
        if not has:
            raise AssertionError(f"{name} holds no Pallas kernel")
    mem = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use={mem.get('peak_bytes_in_use')}")


def four_chip(jax, compiles: _Compiles) -> None:
    from repro.launch import serve
    args = serve.build_parser().parse_args(SERVE_ARGS)
    model = serve.init_model(args.arch, args.smoke)
    outputs = {}
    for name, extra in (("1 replica", []),
                        ("4 mixed", ["--replicas", "4"]),
                        ("prefill,decode,decode,decode",
                         ["--replicas", "4", "--replica-roles",
                          "prefill,decode,decode,decode"])):
        fleet_args = serve.build_parser().parse_args(SERVE_ARGS + extra)
        fleet = serve.build(fleet_args, model)
        c0 = compiles.mark()
        fleet.serve(make_requests(fleet.cfg, seed=1))       # warm-up
        reqs = make_requests(fleet.cfg, seed=2)
        stats = fleet.serve(reqs)
        c1 = compiles.mark()
        devs = [{d for leaf in jax.tree_util.tree_leaves((e.params, e._state))
                 for d in leaf.devices()} for e in fleet.engines]
        print(f"{name}: replica devices "
              f"{[sorted(d.id for d in ds) for ds in devs]}")
        if any(len(ds) != 1 for ds in devs) or \
                len(set().union(*devs)) != len(devs):
            raise AssertionError(f"{name}: replicas do not each sit on a "
                                 f"device of their own")
        print(f"{name}: wall={stats.wall_s:.2f}s "
              f"tok/s={stats.tokens_per_s:.2f} (smoke reading) "
              f"migrations={stats.kv_migrations} "
              f"migrated_blocks={stats.migrated_blocks} "
              f"compile={c1[0] - c0[0]:.2f}s")
        _check_served(reqs, stats, fleet.cfg.vocab_size)
        outputs[name] = [list(r.output) for r in reqs]
        del fleet
        gc.collect()
    print("peak_bytes_in_use per chip: "
          f"{[(d.memory_stats() or {}).get('peak_bytes_in_use') for d in jax.devices()]}")
    base = outputs.pop("1 replica")
    for name, out in outputs.items():
        same = out == base
        print(f"{name} vs 1 replica: bit-identical={same}")
        if not same:
            raise AssertionError(f"{name} outputs differ from one replica")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-replica fleets and their "
                         "one-replica comparison (needs 4 chips)")
    args = ap.parse_args(argv)
    device = None
    try:
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        device = _device_info(jax)
        _require(device, 4 if args.four_chip else 1)
        print(f"compile cache: {enable_compile_cache()}")
        compiles = _Compiles()
        t0 = time.monotonic()
        (four_chip if args.four_chip else one_chip)(jax, compiles)
        print(f"total: wall={time.monotonic() - t0:.2f}s "
              f"compile={compiles.seconds:.2f}s programs={compiles.programs} "
              f"cache_hits={compiles.cache_hits}")
        ok = True
    except Exception:  # noqa: BLE001 — any failure is reported as ok=false
        traceback.print_exc()
        ok = False
    sys.stdout.flush()
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
