"""Everything the benchmark touches of the system under test, in one place.

The benchmark builds the served model from its own configuration file (the
program's ``ModelConfig`` from its architecture module) and its own seeded
weights, and hands them to the launcher's own fleet builder
(``repro.launch.serve.build``); it warms up the engine's jitted
step programs at every shape the cell's traffic can reach.  Nothing else
of the program is used: the window drives ``ServingEngine.start`` /
``submit``, and the results are the requests' own outputs and stamps and
the engine's ``ServeStats`` counters.
"""
from __future__ import annotations

import numpy as np

from bench import archs
from bench import weights as W


def init_params(conf: dict, cfg, seed: int, device):
    """The seeded weights, made on ``device`` by one jitted call in the
    served dtype, checked leaf by leaf against the program's own tree."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.models.registry import fns_for
    arch = archs.of(conf)
    s = arch.spec_of(conf)
    dt = jnp.dtype(s["dtype"])
    make = jax.jit(lambda key: W.make_tree(arch, s, key, dt),
                   out_shardings=SingleDeviceSharding(device))
    want = jax.eval_shape(lambda k: fns_for(cfg).init(cfg, k),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(make, W.root_key(seed))
    def sig(tree):
        return (jax.tree_util.tree_structure(tree),
                [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(tree)])
    if sig(want) != sig(got):
        raise ValueError(f"benchmark weight tree does not match the "
                         f"program's: {want} vs {got}")
    return make(W.root_key(seed))


def build_fleet(conf: dict, cfg, params):
    """One replica on the first device, built by the launcher."""
    from repro.launch import serve
    sv = conf["serve"]
    new = sv["max_new_tokens"]
    argv = ["--arch", conf["name"], "--slots", str(sv["slots"]),
            "--prompt-len", str(sv["max_len"] - 1 - new),
            "--new-tokens", str(new),
            "--prefill-chunk", str(sv["prefill_chunk"])]
    if sv.get("pool_blocks"):
        argv += ["--kv-pool-blocks", str(sv["pool_blocks"])]
    args = serve.build_parser().parse_args(argv)
    return serve.build(args, model=(cfg, params))


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def prefill_buckets(block: int, chunk: int, max_rows: int,
                    max_blocks: int) -> set[tuple[int, int]]:
    """Every (chunk width, read-table width) signature the engine's paged
    prefill can take for prompts of up to ``max_rows`` tokens.  The
    engine pads a chunk to a power-of-two multiple of the block size, at
    most ``chunk``: a chunk of width C > block holds more than C / 2 real
    rows (a smaller one would have taken C / 2) unless the step's budget
    cut it, when it is full; its table is the power of two at least
    covering the rows seen so far, capped at the slot's table."""
    out = set()
    hi = min(_pow2_at_least(-(-max_rows // block)), max_blocks)
    C = block
    while C <= chunk:
        real_min = 1 if C == block else C // 2 + 1
        mb = _pow2_at_least(-(-real_min // block))
        while mb <= hi:
            out.add((C, min(mb, max_blocks)))
            mb *= 2
        C *= 2
    return out


def warm_up(eng, buckets: set[tuple[int, int]], vocab: int) -> None:
    """Compile (or read back from the persistent cache) every prefill
    signature in ``buckets`` and the decode step, by calling the engine's
    own jitted programs with inputs that write only the trash block; then
    serve one short request through the engine so every eager helper on
    the path is compiled too."""
    import jax.numpy as jnp
    from repro.serving.engine import Request
    if eng._state is None:
        eng._state = eng._init_state()
    bs = eng.block_size
    for C, mb in sorted(buckets):
        last, eng._state = eng._prefill_paged(
            eng.params, eng._put(np.zeros((1, C), np.int32)), eng._state,
            eng._put(np.zeros((C // bs,), np.int32)),
            eng._put(np.zeros((1, mb), np.int32)),
            eng._put(np.asarray([0], np.int32)),
            eng._put(np.asarray([C], np.int32)), jnp.int32(C - 1))
        np.asarray(last[0])
    last, eng._state = eng._decode(
        eng.params, eng._put(np.zeros((eng.slots, 1), np.int32)), eng._state)
    np.asarray(last)
    rng = np.random.default_rng(12345)
    eng.serve([Request(-1, rng.integers(0, vocab, 3 * bs + 5,
                                        dtype=np.int32), max_new_tokens=3)])
