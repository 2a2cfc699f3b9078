"""The warm-up's prefill signatures cover every one the engine takes
for the cell's length range: serve prompts across the range, with shared
prefixes (seeded starts) and several prompts per step (budget-cut
chunks), and compare the engine's own record of what it compiled."""
import numpy as np

from bench import archs
from bench import program as P
from bench.tests import tiny


def test_buckets_cover_what_the_engine_serves():
    import jax
    from repro.serving.engine import Request
    conf = tiny.conf()
    cfg = archs.of(conf).model_config(conf)
    fleet = P.build_fleet(conf, cfg, P.init_params(conf, cfg, 4,
                                                   jax.devices()[0]))
    eng = fleet.engines[0]
    max_rows = 128
    buckets = P.prefill_buckets(eng.block_size, eng.prefill_chunk, max_rows,
                                eng.max_blocks)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, 256, 48).astype(np.int32)
    reqs = []
    for i, n in enumerate(list(range(1, max_rows + 1, 7)) + [max_rows]):
        p = rng.integers(0, 256, n).astype(np.int32)
        if i % 2 and n > 48:
            p[:48] = prefix
        reqs.append(Request(i, p, max_new_tokens=2))
    eng.serve(reqs)
    seen = {(C, mb) for _, C, mb in eng._prefill_shapes}
    assert seen <= buckets, seen - buckets
    assert len(seen) >= len(buckets) - 2
    assert buckets == {(16, 1), (16, 2), (16, 4), (16, 8),
                       (32, 2), (32, 4), (32, 8)}
