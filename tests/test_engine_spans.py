"""The serving executor's spans: a tiny paged model served through
``ServingEngine.start`` / ``submit`` under a profiler trace, read back from
the trace's ``.xplane.pb``.  On the CPU the spans land on the ``/host:CPU``
plane, one line per host thread, with their keyword arguments as event
stats."""
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import registry as R
from repro.models.registry import fns_for
from repro.serving.engine import Request, ServingEngine
from repro.serving.sampler import greedy

SPANS = ["engine.step", "engine.admit", "engine.prefill_chunk",
         "engine.sample", "engine.commit", "engine.tables",
         "engine.dispatch", "engine.device_wait", "engine.logits_to_host",
         "engine.idle"]
IN_STEP = ["engine.sample", "engine.commit", "engine.tables",
           "engine.dispatch", "engine.device_wait", "engine.logits_to_host"]
RIDS = (701, 702, 703)
# the profiler's stats type warns each time it is read
pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats:DeprecationWarning")


@pytest.fixture(scope="module")
def host_events(tmp_path_factory):
    """(line, name, start_ns, end_ns, stats) of every host event of one
    traced serve: three requests on two slots, so one queues, prompts
    longer than one prefill chunk, and an idle loop before the first."""
    cfg = R.smoke("qwen2.5-3b")
    params = fns_for(cfg).init(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_len=48, batch_slots=2, paged=True,
                        block_size=4, prefill_chunk=8)
    rng = np.random.default_rng(0)
    reqs = [Request(rid, rng.integers(0, cfg.vocab_size, size=11)
                    .astype(np.int32), max_new_tokens=4, sampler=greedy())
            for rid in RIDS]
    done = threading.Semaphore(0)
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        eng.start()
        try:
            threading.Event().wait(0.1)         # the loop idles first
            for r in reqs:
                eng.submit(r, on_finish=lambda r: done.release())
            for _ in reqs:
                assert done.acquire(timeout=120)
        finally:
            eng.stop()
    assert all(len(r.output) == 4 for r in reqs)
    path = sorted(Path(out).rglob("*.xplane.pb"))[-1]
    events = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                events.append((line.name, e.name, e.start_ns,
                               e.start_ns + e.duration_ns, dict(e.stats)))
    return events


def _named(events, name):
    return [e for e in events if e[1] == name]


@pytest.mark.parametrize("name", SPANS)
def test_span_is_on_the_host_plane(host_events, name):
    assert _named(host_events, name), name


@pytest.mark.parametrize("name", IN_STEP)
def test_phase_nests_inside_a_step(host_events, name):
    steps = _named(host_events, "engine.step")
    for line, _, a, b, _ in _named(host_events, name):
        assert any(sl == line and sa <= a and b <= sb
                   for sl, _, sa, sb, _ in steps), (name, a, b)


def test_prefill_chunk_carries_the_request_id(host_events):
    chunks = _named(host_events, "engine.prefill_chunk")
    assert {e[4].get("rid") for e in chunks} == set(RIDS)
    # 11-token prompts in chunks of at most 8: two chunks each (the
    # step's leftover budget may cut the first), 11 real rows in all
    for rid in RIDS:
        rows = [e[4]["rows"] for e in chunks if e[4].get("rid") == rid]
        assert len(rows) == 2 and sum(rows) == 11 and max(rows) <= 8, rid


def test_step_and_phase_metadata(host_events):
    steps = _named(host_events, "engine.step")
    assert max(e[4].get("decoding", 0) for e in steps) == 2
    admitted = sum(e[4].get("admitted", 0)
                   for e in _named(host_events, "engine.admit"))
    finished = sum(e[4].get("finished", 0)
                   for e in _named(host_events, "engine.commit"))
    assert admitted == finished == len(RIDS)
    assert all(e[4].get("bytes", 0) > 0
               for e in _named(host_events, "engine.logits_to_host"))
