"""The readers of the executor's counters, on hand-built ``ServeStats``
and on the stats of a program that has no such counters."""
import io
from types import SimpleNamespace

import pytest

from bench import run as R
from repro.serving.engine import ServeStats


def read(name, stats):
    out = io.StringIO()
    return R.load_reader(name)(dict(stats=stats, out=out)), out.getvalue()


def test_host_ms_per_step():
    v, printed = read("engine.host_ms_per_step",
                      ServeStats(step_host_s=0.9, decode_steps=80,
                                 verify_steps=20))
    assert v == pytest.approx(9.0)
    assert "0.9s of host time over 80 decode and 20 verify steps" in printed


def test_queue_wait_ms():
    v, _ = read("sched.queue_wait_ms",
                ServeStats(queue_wait_s=1.2, admissions=10))
    assert v == pytest.approx(120.0)


@pytest.mark.parametrize("name, stats", [
    ("engine.host_ms_per_step", ServeStats(step_host_s=0.9)),
    ("sched.queue_wait_ms", ServeStats(queue_wait_s=0.0, admissions=0)),
    # a program without the counters (the parent of the change that
    # added them) reports nothing and does not raise
    ("engine.host_ms_per_step",
     SimpleNamespace(decode_steps=10, verify_steps=0)),
    ("sched.queue_wait_ms", SimpleNamespace(decode_steps=10, verify_steps=0)),
], ids=["no-steps", "no-admissions", "no-host-counter", "no-queue-counter"])
def test_nothing_to_read(name, stats):
    assert read(name, stats)[0] is None
