"""The trace reduction, on hand-made events and on a small trace recorded
on a TPU v5e (``data/trace_v5e.json``: the events of a short slice of a
``qwen2.5-3b.chat`` traced run, kept small)."""
from pathlib import Path

import pytest

from bench import trace_reduce as TR
from bench.archs import qwen_dense as QD

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


def ev(line, name, start, dur, plane=DEV):
    return TR.Event(plane, line, name, start, dur)


def test_busy_idle_kernels_programs_and_blame():
    events = [
        ev("XLA Modules", "jit__lambda_", 0.0, 4.0),
        ev("XLA Ops", "fusion.1", 0.0, 1.0),
        ev("XLA Ops", "paged_decode_attention.3", 1.0, 1.0),
        ev("XLA Ops", "fusion.2", 1.5, 1.0),             # overlaps: union
        ev("XLA Modules", "jit__lambda_", 6.0, 2.0),
        ev("XLA Ops", "paged_prefill_attention.6", 6.0, 2.0),
        ev("XLA Ops", "while.5", 0.0, 4.0),                # a container
        ev("python", "bench.wait", 3.0, 4.0, plane="/host:CPU"),
        ev("python", "np.asarray", 4.5, 0.2, plane="/host:CPU"),
        ev("python", "bench.trace_span", -1.0, 20.0, plane="/host:CPU"),
    ]
    red = TR.reduce(events, (0.0, 10.0), 1, QD.KERNELS,
                    skip_host=("bench.trace_span",))
    assert red.window_s == 10.0
    assert red.busy_s == pytest.approx(4.0 + 2.0)     # the loop covers 0..4
    assert red.idle_share == pytest.approx(0.4)
    assert red.kernel_s == {"decode_attn": 1.0, "prefill_attn": 2.0}
    assert red.program_s == {"decode": 4.0, "prefill": 2.0}
    # gaps: [4, 6] mid 5 -> bench.wait; [8, 10] mid 9 -> nothing
    assert red.gaps == {"bench.wait": pytest.approx(2.0),
                        "(no host span: Python between calls)": pytest.approx(2.0)}
    b = red.breakdown()
    assert dict(b["device_ops"]) == {"paged_prefill_attention": 2.0,
                                     "fusion": 2.0,
                                     "paged_decode_attention": 1.0}
    assert len(b["idle_gaps"]) == 2


def test_span_clips_and_averages_over_chips():
    events = [ev("XLA Ops", "f", -1.0, 3.0),
              ev("XLA Ops", "f", 0.0, 1.0, plane="/device:TPU:1"),
              ev("XLA Ops", "f", 0.0, 1.0, plane="/device:TPU:2")]
    red = TR.reduce(events, (0.0, 4.0), 2, QD.KERNELS)
    assert red.busy_s == pytest.approx((2.0 + 1.0) / 2)
    with pytest.raises(ValueError):
        TR.reduce(events[:1], (0.0, 4.0), 2, QD.KERNELS)


def test_find_span():
    events = [ev("python", "bench.trace_span", 1.0, 2.0, plane="/host:CPU")]
    assert TR.find_span(events, "bench.trace_span") == (1.0, 3.0)
    with pytest.raises(ValueError):
        TR.find_span(events, "other")


def test_recorded_v5e_trace():
    """150 ms of a traced qwen2.5-3b.chat run on one v5e: one whole decode
    step (32 slots) and the tail of a prefill chunk."""
    events = TR.load_events(DATA / "trace_v5e.json")
    span = TR.find_span(events, "bench.trace_span")
    red = TR.reduce(events, span, 1, QD.KERNELS, skip_host=("bench.trace_span",))
    assert red.window_s == pytest.approx(0.15)
    assert red.busy_s == pytest.approx(0.141898083, rel=1e-6)
    assert red.kernel_s["decode_attn"] == pytest.approx(0.072605281, rel=1e-6)
    assert red.kernel_s["prefill_attn"] == pytest.approx(0.003323517, rel=1e-6)
    assert red.program_s["decode"] == pytest.approx(0.129541418, rel=1e-6)
    assert red.program_s["prefill"] == pytest.approx(0.012357641, rel=1e-6)
    # the idle time falls while the harness's generator sleeps ...
    assert set(red.gaps) == {"bench.wait"}
    assert red.gaps["bench.wait"] == pytest.approx(red.window_s - red.busy_s,
                                                   rel=0.05)
    # ... which explains nothing: without it, the engine's pull of the
    # step's logits to the host takes most of the idle time
    red = TR.reduce(events, span, 1, QD.KERNELS,
                    skip_host=("bench.trace_span", "bench.wait"))
    assert max(red.gaps, key=red.gaps.get) == "np.asarray(jax.Array)"
    assert sum(red.gaps.values()) == pytest.approx(0.0081015, rel=1e-3)
    top = dict(red.breakdown()["device_ops"])
    assert max(top, key=top.get) == "paged_decode_attention"
    assert sum(top.values()) <= red.chips * red.window_s * 1.5
