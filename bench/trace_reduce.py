"""From a profiler trace to device busy and idle time, time per program
and per kernel, and the breakdown the run's result line carries.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  It is read
with ``jax.profiler.ProfileData`` into flat :class:`Event` records, so the
reduction itself is plain Python over a list and is tested on a small
recorded trace (``bench/tests/data``).

- A device is a plane named ``/device:TPU:<i>``.  Its ``XLA Ops`` line
  holds one event per operation run; its ``XLA Modules`` line one event
  per program run.
- Busy time is the union of the operation intervals inside the span;
  idle is the rest of the span.
- A kernel is found by its name among the operations; a program is
  classed by the kernels that ran inside it (the step programs are
  jitted lambdas, so their own names say nothing).  The kernels, each
  with its name substrings and the program class it marks, are the
  cell's architecture module's ``KERNELS`` (``bench/archs/``).
- Each idle gap is put down to the innermost host span that covers it,
  on any host thread: the host was busy with that when the device
  waited.
"""
from __future__ import annotations

import bisect
import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

# control flow whose events span the operations inside them: busy time
# takes their union anyway, the breakdown leaves them out
CONTAINERS = ("while", "conditional", "call")
DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MIN_GAP_S = 20e-6      # shorter idle slivers are launch jitter, not gaps
TOP = 10


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start: float        # seconds, on the trace's common clock
    dur: float          # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Reduced:
    window_s: float
    busy_s: float                   # mean over the chips read
    chips: int
    op_s: dict = field(default_factory=dict)        # op kind -> s (all chips)
    kernel_s: dict = field(default_factory=dict)    # kernel -> s (all chips)
    program_s: dict = field(default_factory=dict)   # class -> s (all chips)
    gaps: dict = field(default_factory=dict)        # host span -> idle s

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def load_xplane(path: str | Path) -> list[Event]:
    """Device op/module events and host events of one trace file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        dev = DEVICE.match(plane.name)
        if not dev and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                name = short_name(e.name) if dev else e.name
                out.append(Event(plane.name, line.name, name,
                                 e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return out


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """``fusion.12`` -> ``fusion``: what the breakdown groups by."""
    return re.sub(r"\.\d+$", "", name)


def load_events(path: str | Path) -> list[Event]:
    """Events saved as JSON rows ``[plane, line, name, start, dur]`` (the
    recorded trace the tests read)."""
    return [Event(*r) for r in json.loads(Path(path).read_text())]


def find_span(events: list[Event], name: str) -> tuple[float, float]:
    spans = [e for e in events if e.plane.startswith("/host:")
             and e.name == name]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    e = max(spans, key=lambda e: e.dur)
    return e.start, e.end


def _clip(a: float, b: float, t0: float, t1: float) -> tuple[float, float]:
    return max(a, t0), min(b, t1)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def kernel_of(name: str, kernels: dict) -> str | None:
    for k, keys in kernels.items():
        if any(s in name for s in keys):
            return k
    return None


def reduce(events: list[Event], span: tuple[float, float], chips: int,
           kernels: dict, skip_host: tuple[str, ...] = ()) -> Reduced:
    """Reduce the events of devices ``0 .. chips - 1`` inside ``span``.
    ``kernels`` is an architecture module's ``KERNELS``: each kernel with
    the substrings that name it among the operations and the program
    class its presence marks (the first class that matches wins).  Host
    spans named in ``skip_host`` (the harness's own span around the whole
    traced window) never take the blame for a gap."""
    t0, t1 = span
    programs = {kern.program: k for k, kern in kernels.items()
                if kern.program is not None}
    kernels = {k: kern.names for k, kern in kernels.items()}
    by_dev: dict[int, dict[str, list[Event]]] = defaultdict(
        lambda: defaultdict(list))
    host = []
    for e in events:
        m = DEVICE.match(e.plane)
        if m:
            if int(m.group(1)) < chips:
                by_dev[int(m.group(1))][e.line].append(e)
        elif e.name not in skip_host:
            host.append(e)
    if len(by_dev) < chips:
        raise ValueError(f"trace holds {len(by_dev)} of {chips} devices")
    red = Reduced(window_s=t1 - t0, busy_s=0.0, chips=chips)
    op_s: dict = defaultdict(float)
    kernel_s: dict = defaultdict(float)
    program_s: dict = defaultdict(float)
    gaps: dict = defaultdict(float)
    host.sort(key=lambda e: e.start)
    for dev in sorted(by_dev):
        ops = by_dev[dev][OPS_LINE]
        busy = []
        for e in ops:
            a, b = _clip(e.start, e.end, t0, t1)
            if b <= a:
                continue
            busy.append((a, b))
            kind = op_kind(e.name)
            if kind not in CONTAINERS:
                op_s[kind] += b - a
            k = kernel_of(e.name, kernels)
            if k:
                kernel_s[k] += b - a
        union = _union(busy)
        red.busy_s += sum(b - a for a, b in union) / chips
        ops.sort(key=lambda e: e.start)
        starts = [e.start for e in ops]
        for mod in by_dev[dev][MODULES_LINE]:
            a, b = _clip(mod.start, mod.end, t0, t1)
            if b <= a:
                continue
            i0 = bisect.bisect_left(starts, mod.start)
            i1 = bisect.bisect_right(starts, mod.end)
            inside = {kernel_of(e.name, kernels) for e in ops[i0:i1]}
            cls = next((p for p, k in programs.items() if k in inside),
                       "other")
            program_s[cls] += b - a
        edges = [t0] + [x for ab in union for x in ab] + [t1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2])
                if b - a >= MIN_GAP_S]
        for (a, b), who in zip(idle, _blame(host, idle)):
            gaps[who] += (b - a) / chips
    red.op_s, red.kernel_s = dict(op_s), dict(kernel_s)
    red.program_s, red.gaps = dict(program_s), dict(gaps)
    return red


def _blame(host: list[Event], idle: list[tuple[float, float]]) -> list[str]:
    """For each gap (in time order): the innermost (latest-starting) host
    span that covers its middle.  ``host`` is sorted by start."""
    out, live, i = [], [], 0
    for a, b in idle:
        mid = 0.5 * (a + b)
        while i < len(host) and host[i].start <= mid:
            live.append(host[i])
            i += 1
        live = [e for e in live if e.end >= mid]
        best = max(live, key=lambda e: e.start, default=None)
        out.append(best.name if best is not None else "(no host span: Python between calls)")
    return out
