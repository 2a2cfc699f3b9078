"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``bench/configs/<config>.json``, whose ``bench_arch`` names
its architecture module in ``bench/archs/``) under a traffic mix
(``bench/traffic/<mix>.json``, overridden by ``bench/cells/<cell>.json``).
The run, in one process that holds the chips:

1. fails, printing no result, unless JAX finds a TPU and as many chips as
   the cell asks for;
2. keeps JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
   or ``.jax_cache/`` in the checkout, keeping every program;
3. makes the weights on the device from ``--seed``, builds the engine
   with the launcher's builder, and warms up every step shape the cell's
   lengths can reach (``setup_s`` runs from process start to here);
4. drives the engine for ``--seconds``: open loop (requests on a seeded
   Poisson schedule; latency counts from the scheduled arrival, and
   arrivals go on while the window's requests drain) or closed loop
   (clients that each send the next request when the last finishes; the
   first round is sent, and prefilled, before the window opens, and the
   window counts the tokens delivered inside it); a program compiled
   inside the window fails the run;
5. with ``--trace 1``, takes a profiler trace of a few seconds in the
   middle of the window and reports the per-layer metrics instead of the
   end-to-end ones;
6. frees the served model and compares a seeded sample of the served
   requests with the float32 reference (``bench/check.py``).

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared beside its limit); the last
lines of stderr repeat the checks.  ``--rate`` (a sweep for the knee) and
``--control`` (the fp8 control judged in the program's place, see
``check.py``) are for defining the benchmark, not for its runs.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import archs  # noqa: E402
from bench import traffic as T  # noqa: E402
from bench import work as WK  # noqa: E402

DRAIN_S = 150.0          # longest wait for the window's requests to finish
PRIME_S = 120.0          # longest wait for a closed loop's first round
TRACE_AT = 0.35          # the trace starts this far into the window ...
TRACE_S = 5.0            # ... and lasts this long (or 30% of the window)
WINDOW_SPAN = "bench.trace_span"


class NoChip(RuntimeError):
    pass


def _process_age() -> float:
    """Seconds since this process started, from /proc (0 where absent)."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


AGE0 = _process_age() - (time.monotonic() - T_START)


def load_cell(name: str, root: Path = ROOT):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    conf = json.loads((root / cfg["file"]).read_text())
    mix = T.load_mix(cell["traffic"], name)
    return bench, cell, conf, mix


def metric_names(bench: dict, cell: str, kind: str) -> list[dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _pct(xs, q) -> float | None:
    """The q-th percentile (linear) of xs, inf counted; None when it is
    inf or there is nothing to read."""
    if not xs:
        return None
    v = float(np.percentile(np.asarray(xs, float), q))
    return v if np.isfinite(v) else None


# -- tracing -----------------------------------------------------------------

class Tracer:
    """A profiler trace of ``[at, at + length]`` seconds into the window,
    with the served progress snapshotted at both ends."""

    def __init__(self, eng, reqs: dict, seeded: dict, seconds: float):
        self.eng, self.reqs, self.seeded = eng, reqs, seeded
        self.at = TRACE_AT * seconds
        self.length = min(TRACE_S, 0.3 * seconds)
        self.state = "idle"
        self.dir = None
        self.snaps = []

    def next_event(self, t0: float) -> float | None:
        if self.state == "idle":
            return t0 + self.at
        if self.state == "on":
            return t0 + self.at + self.length
        return None

    def _snap(self):
        tot = self.eng.totals
        return dict(t=time.monotonic(),
                    prefill_rows=tot.prefill_tokens_computed,
                    reqs={rid: (len(r.prompt),
                                max(r.shared_blocks, self.seeded.get(rid, 0)),
                                len(r.output), r.first_token_at)
                          for rid, r in list(self.reqs.items())})

    def poll(self, t0: float, now: float) -> None:
        import jax
        due = self.next_event(t0)
        if due is None or now < due:
            return
        if self.state == "idle":
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            # host spans only: a trace of every Python call slows the host
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self.ann.__enter__()
            self.snaps.append(self._snap())
            self.state = "on"
        elif self.state == "on":
            self.snaps.append(self._snap())
            self.ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def finish(self) -> None:
        if self.state == "on":
            self.poll(-1e18, time.monotonic())

    def reduce(self, chips: int, arch):
        from bench import trace_reduce as TR
        paths = sorted(Path(self.dir).rglob("*.xplane.pb"))
        try:
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            events = TR.load_xplane(paths[-1])
            span = TR.find_span(events, WINDOW_SPAN)
            # the generator's sleeps cover every gap and explain none
            return TR.reduce(events, span, chips, arch.KERNELS,
                             skip_host=(WINDOW_SPAN, "bench.wait"))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# -- drivers -------------------------------------------------------------------

class Driver:
    """Feeds the engine a generated request list for one window."""

    def __init__(self, eng, items, mix, seconds, tracer_on):
        from repro.serving.engine import Request
        self.eng, self.items, self.mix = eng, items, mix
        self.seconds = seconds
        self.reqs: dict = {}                 # rid -> Request, as submitted
        self.seeded: dict = {}               # rid -> prefix blocks it shared
        self.Request = Request
        self.done = 0
        self.lock = threading.Lock()
        self.late: list[float] = []
        self.tracer = (Tracer(eng, self.reqs, self.seeded, seconds)
                       if tracer_on else None)

    def _submit(self, it, on_finish=None):
        import jax
        r = self.Request(it.rid, it.prompt, max_new_tokens=it.max_new)
        self.reqs[it.rid] = r
        with jax.profiler.TraceAnnotation("bench.submit"):
            self.eng.submit(r, on_finish=on_finish)
        return r

    def _wait(self, until: float) -> None:
        import jax
        dt = until - time.monotonic()
        if dt > 0:
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(dt)

    def prime(self) -> None:
        """Work sent before the window opens (none by default)."""

    def _tick(self, t0: float, until: float) -> float:
        """Sleep toward ``until``, waking for the tracer; returns now."""
        if self.tracer is not None:
            ev = self.tracer.next_event(t0)
            if ev is not None:
                until = min(until, ev)
        self._wait(until)
        for rid, r in list(self.reqs.items()):
            # the scheduler zeroes a request's count on release: note it
            # while the request holds its blocks
            if r.shared_blocks:
                self.seeded[rid] = r.shared_blocks
        now = time.monotonic()
        if self.tracer is not None:
            self.tracer.poll(t0, now)
        return now


class OpenLoop(Driver):
    """Poisson arrivals on the schedule ``Item.at``; the window's requests
    are those due in ``[0, seconds)``.  Arrivals go on after the window
    closes, uncounted, until every window request has finished or the
    drain limit is reached, so they finish under the same load."""

    def run(self, t0: float) -> dict:
        in_win = [it for it in self.items if it.at < self.seconds]
        n_win = len(in_win)

        def finished(r):
            if r.rid < n_win:
                with self.lock:
                    self.done += 1

        i = 0
        now = time.monotonic()
        stop = t0 + self.seconds + DRAIN_S
        while now < stop:
            if i < len(self.items) and now >= t0 + self.items[i].at:
                it = self.items[i]
                if it.at < self.seconds:
                    self.late.append(now - (t0 + it.at))
                self._submit(it, on_finish=finished)
                i += 1
                continue
            if now >= t0 + self.seconds and self.done >= n_win:
                break
            nxt = t0 + self.items[i].at if i < len(self.items) else stop
            now = self._tick(t0, min(nxt, now + 0.05, stop))
        t_end = time.monotonic()
        if self.tracer is not None:
            self.tracer.finish()
        self.eng.stop()
        window = [self.reqs[it.rid] for it in in_win if it.rid in self.reqs]
        return dict(window=window, due={it.rid: t0 + it.at for it in in_win},
                    t_end=t_end, n_win=n_win)


class ClosedLoop(Driver):
    """``clients`` clients, each sending its next request the moment the
    last one finishes.  The first round goes out before the window opens
    and the window opens once each of its requests has its first token,
    so the window starts with every slot decoding; it counts the tokens
    delivered between its opening and its close, after ``seconds``, when
    the requests still in flight are stopped where they are."""

    def prime(self) -> None:
        self.nxt = iter(self.items[self.mix["clients"]:])
        self.closing = threading.Event()
        first = [self._submit(it, on_finish=self._finished)
                 for it in self.items[:self.mix["clients"]]]
        from repro.serving.scheduler import RequestState
        stop = time.monotonic() + PRIME_S
        while not all(r.first_token_at is not None
                      or r.state is RequestState.FAILED for r in first):
            if time.monotonic() > stop:
                raise RuntimeError(f"the first round had no first token "
                                   f"after {PRIME_S:.0f}s")
            time.sleep(0.005)

    def _finished(self, r):
        if self.closing.is_set():
            return
        with self.lock:
            self.done += 1
            it = next(self.nxt, None)
        if it is not None:
            self._submit(it, on_finish=self._finished)

    def _delivered(self) -> int:
        return sum(len(r.output) for r in list(self.reqs.values()))

    def run(self, t0: float) -> dict:
        before = self._delivered()
        t1 = t0 + self.seconds
        now = time.monotonic()
        while now < t1:
            now = self._tick(t0, t1)
        self.closing.set()
        delivered = self._delivered() - before
        t_end = time.monotonic()
        if self.tracer is not None:
            self.tracer.finish()
        self.eng.stop()
        return dict(window=list(self.reqs.values()), delivered=delivered,
                    t_end=t_end, n_win=len(self.reqs))


# -- the run -------------------------------------------------------------------

def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {d.platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def enable_cache(jax) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def span_work(arch, s: dict, snaps: list, block: int, chunk: int) -> dict:
    """Operations and bytes served between the two trace snapshots: the
    model FLOPs of the tokens decoded and of the prompt rows prefilled,
    and each of the architecture's kernels' least work."""
    a, b = snaps
    dec_keys, prefill = [], []
    for rid, (P, shared, o1, ftok) in b["reqs"].items():
        o0 = a["reqs"].get(rid, (P, 0, 0, None))[2]
        dec_keys.append(WK.decode_keys(P, o0, o1))
        if ftok is not None and a["t"] <= ftok <= b["t"]:
            prefill.append((shared * block, P))
    keys = np.concatenate(dec_keys) if dec_keys else np.zeros(0)
    rows = b["prefill_rows"] - a["prefill_rows"]
    pre_rows = sum(P - start for start, P in prefill)
    scale = rows / pre_rows if pre_rows else 0.0
    pre_model = 0.0
    for start, P in prefill:
        pre_model += arch.model_flops(s, WK.prefill_keys(start, P), 1)
    served = WK.Served(keys, prefill, chunk, scale)
    work = dict(
        decode_tokens=int(np.size(keys)),
        decode_model_flops=arch.model_flops(s, keys, np.size(keys)),
        prefill_rows=rows,
        prefill_model_flops=pre_model * scale)
    work.update({k: kern.work(s, served) for k, kern in arch.KERNELS.items()})
    return work


def run_cell(bench: dict, cell: dict, conf: dict, mix: dict, seed: int,
             seconds: float, trace: bool, *, control: bool = False,
             require_tpu: bool = True, out=None, err=None) -> dict:
    """One run of one cell; returns the result object it printed."""
    out, err = out or sys.stdout, err or sys.stderr
    import jax
    from bench import check as CK
    from bench import program as P
    from bench.compiles import Compiles
    from bench.peaks import peaks_for
    device = device_info(jax, cell["chips"], require_tpu)
    peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" else None
    print(f"device: {device}  compile cache: {enable_cache(jax)}", file=out)
    compiles = Compiles()
    arch = archs.of(conf)
    s = arch.spec_of(conf)
    CK.limits(conf)                 # every number compared is one it reads
    cfg = arch.model_config(conf)
    dev0 = jax.devices()[0]
    fleet = P.build_fleet(conf, cfg, P.init_params(conf, cfg, seed, dev0))
    eng = fleet.engines[0]
    buckets = P.prefill_buckets(eng.block_size, eng.prefill_chunk,
                                T.max_prompt(mix), eng.max_blocks)
    P.warm_up(eng, buckets, s["V"])
    items = T.generate(mix, seed, T.count_for(mix, seconds, DRAIN_S), s["V"],
                       window_s=seconds)
    c_warm = compiles.mark()
    print(f"warm-up: prefill shapes={len(buckets)} compile={c_warm[0]:.2f}s "
          f"programs={c_warm[1]} cache_hits={c_warm[2]}", file=out)
    drive = {"open": OpenLoop, "closed": ClosedLoop}[mix["loop"]]
    driver = drive(eng, items, mix, seconds, trace)
    eng.start()
    t_prime = time.monotonic()
    driver.prime()
    base = eng.begin_window()
    c_warm = compiles.mark()
    t0 = time.monotonic()
    setup_s = t0 - T_START + AGE0
    print(f"window opens: set-up {setup_s:.3f}s, of it sending the first "
          f"round {t0 - t_prime:.3f}s", file=out)
    res = driver.run(t0)
    c_end = compiles.mark()
    stats = eng.collect_window(base, res["window"], seconds)
    mstats = [d.memory_stats() or {} for d in jax.devices()[:cell["chips"]]]
    mem = [m.get("peak_bytes_in_use") for m in mstats]
    peak_bytes = max((m for m in mem if m is not None), default=None)
    block, chunk = eng.block_size, eng.prefill_chunk
    del fleet, eng, driver.eng
    if driver.tracer is not None:
        driver.tracer.eng = None
    gc.collect()
    print(f"memory: limit {[m.get('bytes_limit') for m in mstats]}, live "
          f"after freeing the engine "
          f"{sum(a.nbytes for a in jax.live_arrays())} bytes", file=out)

    window = res["window"]
    from repro.serving.scheduler import RequestState
    failed = [r for r in window if r.state is RequestState.FAILED]
    e2e = {"setup_s": setup_s}
    if mix["loop"] == "open":
        due = res["due"]
        inf = float("inf")
        unfinished = [r for r in window if r.state is not RequestState.DONE]
        missing = res["n_win"] - len(window)
        ttft = [(r.first_token_at - due[r.rid]) if r.first_token_at else inf
                for r in window] + [inf] * missing
        tpot = [((r.finished_at - r.first_token_at) / (len(r.output) - 1)
                 if r.state is RequestState.DONE else inf)
                for r in window if r.max_new_tokens > 1] + [inf] * missing
        for q, name in ((50, "ttft_p50_ms"), (95, "ttft_p95_ms")):
            v = _pct(ttft, q)
            e2e[name] = None if v is None else 1e3 * v
        v = _pct(tpot, 95)
        e2e["tpot_p95_ms"] = None if v is None else 1e3 * v
        late = driver.late
        print(f"window: {res['n_win']} requests due, "
              f"{res['n_win'] - len(unfinished) - missing} finished, "
              f"{len(failed)} failed, drained at "
              f"{res['t_end'] - t0:.2f}s; generator late p50="
              f"{1e3 * float(np.median(late)) if late else 0:.3f}ms "
              f"max={1e3 * max(late, default=0):.3f}ms", file=out)
        n_fail = len(unfinished) + missing      # failed ones included
    else:
        e2e["output_tok_s"] = res["delivered"] / seconds
        print(f"window: {res['n_win']} requests sent, {driver.done} "
              f"finished, {len(failed)} failed, {res['delivered']} tokens "
              f"delivered", file=out)
        n_fail = len(failed)
    in_window = c_end[3] - c_warm[3]
    print(f"compiles inside the window: {in_window} "
          f"({c_end[0] - c_warm[0]:.3f}s)", file=out)
    if in_window:
        raise RuntimeError(f"{in_window} programs compiled inside the "
                           f"window: warm-up missed a shape")
    print(f"peak_bytes_in_use per chip: {mem}", file=out)
    print(f"counters: prefill_tokens={stats.prefill_tokens_computed}/"
          f"{stats.prefill_tokens_total} slot_occupancy="
          f"{stats.slot_occupancy:.4f} decode_steps={stats.decode_steps} "
          f"tokens={stats.tokens}", file=out)

    device["memory_peak_bytes"] = peak_bytes
    result = {"correct": False, "attempted": res["n_win"], "failed": n_fail}
    if trace:
        red = driver.tracer.reduce(cell["chips"], arch)
        work = span_work(arch, s, driver.tracer.snaps, block, chunk)
        ctx = dict(stats=stats, trace=red, work=work, peaks=peaks, spec=s,
                   chips=cell["chips"], out=out)
        metrics = {}
        for m in metric_names(bench, cell["name"], "per_layer"):
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        print(f"trace: window={red.window_s:.4f}s busy={red.busy_s:.4f}s "
              f"programs={red.program_s} kernels={red.kernel_s} "
              f"decode_tokens={work['decode_tokens']} "
              f"prefill_rows={work['prefill_rows']}", file=out)
    else:
        metrics = {}
        for m in metric_names(bench, cell["name"], "end_to_end"):
            v = e2e.get(m["name"])
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = red.breakdown()

    checks, ok = CK.check(conf, mix, seed, window, control=control, out=out,
                          seeded=driver.seeded)
    result["correct"] = ok
    result["checks"] = checks
    out.flush()
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}", file=err)
    print(json.dumps(result), file=out)
    out.flush()
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="open loop: arrivals per second in place of the "
                         "cell's (the sweep that finds the knee)")
    ap.add_argument("--control", action="store_true",
                    help="judge the fp8 control in the program's place")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        bench, cell, conf, mix = load_cell(args.workload)
        if args.rate is not None:
            mix["rate_per_s"] = args.rate
        run_cell(bench, cell, conf, mix, args.seed, args.seconds,
                 bool(args.trace), control=args.control)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except Exception:  # noqa: BLE001 — a failed run prints no result
        traceback.print_exc()
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
