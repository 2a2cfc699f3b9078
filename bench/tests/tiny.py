"""A test-sized cell: the tiny configuration under a short chat or
reasoning mix, run through the whole harness on the CPU (the look for a
chip skipped, the persistent compile cache left alone)."""
import io
import json
from pathlib import Path

from bench import run as R
from bench import traffic as T

DATA = Path(__file__).resolve().parent / "data"


def conf(name: str = "tiny") -> dict:
    return json.loads((DATA / f"{name}.json").read_text())


# the end-to-end metrics each loop reports, as BENCHMARK.json names them
BENCH = {"end_to_end": [
    {"name": n, "unit": "ms", "workloads": ["tiny.chat"]}
    for n in ("ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms")] + [
    {"name": "output_tok_s", "unit": "tokens/s",
     "workloads": ["tiny.reasoning"]},
    {"name": "setup_s", "unit": "s"}], "per_layer": []}


def cell(name: str):
    """``name`` is ``tiny.<mix>``: the tiny configuration under a short
    version of that traffic mix."""
    mix = T.load_mix(name.split(".", 1)[1])
    c = {"name": name, "config": "tiny", "traffic": name, "chips": 1}
    if mix["loop"] == "open":
        mix.update(rate_per_s=8.0,
                   system_prompts=dict(count=2, tokens=32, zipf_s=1.0),
                   prompt_tokens=dict(dist="lognormal", median=24, sigma=0.5,
                                      min=4, max=96),
                   output_tokens=dict(dist="lognormal", median=8, sigma=0.5,
                                      min=2, max=32))
    else:
        mix.update(clients=4,
                   prompt_tokens=dict(dist="lognormal", median=24, sigma=0.5,
                                      min=4, max=64),
                   output_tokens=dict(dist="lognormal", median=16, sigma=0.5,
                                      min=8, max=32))
    return BENCH, c, mix


def run(monkeypatch, name: str, seed: int, *, seconds: float = 2.0,
        control: bool = False, config: str = "tiny") -> tuple[dict, str]:
    """One run of a tiny cell (of configuration ``data/<config>.json``);
    returns (result, what it printed)."""
    monkeypatch.setattr(R, "enable_cache", lambda jax: "off")
    monkeypatch.setattr(R, "DRAIN_S", 20.0)
    bench, c, mix = cell(name)
    out, err = io.StringIO(), io.StringIO()
    res = R.run_cell(bench, c, conf(config), mix, seed, seconds, False,
                     control=control, require_tpu=False, out=out, err=err)
    return res, out.getvalue()
