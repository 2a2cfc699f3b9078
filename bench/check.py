"""Whether what the timed path served is correct.

Once the window has closed and the served model is freed, a sample of
the window's requests, drawn from the seed, is run through the float32
reference (``bench/reference.py``) over its prompt and every token it
was served.  The sample holds the request with the most served tokens
and the one with the longest prompt (the one most chunked) beside the
seeded draws; in a cell with shared system prompts nearly every one of
them was prefilled cache-seeded, starting past a shared prefix.

The numbers compared are read from the gaps, over every served token of
the sample, by which the token's reference logit lies below the
reference's best logit at that position (greedy decoding serves the
argmax, so a sound bf16 run leaves only rounding-sized gaps where two
logits nearly tie).  The configuration's ``check`` stanza names them: each
key ``<number>_limit`` compares that number against its limit, and the
run is correct when every one is within its limit.  The numbers:
``logit_gap``, the widest gap (what the dense configurations compare),
and ``gap_p<q>``, the q-th percentile of the gaps (q a whole number from
1 to 99).  A routed model needs a quantile: where two router scores
nearly tie, a sound bf16 run routes a token otherwise than the float32
reference, and that token's gap alone can reach the control's.  Each
limit lies between the readings of sound runs and of the fp8 control, as
``PERF.md`` records.

With ``--control`` the control is put in the program's place: at every
served position of the same sample, the token that the reference
computed through float8 puts first is judged by the same comparison,
and ``correct`` follows from it alone (it has to come out false).  The
program's own readings are printed beside it.
"""
from __future__ import annotations

import re
import time

import numpy as np

from bench import reference as R
from bench import traffic as T


QUANTILE = re.compile(r"gap_p([1-9][0-9]?)")


def number(name: str):
    """The function reading the number ``name`` from an array of gaps."""
    if name == "logit_gap":
        return lambda g: float(np.max(g))
    m = QUANTILE.fullmatch(name)
    if m is None:
        raise ValueError(f"check: no number {name!r} to compare")
    q = int(m.group(1)) / 100
    return lambda g: float(np.quantile(g, q))


def limits(conf: dict) -> dict:
    """The numbers the configuration compares, each with its limit."""
    lim = {k[:-len("_limit")]: v for k, v in conf["check"].items()
           if k.endswith("_limit")}
    if not lim:
        raise ValueError("check: the configuration names no limit")
    for k in lim:
        number(k)
    return lim


def readings(gaps, lim: dict) -> str:
    out = (f"widest gap {float(np.max(gaps))!r}, mean gap "
           f"{float(np.mean(gaps))!r}, tokens off the argmax "
           f"{int(np.sum(gaps > 0))}")
    return out + "".join(f", {k} {number(k)(gaps)!r}" for k in lim
                         if QUANTILE.fullmatch(k))


def sample(mix: dict, seed: int, window: list) -> list:
    """The requests the reference reads: the longest served, the longest
    prompt, and seeded draws among the rest that served any token."""
    served = [r for r in window if len(r.output) >= 1]
    if not served:
        return []
    picks = [max(served, key=lambda r: (len(r.output), -r.rid))]
    lp = max(served, key=lambda r: (len(r.prompt), -r.rid))
    if lp is not picks[0]:
        picks.append(lp)
    rest = [r for r in served if all(r is not p for p in picks)]
    rng = np.random.default_rng([int(seed), 0x5EED])
    k = max(0, min(mix["check"]["requests"] - len(picks), len(rest)))
    picks += [rest[i] for i in sorted(rng.choice(len(rest), k,
                                                 replace=False))]
    return picks


def check(conf: dict, mix: dict, seed: int, window: list, *,
          control: bool = False, out=None,
          seeded: dict | None = None) -> tuple[dict, bool]:
    lim = limits(conf)
    unread = {k: {"value": None, "limit": v} for k, v in lim.items()}
    reqs = sample(mix, seed, window)
    vocab = conf["vocab_size"]
    if not reqs:
        print("check: no request served a token", file=out)
        return unread, False
    n = mix["check"]["requests"]
    prompts = [r.prompt for r in reqs] + [np.zeros(1, np.int32)] * (n - len(reqs))
    outputs = [list(r.output) for r in reqs] + [[]] * (n - len(reqs))
    if any(not 0 <= t < vocab for o in outputs for t in o):
        print("check: a served token lies outside the vocabulary", file=out)
        return unread, False
    smp = R.Sample(prompts, outputs, T.max_prompt(mix) + T.max_output(mix),
                   T.max_output(mix))
    t0 = time.monotonic()
    if control:
        gaps, ctrl = R.control_gaps(conf, seed, smp)
    else:
        gaps = R.served_gaps(conf, seed, smp)
    print(f"check: {len(reqs)} requests, {int(smp.mask.sum())} served "
          f"tokens, prompts {[len(r.prompt) for r in reqs]}, prefix blocks "
          f"seeded {[(seeded or {}).get(r.rid, 0) for r in reqs]}, "
          f"{readings(gaps, lim)}; reference "
          f"{time.monotonic() - t0:.2f}s", file=out)
    if control:
        gaps = ctrl
        print(f"control fp8: {readings(ctrl, lim)}; the control is "
              f"compared in the program's place", file=out)
    checks = {k: {"value": number(k)(gaps), "limit": v}
              for k, v in lim.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return checks, bool(ok)
