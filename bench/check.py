"""Whether what the timed path served is correct.

Once the window has closed and the served model is freed, a sample of
the window's requests, drawn from the seed, is run through the float32
reference (``bench/reference.py``) over its prompt and every token it
was served.  The sample holds the request with the most served tokens
and the one with the longest prompt (the one most chunked) beside the
seeded draws; in a cell with shared system prompts nearly every one of
them was prefilled cache-seeded, starting past a shared prefix.

The number compared is the widest gap, over every served token of the
sample, by which the token's reference logit lies below the reference's
best logit at that position (greedy decoding serves the argmax, so a
sound bf16 run leaves only rounding-sized gaps where two logits nearly
tie).  Its limit is the configuration's ``check.logit_gap_limit``, set
between the widest gap of sound runs and that of the fp8 control, as
``PERF.md`` records.

With ``--control`` the control is put in the program's place: at every
served position of the same sample, the token that the reference
computed through float8 puts first is judged by the same comparison,
and ``correct`` follows from it alone (it has to come out false).  The
program's own reading is printed beside it.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference as R
from bench import traffic as T


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
    limit = conf["check"]["logit_gap_limit"]
    reqs = sample(mix, seed, window)
    vocab = conf["vocab_size"]
    if not reqs:
        print("check: no request served a token", file=out)
        return {"logit_gap": {"value": None, "limit": limit}}, False
    n = mix["check"]["requests"]
    prompts = [r.prompt for r in reqs] + [np.zeros(1, np.int32)] * (n - len(reqs))
    outputs = [list(r.output) for r in reqs] + [[]] * (n - len(reqs))
    if any(not 0 <= t < vocab for o in outputs for t in o):
        print("check: a served token lies outside the vocabulary", file=out)
        return {"logit_gap": {"value": None, "limit": limit}}, False
    smp = R.Sample(prompts, outputs, T.max_prompt(mix) + T.max_output(mix),
                   T.max_output(mix))
    t0 = time.monotonic()
    if control:
        gaps, ctrl = R.control_gaps(conf, seed, smp)
    else:
        gaps = R.served_gaps(conf, seed, smp)
    widest = float(np.max(gaps))
    print(f"check: {len(reqs)} requests, {int(smp.mask.sum())} served "
          f"tokens, prompts {[len(r.prompt) for r in reqs]}, prefix blocks "
          f"seeded {[(seeded or {}).get(r.rid, 0) for r in reqs]}, widest "
          f"gap {widest!r}, "
          f"mean gap {float(np.mean(gaps))!r}, tokens off the argmax "
          f"{int(np.sum(gaps > 0))}; reference "
          f"{time.monotonic() - t0:.2f}s", file=out)
    if control:
        widest = float(np.max(ctrl))
        print(f"control fp8: widest gap {widest!r}, mean gap "
              f"{float(np.mean(ctrl))!r}, tokens off the argmax "
              f"{int(np.sum(ctrl > 0))}; the control is compared in the "
              f"program's place", file=out)
    ok = bool(np.isfinite(widest) and widest <= limit)
    return {"logit_gap": {"value": widest, "limit": limit}}, ok
