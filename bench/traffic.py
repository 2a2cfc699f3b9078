"""One general traffic generator, driven by a mix's data file.

A mix is ``bench/traffic/<mix>.json``; a cell may add
``bench/cells/<cell>.json`` whose ``traffic`` object overrides keys of
the mix (a rate fixed for that cell, say).  Keys:

- ``loop``: ``open`` (arrivals on a schedule: ``rate_per_s``, Poisson)
  or ``closed`` (``clients`` that each send the next request when the
  last one finishes).
- ``prompt_tokens`` / ``output_tokens``: a length distribution,
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
  ``{"dist": "uniform", "min", "max"}``.
- ``system_prompts``: ``{"count", "tokens", "zipf_s"}`` shared prefixes
  put before the prompt (chosen by a Zipf law over ``count``).
- ``stagger_first_round``: closed loop; the first request of client i
  gets (i + 1) / clients of its output length, so completions spread.
- ``template_seed``: the sizes, prefix choices and arrival times are
  drawn once from this seed.  The run's seed puts the sizes and prefix
  choices in another order over the same arrival times (in an open loop,
  among the requests due in the window, and apart among those after it)
  and draws every token id, so every seed runs the same work.
- ``check``: how many finished requests the reference reads.

Every generated request is greedy.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


@dataclass
class Item:
    rid: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    at: float = 0.0             # open loop: scheduled arrival (s from open)


def load_mix(mix: str, cell: str | None = None) -> dict:
    params = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    if cell is not None:
        over = BENCH / "cells" / f"{cell}.json"
        if over.exists():
            params.update(json.loads(over.read_text()).get("traffic", {}))
    return params


def draw(rng: np.random.Generator, d: dict, n: int) -> np.ndarray:
    if d["dist"] == "lognormal":
        x = np.round(d["median"] * np.exp(d["sigma"] * rng.standard_normal(n)))
    elif d["dist"] == "uniform":
        x = rng.integers(d["min"], d["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return np.clip(x, d["min"], d["max"]).astype(np.int64)


def _zipf(rng, count: int, s: float, n: int) -> np.ndarray:
    w = 1.0 / np.arange(1, count + 1) ** s
    return rng.choice(count, size=n, p=w / w.sum())


def max_prompt(mix: dict) -> int:
    n = mix["prompt_tokens"]["max"]
    if "system_prompts" in mix:
        n += mix["system_prompts"]["tokens"]
    return n


def max_output(mix: dict) -> int:
    return mix["output_tokens"]["max"]


def count_for(mix: dict, seconds: float, drain_s: float) -> int:
    """Requests the template holds: enough for the window and the drain."""
    if mix["loop"] == "open":
        return int(math.ceil(mix["rate_per_s"] * (seconds + drain_s) * 1.3)) + 8
    return mix["clients"] * 64


def _perm(rng, n: int, window: int) -> np.ndarray:
    """A permutation of 0..n-1 that keeps the first ``window`` indices,
    and the one after them, among themselves."""
    head = rng.permutation(window)
    tail = window + 1 + rng.permutation(max(0, n - window - 1))
    return np.concatenate([head, np.arange(window, min(window + 1, n)),
                           tail]).astype(np.int64)


def generate(mix: dict, seed: int, n: int, vocab: int,
             window_s: float = 0.0) -> list[Item]:
    """``n`` requests of ``mix`` for run seed ``seed``.  In an open loop
    the requests due in the first ``window_s`` seconds are the same set
    of sizes for every seed, in another order, at the same times."""
    t = np.random.default_rng(mix.get("template_seed", 0))
    rng = np.random.default_rng(int(seed))
    loop = mix["loop"]
    plen = draw(t, mix["prompt_tokens"], n)
    olen = draw(t, mix["output_tokens"], n)
    gaps = (t.exponential(1.0 / mix["rate_per_s"], n) if loop == "open"
            else np.zeros(n))
    sp = mix.get("system_prompts")
    sys_ix = _zipf(t, sp["count"], sp["zipf_s"], n) if sp else np.zeros(n, int)
    window = int(np.sum(np.cumsum(gaps) < window_s)) if loop == "open" else 0
    perm = _perm(rng, n, window) if window else rng.permutation(n)
    plen, olen, sys_ix = plen[perm], olen[perm], sys_ix[perm]
    if loop == "closed" and mix.get("stagger_first_round"):
        c = mix["clients"]
        frac = (np.arange(c) + 1) / c
        olen[:c] = np.maximum(1, np.round(olen[:c] * frac)).astype(np.int64)
    prefixes = ([rng.integers(0, vocab, sp["tokens"], dtype=np.int32)
                 for _ in range(sp["count"])] if sp else [])
    at = np.cumsum(gaps) if loop == "open" else np.zeros(n)
    items = []
    for i in range(n):
        body = rng.integers(0, vocab, int(plen[i]), dtype=np.int32)
        prompt = (np.concatenate([prefixes[sys_ix[i]], body]) if sp else body)
        items.append(Item(rid=i, prompt=prompt, max_new=int(olen[i]),
                          at=float(at[i])))
    return items
