"""What every architecture's work counts share: the record of operations
and bytes, the roofline, the keys that served tokens attend, and what a
traced span served.  The counts themselves live with each architecture
(``bench/archs/``), computed from its published widths and the served
lengths; nothing here reads the program.

A token attends to every key up to and including itself (causal), so a
token at position p attends p + 1 keys.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np


@dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)


@dataclass
class Served:
    """What a traced span served, for the kernels' work functions."""
    decode_keys: np.ndarray             # keys attended, per decoded token
    prefill: list[tuple[int, int]]      # (first row computed, prompt length)
    chunk: int                          # prompt rows per prefill chunk
    prefill_scale: float                # rows the span computed / rows above

    def per_prompt(self, work: Callable[[int, int], Work]) -> Work:
        """``work(start, end)`` summed over the prompts prefilled, scaled
        to the rows the span computed."""
        w = Work()
        for start, end in self.prefill:
            w = w + work(start, end)
        return Work(w.flops * self.prefill_scale, w.bytes * self.prefill_scale)


def decode_keys(prompt_len: int, out0: int, out1: int) -> np.ndarray:
    """Keys attended by the decode steps that produced output tokens
    ``out0 .. out1 - 1`` of a request (output 0 comes from prefill;
    output j >= 1 is produced by feeding output j - 1 at position
    prompt_len + j - 1)."""
    j = np.arange(max(out0, 1), max(out1, 1))
    return prompt_len + j


def prefill_keys(start: int, end: int) -> np.ndarray:
    """Keys attended by prompt rows ``start .. end - 1``."""
    return np.arange(start, end) + 1


def least_time(w: Work, flops_s: float, bytes_s: float) -> tuple[float, str]:
    """The roofline: the larger of compute time and memory time, and
    which of the two bounds it."""
    tc, tm = w.flops / flops_s, w.bytes / bytes_s
    return (tc, "compute") if tc >= tm else (tm, "memory")
