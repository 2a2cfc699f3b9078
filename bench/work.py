"""Operations and bytes the algorithm needs, computed from the published
widths and the lengths that were served.  Nothing here reads the
program: these are the numerators of every utilization and roofline
share the benchmark reports.

Conventions: a multiply-add is 2 FLOPs; a token attends to every key up
to and including itself (causal), so a token at position p attends p + 1
keys; the LM head is counted once per sampled token; the KV cache and
the activations a kernel reads and writes are bf16 (2 bytes).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

BF16 = 2


@dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)


def layer_matmul_params(s: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, H, K, hd, f = s["d"], s["H"], s["K"], s["hd"], s["f"]
    return d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * f


def attn_flops(s: dict, keys) -> float:
    """QK^T and PV over ``keys`` attended keys (summed over tokens),
    all layers."""
    return 4.0 * s["L"] * s["H"] * s["hd"] * float(np.sum(keys))


def model_flops(s: dict, keys, sampled: int) -> float:
    """Forward FLOPs of tokens that attend ``keys`` keys each (an array,
    one entry per token), with ``sampled`` of them going through the LM
    head."""
    n = np.size(keys)
    return (2.0 * s["L"] * layer_matmul_params(s) * n + attn_flops(s, keys)
            + 2.0 * s["d"] * s["V"] * sampled)


def decode_keys(prompt_len: int, out0: int, out1: int) -> np.ndarray:
    """Keys attended by the decode steps that produced output tokens
    ``out0 .. out1 - 1`` of a request (output 0 comes from prefill;
    output j >= 1 is produced by feeding output j - 1 at position
    prompt_len + j - 1)."""
    j = np.arange(max(out0, 1), max(out1, 1))
    return prompt_len + j


def decode_attn(s: dict, keys) -> Work:
    """The paged decode kernel's least work for decode tokens attending
    ``keys`` keys each: every live K and V row read once per token, the
    query read and the output written."""
    n = np.size(keys)
    kv = 2.0 * s["K"] * s["hd"] * BF16 * float(np.sum(keys))
    qo = 2.0 * s["H"] * s["hd"] * BF16 * n
    return Work(attn_flops(s, keys), s["L"] * (kv + qo))


def prefill_keys(start: int, end: int) -> np.ndarray:
    """Keys attended by prompt rows ``start .. end - 1``."""
    return np.arange(start, end) + 1


def prefill_attn(s: dict, start: int, end: int, chunk: int) -> Work:
    """The paged prefill kernel's least work for rows ``start .. end-1``
    computed in chunks of ``chunk`` rows: per chunk, every K and V row up
    to the chunk's end read once, its queries read and outputs written."""
    byt = 0.0
    for c0 in range(start, end, chunk):
        c1 = min(c0 + chunk, end)
        byt += (2.0 * s["K"] * s["hd"] * BF16 * c1
                + 2.0 * s["H"] * s["hd"] * BF16 * (c1 - c0))
    return Work(attn_flops(s, prefill_keys(start, end)), s["L"] * byt)


def least_time(w: Work, flops_s: float, bytes_s: float) -> tuple[float, str]:
    """The roofline: the larger of compute time and memory time, and
    which of the two bounds it."""
    tc, tm = w.flops / flops_s, w.bytes / bytes_s
    return (tc, "compute") if tc >= tm else (tm, "memory")
