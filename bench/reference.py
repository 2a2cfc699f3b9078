"""The plain reference: the configuration's model in float32
``jax.numpy``, written from the published equations and importing nothing
of the program.  Its equations come from the configuration's architecture
module (``bench/archs/``); this file runs them.  It makes its weights again
from the seed (``bench/weights.py``) and upcasts the served bf16 values, so
the only difference from the served model is arithmetic: float32 at
``Precision.HIGHEST`` everywhere, no cache, no kernel, no chunking, no
batching across requests.

It runs layer by layer over a block of whole sequences, after the
served model has been freed, so it fits beside nothing else.  With
``quant="fp8"`` every projection's inputs (activations per row, weights
per output column) are rounded to float8 e4m3 first: the control that
must come out not correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import archs
from bench import weights as W
from bench.archs import HI, QBLOCK

HEAD_ROWS = 256       # LM-head rows per block
FP8_MAX = 448.0       # largest finite float8_e4m3fn


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (the contracted axis), and back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    sc = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / sc).astype(jnp.float8_e4m3fn).astype(jnp.float32) * sc


def _mm(a, w, quant):
    """(S, i) @ (i, o) in float32 at HIGHEST, optionally through fp8."""
    if quant == "fp8":
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=HI)


@functools.lru_cache(maxsize=None)
def _programs(arch, s_items: tuple, quant):
    """Jitted pieces for one configuration (keyed by its module and its
    sizes); the sizes are static, the key and the data are arguments.
    ``layers`` holds one program per layer group, taking the layer's
    index in its group."""
    s = dict(s_items)
    dt = jnp.dtype(s["dtype"])
    mm = functools.partial(_mm, quant=quant)

    @jax.jit
    def embed(key, toks):
        return arch.embed(s, W.global_weights(arch, s, key, dt), toks)

    def layer_program(group):
        @jax.jit
        def layer(key, l, X):
            w = W.layer_weights(arch, s, key, group, l, dt)
            return jax.lax.map(lambda x: arch.layer(s, group, w, x, mm), X)
        return layer

    layers = {g: layer_program(g) for g, _, _ in arch.groups(s)}

    @jax.jit
    def final(key, X, read):
        g = W.global_weights(arch, s, key, dt)
        h = jnp.take_along_axis(X, read[..., None], axis=1)   # (B, n, d)
        return arch.final(s, g, h)

    @jax.jit
    def head(key, h, toks):
        """h: (N, d) -> per row (max logit, logit of toks, argmax)."""
        wh = arch.head(s, W.global_weights(arch, s, key, dt))
        N = h.shape[0]

        def rows(args):
            hb, tb = args
            lg = mm(hb, wh)
            return (lg.max(-1), jnp.take_along_axis(lg, tb[:, None], 1)[:, 0],
                    lg.argmax(-1).astype(jnp.int32))

        hb = h.reshape(N // HEAD_ROWS, HEAD_ROWS, -1)
        tb = toks.reshape(N // HEAD_ROWS, HEAD_ROWS)
        return tuple(o.reshape(N) for o in jax.lax.map(rows, (hb, tb)))

    return embed, layers, final, head


def _arch_programs(conf: dict, quant):
    arch = archs.of(conf)
    s = arch.spec_of(conf)
    return arch, s, _programs(arch, tuple(sorted(s.items())), quant)


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


class Sample:
    """Requests laid out for the reference: whole sequences (prompt plus
    every served token but the last, which no later position reads) in a
    (B, S) block, and the positions whose logits produced each served
    token."""

    def __init__(self, prompts, outputs, seq_len: int, n_out: int):
        B = len(prompts)
        self.S = _pad_to(seq_len, QBLOCK)
        self.n = _pad_to(max(n_out, 1), HEAD_ROWS)
        self.tokens = np.zeros((B, self.S), np.int32)
        self.read = np.zeros((B, self.n), np.int32)
        self.served = np.zeros((B, self.n), np.int32)
        self.mask = np.zeros((B, self.n), bool)
        for i, (p, o) in enumerate(zip(prompts, outputs)):
            seq = np.concatenate([np.asarray(p, np.int32),
                                  np.asarray(o[:-1], np.int32)])
            if len(seq) > self.S or len(o) > self.n:
                raise ValueError(f"request {i} ({len(p)}+{len(o)} tokens) "
                                 f"outgrows the reference block "
                                 f"({self.S}, {self.n})")
            self.tokens[i, :len(seq)] = seq
            self.read[i, :len(o)] = len(p) - 1 + np.arange(len(o))
            self.served[i, :len(o)] = o
            self.mask[i, :len(o)] = True


def final_hidden(conf: dict, seed: int, sample: Sample, quant=None):
    """Normed final hidden states at the read positions: (B * n, d)."""
    arch, s, (embed, layers, final, _) = _arch_programs(conf, quant)
    key = W.root_key(seed)
    X = embed(key, jnp.asarray(sample.tokens))
    for group, n, _ in arch.groups(s):
        for l in range(n):
            X = layers[group](key, jnp.int32(l), X)
    h = final(key, X, jnp.asarray(sample.read))
    return h.reshape(-1, h.shape[-1])


def head_stats(conf: dict, seed: int, h, toks, quant=None):
    """(max logit, logit of ``toks``, argmax) per row of ``h``."""
    *_, head = _arch_programs(conf, quant)[2]
    out = head(W.root_key(seed), h, jnp.asarray(toks, jnp.int32).reshape(-1))
    return tuple(np.asarray(o) for o in out)


def served_gaps(conf: dict, seed: int, sample: Sample) -> np.ndarray:
    """For every served token: how far its reference logit lies below the
    reference's best at that position (0 where it is the argmax)."""
    h = final_hidden(conf, seed, sample)
    mx, at, _ = head_stats(conf, seed, h, sample.served)
    return (mx - at).reshape(sample.served.shape)[sample.mask]


def control_gaps(conf: dict, seed: int, sample: Sample) -> tuple:
    """The control: the reference computed through fp8, teacher-forced on
    the same tokens.  Returns (the program's gaps, the control's gaps):
    at each position, how far below the float32 reference's best lies
    the token that the fp8 model puts first."""
    h = final_hidden(conf, seed, sample)
    mx, at, _ = head_stats(conf, seed, h, sample.served)
    hq = final_hidden(conf, seed, sample, quant="fp8")
    _, _, top = head_stats(conf, seed, hq, np.zeros(len(hq), np.int32),
                           quant="fp8")
    _, at_top, _ = head_stats(conf, seed, h, top)
    m = sample.mask.reshape(-1)
    return (mx - at)[m], (mx - at_top)[m]
