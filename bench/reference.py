"""The plain reference: a Qwen2/Qwen3 dense decoder in float32 ``jax.numpy``,
written from the published equations and importing nothing of the
program.  It makes its weights again from the seed (``bench/weights.py``)
and upcasts the served bf16 values, so the only difference from the
served model is arithmetic: float32 at ``Precision.HIGHEST`` everywhere,
no cache, no kernel, no chunking, no batching across requests.

Per layer (pre-norm, as ``Qwen2DecoderLayer``/``Qwen3DecoderLayer``):
    h = RMSNorm(x); q,k,v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
    [Qwen3] q,k = RMSNorm_head(q), RMSNorm_head(k)
    q,k = RoPE(q), RoPE(k)  (rotate-half form, theta from the config)
    x = x + softmax(q k^T / sqrt(hd) + causal) v Wo
    x = x + W_down(silu(h2 W_gate) * h2 W_up),  h2 = RMSNorm(x)
logits = RMSNorm(x) E^T (tied) or RMSNorm(x) W_head (untied).

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

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
QBLOCK = 512          # attention query rows per block
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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (S, n, hd); rotate-half RoPE at absolute positions ``pos``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(s, quant, w, x):
    """One decoder layer over one whole sequence x: (S, d) float32."""
    S, d = x.shape
    H, K, hd = s["H"], s["K"], s["hd"]
    G = H // K
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.arange(S, dtype=jnp.int32)
    h = _rms(x, w["ln1.scale"], s["eps"])
    q = _mm(h, w["attn.wq"].reshape(d, H * hd), quant).reshape(S, H, hd)
    k = _mm(h, w["attn.wk"].reshape(d, K * hd), quant).reshape(S, K, hd)
    v = _mm(h, w["attn.wv"].reshape(d, K * hd), quant).reshape(S, K, hd)
    if s["qkv_bias"]:
        q, k, v = q + w["attn.bq"], k + w["attn.bk"], v + w["attn.bv"]
    if s["qk_norm"]:
        q = _rms(q, w["attn.q_norm"], s["eps"])
        k = _rms(k, w["attn.k_norm"], s["eps"])
    q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
    qg = q.reshape(S // QBLOCK, QBLOCK, K, G, hd)

    def attend(args):
        qb, q0 = args                                   # (QB, K, G, hd)
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) / np.sqrt(hd)
        qpos = q0 + jnp.arange(QBLOCK)
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    starts = jnp.arange(S // QBLOCK, dtype=jnp.int32) * QBLOCK
    att = jax.lax.map(attend, (qg, starts)).reshape(S, H * hd)
    x = x + _mm(att, w["attn.wo"].reshape(H * hd, d), quant)
    h2 = _rms(x, w["ln2.scale"], s["eps"])
    g = _mm(h2, w["mlp.w_gate"], quant)
    u = _mm(h2, w["mlp.w_up"], quant)
    return x + _mm(jax.nn.silu(g) * u, w["mlp.w_down"], quant)


@functools.lru_cache(maxsize=None)
def _programs(s_items: tuple, quant):
    """Jitted pieces for one configuration (keyed by its sizes); the
    sizes are static, the key and the data are arguments."""
    s = dict(s_items)
    dt = jnp.dtype(s["dtype"])

    @jax.jit
    def embed(key, toks):
        tok = W.global_weights(s, key, dt)["embed.tok"]
        return tok[toks].astype(jnp.float32)

    @jax.jit
    def layer(key, l, X):
        w = W.layer_weights(s, key, l, dt)
        return jax.lax.map(lambda x: _layer(s, quant, w, x), X)

    @jax.jit
    def final(key, X, read):
        g = W.global_weights(s, key, dt)
        h = jnp.take_along_axis(X, read[..., None], axis=1)   # (B, n, d)
        return _rms(h, g["ln_f.scale"].astype(jnp.float32), s["eps"])

    @jax.jit
    def head(key, h, toks):
        """h: (N, d) -> per row (max logit, logit of toks, argmax)."""
        g = W.global_weights(s, key, dt)
        wh = (g["embed.tok"].T if s["tie"] else g["embed.lm_head"])
        wh = wh.astype(jnp.float32)
        N = h.shape[0]

        def rows(args):
            hb, tb = args
            lg = _mm(hb, wh, quant)
            return (lg.max(-1), jnp.take_along_axis(lg, tb[:, None], 1)[:, 0],
                    lg.argmax(-1).astype(jnp.int32))

        hb = h.reshape(N // HEAD_ROWS, HEAD_ROWS, -1)
        tb = toks.reshape(N // HEAD_ROWS, HEAD_ROWS)
        return tuple(o.reshape(N) for o in jax.lax.map(rows, (hb, tb)))

    return embed, layer, final, head


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
    s = W.spec_of(conf)
    embed, layer, final, _ = _programs(tuple(sorted(s.items())), quant)
    key = W.root_key(seed)
    X = embed(key, jnp.asarray(sample.tokens))
    for l in range(s["L"]):
        X = layer(key, jnp.int32(l), X)
    h = final(key, X, jnp.asarray(sample.read))
    return h.reshape(-1, h.shape[-1])


def head_stats(conf: dict, seed: int, h, toks, quant=None):
    """(max logit, logit of ``toks``, argmax) per row of ``h``."""
    s = W.spec_of(conf)
    head = _programs(tuple(sorted(s.items())), quant)[3]
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
