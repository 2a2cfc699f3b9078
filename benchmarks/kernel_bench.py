"""Kernel micro-benchmarks: correctness deltas + analytic VMEM/MXU roofline
per block configuration (no TPU on this host, so the report is structural:
working-set bytes vs VMEM, FLOPs per HBM byte vs the v5e ridge point).

``--smoke`` runs only the Pallas-vs-oracle correctness checks (interpret
mode on CPU, compiled on TPU) and exits non-zero on any mismatch — the
tier-1 CI gate against kernel regressions.
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

import repro.kernels.conv2d.ops        # noqa: F401  (register_kernel)
import repro.kernels.decode_attention.ops  # noqa: F401
import repro.kernels.flash_attention.ops   # noqa: F401
import repro.kernels.matmul.ops        # noqa: F401
import repro.kernels.prefill_attention.ops  # noqa: F401
import repro.kernels.ssm_scan.ops      # noqa: F401
from repro.kernels.conv2d.kernel import conv2d
from repro.kernels.conv2d.ref import conv2d_ref
from repro.kernels.decode_attention.kernel import (decode_attention,
                                                   paged_decode_attention)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref)
from repro.kernels.dispatch import kernel_table
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.matmul.kernel import matmul
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.prefill_attention.kernel import paged_prefill_attention
from repro.kernels.prefill_attention.ref import paged_prefill_attention_ref
from repro.kernels.ssm_scan.kernel import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.roofline.hw import TPU_V5E

from benchmarks.common import save_artifact

RIDGE = TPU_V5E.peak_flops_bf16 / TPU_V5E.hbm_bandwidth   # flops/byte

# registered kernel name -> the err key(s) its smoke cases produce (a tuple
# lists every gated shape family); smoke() fails if a kernel is registered
# in the dispatch table without a case here
COVERAGE = {
    "matmul": "matmul_err",
    "flash_attention": "flash_err",
    "decode_attention": "decode_err",
    "paged_decode_attention": "paged_decode_err",
    "paged_prefill_attention": ("paged_prefill_err",
                                "paged_prefill_verify_err",
                                "paged_prefill_verify_int8_err"),
    "ssm_scan": "ssm_err",
    "conv2d": "conv2d_err",
}


def _gemm_stats(m, n, k, bm, bn, bk, dtype_bytes=2):
    vmem = (bm * bk + bk * bn) * dtype_bytes + bm * bn * 4
    flops = 2 * m * n * k
    hbm = (m * k + k * n) * dtype_bytes * (n // bn if False else 1) + \
        m * n * dtype_bytes
    # per-tile K-stream model: x tile read n/bn times, y tile read m/bm times
    hbm = (m * k * (n // bn) + k * n * (m // bm)) * dtype_bytes \
        + m * n * dtype_bytes
    return {"vmem_bytes": vmem, "flops": flops, "hbm_bytes": hbm,
            "intensity": flops / hbm, "ridge": RIDGE,
            "compute_bound": flops / hbm > RIDGE}


def _kernel_errs(interpret: bool = True) -> dict:
    """Pallas-vs-oracle max abs error for every registered kernel family."""
    out = {}
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(ks[0], (256, 256), jnp.bfloat16)
    y = jax.random.normal(ks[1], (256, 256), jnp.bfloat16)
    ref = matmul_ref(x, y).astype(jnp.float32)
    err = float(jnp.abs(
        matmul(x, y, bm=128, bn=128, bk=128, interpret=interpret).astype(jnp.float32)
        - ref).max())
    out["matmul_err"] = err / float(jnp.abs(ref).max())   # relative (bf16)

    q = jax.random.normal(ks[2], (1, 256, 4, 64))
    k = jax.random.normal(ks[3], (1, 256, 2, 64))
    v = jax.random.normal(ks[4], (1, 256, 2, 64))
    out["flash_err"] = float(jnp.abs(
        flash_attention(q, k, v, bq=128, bkv=128, interpret=interpret)
        - flash_attention_ref(q, k, v)).max())

    qd = jax.random.normal(ks[5], (2, 4, 64))
    lengths = jnp.array([100, 200], jnp.int32)
    out["decode_err"] = float(jnp.abs(
        decode_attention(qd, k, v, lengths, bkv=128, interpret=interpret)
        - decode_attention_ref(qd, k, v, lengths)).max())

    # paged decode: pool + shuffled block tables + ragged lengths
    bs, mb = 16, 4
    kp = jax.random.normal(ks[6], (1 + 2 * mb, 2, bs, 64))
    vp = jax.random.normal(ks[7], (1 + 2 * mb, 2, bs, 64))
    rng = np.random.default_rng(0)
    tables = jnp.asarray(1 + rng.permutation(2 * mb).reshape(2, mb)
                         .astype(np.int32))
    plens = jnp.array([37, 64], jnp.int32)
    out["paged_decode_err"] = float(jnp.abs(
        paged_decode_attention(qd, kp, vp, tables, plens,
                               interpret=interpret)
        - paged_decode_attention_ref(qd, kp, vp, tables, plens)).max())
    from repro.models.transformer import quantize_kv
    kq, ksc = quantize_kv(kp)
    vq, vsc = quantize_kv(vp)
    out["paged_decode_int8_err"] = float(jnp.abs(
        paged_decode_attention(qd, kq, vq, tables, plens, k_scale=ksc,
                               v_scale=vsc, interpret=interpret)
        - paged_decode_attention_ref(qd, kq, vq, tables, plens,
                                     k_scale=ksc, v_scale=vsc)).max())

    # paged prefill: a multi-row chunk offset into seeded pool KV (causal
    # against absolute positions), same pool/tables as the decode case
    qc = jax.random.normal(ks[5], (2, 8, 4, 64))
    q_start = jnp.array([21, 48], jnp.int32)      # seeded rows before chunk
    clens = q_start + 8
    out["paged_prefill_err"] = float(jnp.abs(
        paged_prefill_attention(qc, kp, vp, tables, q_start, clens,
                                interpret=interpret)
        - paged_prefill_attention_ref(qc, kp, vp, tables, q_start,
                                      clens)).max())
    out["paged_prefill_int8_err"] = float(jnp.abs(
        paged_prefill_attention(qc, kq, vq, tables, q_start, clens,
                                k_scale=ksc, v_scale=vsc,
                                interpret=interpret)
        - paged_prefill_attention_ref(qc, kq, vq, tables, q_start, clens,
                                      k_scale=ksc, v_scale=vsc)).max())

    # verify-shaped paged prefill (speculative decoding): a short k+1-token
    # chunk starting mid-sequence against a short visible block table —
    # the shape `_verify_step` issues every speculative round
    qv = jax.random.normal(ks[5], (2, 4, 4, 64))
    vtables = tables[:, :2]                       # mb=2: 32 visible rows
    vq_start = jnp.array([9, 27], jnp.int32)      # mid-block / near-edge
    vlens = vq_start + 4
    out["paged_prefill_verify_err"] = float(jnp.abs(
        paged_prefill_attention(qv, kp, vp, vtables, vq_start, vlens,
                                interpret=interpret)
        - paged_prefill_attention_ref(qv, kp, vp, vtables, vq_start,
                                      vlens)).max())
    out["paged_prefill_verify_int8_err"] = float(jnp.abs(
        paged_prefill_attention(qv, kq, vq, vtables, vq_start, vlens,
                                k_scale=ksc, v_scale=vsc,
                                interpret=interpret)
        - paged_prefill_attention_ref(qv, kq, vq, vtables, vq_start, vlens,
                                      k_scale=ksc, v_scale=vsc)).max())

    ld = -jax.nn.softplus(jax.random.normal(ks[6], (1, 256, 4)))
    lg = 0.1 * jax.random.normal(ks[7], (1, 256, 4))
    qs = jax.random.normal(ks[2], (1, 256, 4, 16))
    ks_ = jax.random.normal(ks[3], (1, 256, 4, 16))
    vs = jax.random.normal(ks[4], (1, 256, 4, 16))
    out["ssm_err"] = float(jnp.abs(
        ssm_scan(qs, ks_, vs, ld, lg, chunk=64, interpret=interpret)
        - ssm_scan_ref(qs, ks_, vs, ld, lg, chunk=64)).max())

    xc = jax.random.normal(ks[0], (1, 12, 12, 4))
    wc = jax.random.normal(ks[1], (3, 3, 4, 8)) * 0.1
    bc = jax.random.normal(ks[2], (8,)) * 0.1
    out["conv2d_err"] = float(jnp.abs(
        conv2d(xc, wc, bc, stride=1, bc=8, interpret=interpret)
        - conv2d_ref(xc, wc, bc, stride=1)).max())
    return out


def smoke(verbose: bool = True) -> dict:
    """CI gate: every kernel in the dispatch table vs its oracle;
    interpret-mode fallback off-TPU so the check runs on CPU runners too.
    A kernel registered without a COVERAGE case fails the gate outright."""
    uncovered = set(kernel_table()) - set(COVERAGE)
    if uncovered:
        print(f"FAIL: registered kernels without a smoke case: "
              f"{sorted(uncovered)}", file=sys.stderr)
        sys.exit(1)
    interpret = jax.default_backend() != "tpu"
    errs = _kernel_errs(interpret=interpret)
    needed = {key for v in COVERAGE.values()
              for key in (v if isinstance(v, tuple) else (v,))}
    stale = needed - set(errs)
    if stale:       # a COVERAGE entry whose case was deleted/renamed
        print(f"FAIL: smoke cases missing from _kernel_errs: "
              f"{sorted(stale)}", file=sys.stderr)
        sys.exit(1)
    if verbose:
        mode = "interpret" if interpret else "compiled"
        print(f"kernel smoke ({mode}):",
              {k: f"{v:.2e}" for k, v in errs.items()})
    bad = {k: v for k, v in errs.items() if not v < 1e-2}
    if bad:
        print("FAIL: kernel regressions:", bad, file=sys.stderr)
        sys.exit(1)
    if verbose:
        print("kernel smoke PASS")
    return errs


def run(verbose: bool = True) -> dict:
    out = _kernel_errs(interpret=True)
    # structural roofline for the production GEMM tiling
    out["gemm_512"] = _gemm_stats(8192, 8192, 8192, 512, 512, 512)
    out["gemm_256"] = _gemm_stats(8192, 8192, 8192, 256, 256, 256)
    if verbose:
        print("kernels errs:", {k: v for k, v in out.items()
                                if k.endswith("_err")})
        print("gemm tiling 512:", {k: round(v, 2) if isinstance(v, float)
                                   else v for k, v in out["gemm_512"].items()})
    save_artifact("kernel_bench", out)
    assert max(v for k, v in out.items() if k.endswith("_err")) < 1e-2
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="correctness-only CI gate (no artifact)")
    if ap.parse_args().smoke:
        smoke()
    else:
        run()
