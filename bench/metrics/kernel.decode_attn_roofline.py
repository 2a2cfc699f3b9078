"""The paged decode-attention kernel's least time (the larger of its
FLOPs over the bf16 peak and its bytes over HBM bandwidth, for the
contexts decoded in the traced span; bench/work.py) over its device
time in the trace."""

from bench.work import least_time


def read(ctx):
    w, t, pk = ctx["work"], ctx["trace"], ctx["peaks"]
    dt = t.kernel_s.get("decode_attn") if t is not None else None
    if pk is None or not dt or not w["decode_tokens"]:
        return None
    least, bound = least_time(w["decode_attn"], pk.flops_bf16,
                              pk.hbm_bytes_s)
    print(f"kernel.decode_attn_roofline: {bound}-bound, least "
          f"{least!r}s of {dt!r}s", file=ctx["out"])
    return 100.0 * least / dt
