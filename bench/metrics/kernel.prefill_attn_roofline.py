"""The paged prefill-attention kernel's least time (the larger of its
FLOPs over the bf16 peak and its bytes over HBM bandwidth, for the
prompt rows computed in the traced span; bench/work.py) over its device
time in the trace."""

from bench.work import least_time


def read(ctx):
    w, t, pk = ctx["work"], ctx["trace"], ctx["peaks"]
    dt = t.kernel_s.get("prefill_attn") if t is not None else None
    if pk is None or not dt or not w["prefill_rows"]:
        return None
    least, bound = least_time(w["prefill_attn"], pk.flops_bf16,
                              pk.hbm_bytes_s)
    print(f"kernel.prefill_attn_roofline: {bound}-bound, least "
          f"{least!r}s of {dt!r}s", file=ctx["out"])
    return 100.0 * least / dt
