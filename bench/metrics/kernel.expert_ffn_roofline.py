"""The held experts' grouped SwiGLU kernel (``moe_expert_ffn``): its least
time over its device time in the trace.  The least work comes from the
program's window counters (``ServeStats.moe_rows``: token-choices the
held experts computed; ``moe_expert_hits``: held experts that computed a
row, per grouped call; the ``_prefill`` pair: the prefill and verify
programs' part of each), scaled to the traced span part by part: the
decode programs' by the span's share of the window's decoded tokens, the
prefill programs' by its share of the window's prefilled rows (a decode
step makes one grouped call per layer, a 512-row chunk two, so one share
for both would misplace the weights' reads).  Then
    bytes = hits x 3 d fe x 2 (each touched expert's weights read once)
          + rows x 2 d x 2     (its rows read and written)
    FLOPs = rows x 6 d fe
and the least time is the larger of FLOPs over the bf16 peak and bytes
over HBM bandwidth.  A program without the counters, or a window in
which the kernel did not run, reports nothing."""

from bench.work import Work, least_time

BF16 = 2


def read(ctx):
    s, t, pk, w = ctx["stats"], ctx["trace"], ctx["peaks"], ctx["work"]
    dt = t.kernel_s.get("expert_ffn") if t is not None else None
    rows = getattr(s, "moe_rows", 0)
    if pk is None or not dt or not rows or not s.tokens:
        return None
    hits = s.moe_expert_hits
    rows_p, hits_p = s.moe_rows_prefill, s.moe_expert_hits_prefill
    dec = w["decode_tokens"] / s.tokens
    pre = (w["prefill_rows"] / s.prefill_tokens_computed
           if s.prefill_tokens_computed else 0.0)
    span_rows = (rows - rows_p) * dec + rows_p * pre
    span_hits = (hits - hits_p) * dec + hits_p * pre
    d, fe = ctx["spec"]["d"], ctx["spec"]["fe"]
    work = Work(6.0 * d * fe * span_rows,
                3.0 * d * fe * BF16 * span_hits + 2.0 * d * BF16 * span_rows)
    least, bound = least_time(work, pk.flops_bf16, pk.hbm_bytes_s)
    print(f"kernel.expert_ffn_roofline: {bound}-bound, least {least!r}s of "
          f"{dt!r}s; window rows {rows} hits {hits} (prefill {rows_p}, "
          f"{hits_p}); span shares decode {dec!r} prefill {pre!r}",
          file=ctx["out"])
    return 100.0 * least / dt
