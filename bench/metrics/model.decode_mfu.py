"""Model FLOPs of the tokens decoded in the traced span (published widths,
causal attention over each token's live context, LM head once per
token; bench/work.py) over the span times the chips times the bf16 peak:
the whole decode path's share of the chip, idle time included."""


def read(ctx):
    w, t, pk = ctx["work"], ctx["trace"], ctx["peaks"]
    if pk is None or not w["decode_tokens"] or t is None:
        return None
    return 100.0 * w["decode_model_flops"] / (
        t.window_s * ctx["chips"] * pk.flops_bf16)
