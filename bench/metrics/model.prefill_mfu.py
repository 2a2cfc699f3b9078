"""Model FLOPs of the prompt rows computed in the traced span over the
device time of the prefill step programs (those holding the paged
prefill kernel) at the bf16 peak: the prefill step's share of the chip
while it runs."""


def read(ctx):
    w, t, pk = ctx["work"], ctx["trace"], ctx["peaks"]
    busy = t.program_s.get("prefill") if t is not None else None
    if pk is None or not busy or not w["prefill_model_flops"]:
        return None
    return 100.0 * w["prefill_model_flops"] / (busy * pk.flops_bf16)
