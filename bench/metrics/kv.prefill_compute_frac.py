"""Share of the window's prompt tokens that prefill computed; the rest
were seeded from shared prefix blocks (ServeStats counters, counted
where the work happens)."""


def read(ctx):
    s = ctx["stats"]
    if not s.prefill_tokens_total:
        return None
    return 100.0 * s.prefill_tokens_computed / s.prefill_tokens_total
