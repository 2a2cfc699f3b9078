"""Share of the traced span in which no operation ran on the device,
averaged over the cell's chips (bench/trace_reduce.py)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * t.idle_share
