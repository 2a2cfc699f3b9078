"""Mean share of the decode slots that held a decoding request, per
decode step of the window (ServeStats.slot_occupancy)."""


def read(ctx):
    s = ctx["stats"]
    if not s.decode_steps + s.verify_steps:
        return None
    return 100.0 * s.slot_occupancy
