"""Host time of the serving executor per decode-cadence step: the time
``ServingEngine._step`` spent outside its waits on the device
(``ServeStats.step_host_s``) over the window's decode and verify steps.
A program without the counter reports nothing."""


def read(ctx):
    s = ctx["stats"]
    host = getattr(s, "step_host_s", None)
    steps = s.decode_steps + s.verify_steps
    if host is None or not steps:
        return None
    print(f"engine.host_ms_per_step: {host!r}s of host time over "
          f"{s.decode_steps} decode and {s.verify_steps} verify steps",
          file=ctx["out"])
    return 1000.0 * host / steps
