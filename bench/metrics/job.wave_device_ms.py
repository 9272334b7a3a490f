"""Device milliseconds of the fused wave program per wave (trace: the
``jit_wave_fn`` XLA module)."""

PROGRAM = "wave_fn"        # the jitted wave_fn: XLA module jit_wave_fn


def device_seconds(ctx):
    progs = ctx["trace"]["programs"] if ctx["trace"] else {}
    return sum(s for name, s in progs.items() if PROGRAM in name)


def read(ctx):
    if ctx["runner"] != "jobs" or not ctx["facts"]["waves"]:
        return None
    s = device_seconds(ctx)
    return 1e3 * s / ctx["facts"]["waves"] if s > 0 else None
