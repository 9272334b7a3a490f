"""Device milliseconds of a split job's tail program per job (trace: the
``jit_tail_fn`` XLA module, summed over the job's waves)."""

PROGRAM = "tail_fn"        # the jitted tail_fn: XLA module jit_tail_fn


def read(ctx):
    if ctx["runner"] != "jobs" or not ctx["facts"]["jobs"]:
        return None
    progs = ctx["trace"]["programs"] if ctx["trace"] else {}
    s = sum(v for name, v in progs.items() if PROGRAM in name)
    return 1e3 * s / ctx["facts"]["jobs"] if s > 0 else None
