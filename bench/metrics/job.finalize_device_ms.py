"""Device milliseconds of the blocked finalize fold per job (trace: the
``jit__merge_block`` XLA module, summed over the job's blocks)."""

PROGRAM = "_merge_block"   # the jitted _merge_block: XLA module jit__merge_block


def read(ctx):
    if ctx["runner"] != "jobs" or not ctx["facts"]["jobs"]:
        return None
    progs = ctx["trace"]["programs"] if ctx["trace"] else {}
    s = sum(v for name, v in progs.items() if PROGRAM in name)
    return 1e3 * s / ctx["facts"]["jobs"] if s > 0 else None
