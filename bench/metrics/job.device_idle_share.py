"""Share of the job window in which no operation ran on the device (trace)."""


def read(ctx):
    t = ctx["trace"]
    if ctx["runner"] != "jobs" or not t or not t["devices"] \
            or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
