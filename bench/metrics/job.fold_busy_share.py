"""Share of the job window covered by the host fold: the union of the
program's ``wave.fold`` and ``wave.finalize`` spans (program spans)."""

SPANS = ("wave.fold", "wave.finalize")


def read(ctx):
    if ctx["runner"] != "jobs" or ctx["window_s"] <= 0:
        return None
    ivals = sorted((max(s, 0.0), min(e, ctx["window_s"]))
                   for n, s, e in ctx["spans"] if n in SPANS)
    covered, cur_s, cur_e = 0.0, None, None
    for s, e in ivals:
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                covered += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        covered += cur_e - cur_s
    if covered == 0.0:
        return None
    return 100.0 * covered / ctx["window_s"]
