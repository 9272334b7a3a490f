"""Segment rows fed through the host fold per corpus token (the jobs'
``fold_rows`` counter, an exact count)."""


def read(ctx):
    f = ctx["facts"]
    if ctx["runner"] != "jobs" or not f["tokens"]:
        return None
    return f["fold_rows"] / f["tokens"]
