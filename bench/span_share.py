"""Share of the measured window covered by some of the program's spans.

The readers of the job's host-path metrics (``bench/metrics/job.*_share.py``
that read program spans) take the union of the spans they name, over every
thread, clipped to the window: time in which at least one such span was
open counts once.
"""
import trace_reduce


def covered_s(spans, names, window_s: float):
    """Seconds of ``[0, window_s)`` inside at least one span (name, start_s,
    end_s) whose name is in ``names``; ``None`` when no such span was
    recorded at all (a program without those spans)."""
    picked = [(s, e) for n, s, e in spans if n in names]
    if not picked:
        return None
    return sum(e - s for s, e in trace_reduce.union(
        trace_reduce.clip(picked, 0.0, window_s)))


def share(ctx, names):
    """Percent of the job window covered by the spans ``names``; ``None``
    outside the ``jobs`` runner or where the program records none of them."""
    if ctx["runner"] != "jobs" or ctx["window_s"] <= 0:
        return None
    c = covered_s(ctx["spans"], names, ctx["window_s"])
    return None if c is None else 100.0 * c / ctx["window_s"]
