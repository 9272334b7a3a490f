"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

``reduce_profile`` reads the trace with ``jax.profiler.ProfileData`` (nothing
but JAX) and returns, for the window ``[t0, t1)`` on the profiler's clock:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices;
* ``window_s``: ``t1 - t0``;
* ``programs``: device seconds per program (XLA module name without its
  ``(id)`` suffix), summed over devices;
* ``ops``: device seconds per operation name, summed over devices;
* ``gaps``: the longest idle intervals of the first device, each labelled
  with the host span that overlaps it most (``label_gaps``).

Device planes are those named ``/device:TPU:<n>`` (or ``/device:GPU:<n>``);
on them the ``XLA Ops`` line gives the operations and ``XLA Modules`` the
programs.  Events carry their start in ns since the profile began; host
annotations (``jax.profiler.TraceAnnotation``) sit on host planes on the same
clock, which is how the benchmark finds its window.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_MODULE_ID = re.compile(r"\(\d+\)$")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def events_by_line(pd):
    """{(plane name, line name): [(name, start_ns, end_ns), ...]}."""
    out = {}
    for plane in pd.planes:
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                   for e in line.events]
            if evs:
                out.setdefault((plane.name, line.name), []).extend(evs)
    return out


def find_annotation(lines: dict, name: str):
    """(start_ns, end_ns) of the longest host event called ``name``."""
    best = None
    for (plane, _line), evs in lines.items():
        if DEVICE_PLANE.match(plane):
            continue
        for n, s, e in evs:
            if n == name and (best is None or e - s > best[1] - best[0]):
                best = (s, e)
    return best


def union(intervals) -> list:
    """Sorted, merged, disjoint intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def clip(intervals, t0: float, t1: float) -> list:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def reduce_lines(lines: dict, t0: float, t1: float, *, n_gaps: int = 10,
                 host_spans=None) -> dict:
    """The window's device numbers from ``events_by_line`` output."""
    devices = sorted({p for p, _ in lines if DEVICE_PLANE.match(p)})
    busy, programs, ops = [], {}, {}
    first_busy = None
    for dev in devices:
        op_evs = lines.get((dev, OPS_LINE)) or lines.get((dev, MODULES_LINE), [])
        ivals = union(clip([(s, e) for _, s, e in op_evs], t0, t1))
        busy.append(sum(e - s for s, e in ivals))
        if first_busy is None:
            first_busy = ivals
        for n, s, e in lines.get((dev, OPS_LINE), []):
            d = min(e, t1) - max(s, t0)
            if d > 0:
                ops[n] = ops.get(n, 0.0) + d / 1e9
        for n, s, e in lines.get((dev, MODULES_LINE), []):
            d = min(e, t1) - max(s, t0)
            if d > 0:
                key = _MODULE_ID.sub("", n)
                programs[key] = programs.get(key, 0.0) + d / 1e9
    window_s = (t1 - t0) / 1e9
    gaps = idle_gaps(first_busy or [], t0, t1)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    gaps = gaps[:n_gaps]
    return {
        "devices": len(devices),
        "window_s": window_s,
        "busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
        "programs": programs,
        "ops": ops,
        "gaps": label_gaps(gaps, host_spans or []),
    }


def idle_gaps(busy: list, t0: float, t1: float) -> list:
    """The complement of ``busy`` (merged intervals) inside ``[t0, t1)``."""
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    return gaps


def label_gaps(gaps, host_spans) -> list:
    """[(label, seconds)]: each gap named by the host span (name, start_ns,
    end_ns) that overlaps it longest, the shorter span on a tie; "idle"
    where none does."""
    out = []
    for gs, ge in gaps:
        best, best_key = "idle", None
        for name, s, e in host_spans:
            ov = min(e, ge) - max(s, gs)
            if ov <= 0:
                continue
            key = (ov, -(e - s))
            if best_key is None or key > best_key:
                best, best_key = name, key
        out.append((best, (ge - gs) / 1e9))
    return out


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce_profile(path: str, *, window: str, spans=(),
                   n_gaps: int = 10) -> dict:
    """Reduce the trace at ``path`` over the host annotation ``window``.

    ``spans`` are host spans (name, start_s, end_s) in seconds from the
    window's start on another clock (the program's ``obs/trace.py`` spans);
    they are put on the profiler's clock through the annotation's start and
    label the idle gaps, beside the other ``bench.*`` annotations."""
    lines = events_by_line(load(path))
    win = find_annotation(lines, window)
    if win is None:
        raise ValueError(f"no host annotation {window!r} in the trace")
    a0 = win[0]
    host = [(n, a0 + s * 1e9, a0 + e * 1e9) for n, s, e in spans]
    for (plane, _line), evs in lines.items():
        if not DEVICE_PLANE.match(plane):
            host.extend((n, s, e) for n, s, e in evs
                        if n.startswith("bench.") and n != window)
    return reduce_lines(lines, win[0], win[1], n_gaps=n_gaps,
                        host_spans=host)
