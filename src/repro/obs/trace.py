"""Span tracer: nested wall-clock spans -> Chrome/Perfetto ``trace_event`` JSON.

Usage (instrumented code never checks whether tracing is on):

    from repro.obs import trace

    with trace.span("wave.submit") as sp:
        if sp:                       # real span: attach args
            sp.set(wave=i)
        ...

``trace.span`` returns the shared :data:`NULL_SPAN` singleton while tracing is
disabled -- no allocation, no clock read, no profiler annotation, no device
sync -- so the disabled path is a true no-op (regression-tested by
``tests/test_obs.py``).  Enabled, spans nest through a plain stack and record
host ``perf_counter_ns`` intervals.  Each enabled span also enters a
``jax.profiler.TraceAnnotation`` of the same name for its lifetime, so while
the JAX profiler runs every span sits in its ``.xplane.pb`` on the
profiler's own clock, beside the device ops.  A span never blocks on the
device: where the device wait matters, the instrumented code wraps the wait
in a span of its own (``wave.collect.wait``).

Export is the Chrome ``trace_event`` "complete event" (``ph: "X"``) format,
loadable directly in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``;
nesting is inferred from timestamps within a track, so the JSON stays flat.
"""
from __future__ import annotations

import json
import threading
import time

__all__ = ["NULL_SPAN", "Span", "Tracer", "enable_tracing", "disable_tracing",
           "get_tracer", "span", "span_coverage"]


class _NullSpan:
    """Shared do-nothing span: the disabled path's zero-cost stand-in."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self) -> bool:
        return False

    def set(self, **args) -> None:
        pass


NULL_SPAN = _NullSpan()


class Span:
    """One live span, inside a profiler annotation of the same name."""

    __slots__ = ("_tracer", "name", "args", "t0", "t1", "tid", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict | None):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.t0 = 0
        self.t1 = 0
        self.tid = threading.get_ident() & 0xFFFF
        self._ann = None

    def __bool__(self) -> bool:
        return True

    def set(self, **args) -> None:
        """Attach key/value args (rendered in the Perfetto detail pane)."""
        if self.args is None:
            self.args = {}
        self.args.update(args)

    def __enter__(self) -> "Span":
        self._ann = self._tracer._profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        self._tracer._finish(self)
        return False


class Tracer:
    """Collects finished spans; exports Chrome ``trace_event`` JSON."""

    def __init__(self):
        from jax import profiler   # the annotations' home; enabled path only
        self._profiler = profiler
        self.events: list[dict] = []
        self._t_origin = time.perf_counter_ns()

    def span(self, name: str, **args) -> Span:
        return Span(self, name, args or None)

    def _finish(self, sp: Span) -> None:
        ev = {
            "name": sp.name,
            "ph": "X",
            "cat": "repro",
            "ts": (sp.t0 - self._t_origin) / 1e3,    # us, Chrome's unit
            "dur": (sp.t1 - sp.t0) / 1e3,
            "pid": 0,
            "tid": sp.tid,
        }
        if sp.args:
            ev["args"] = {k: _jsonable(v) for k, v in sp.args.items()}
        self.events.append(ev)

    def export(self) -> dict:
        """The Perfetto-loadable trace object (sorted by start time)."""
        return {
            "traceEvents": sorted(self.events, key=lambda e: e["ts"]),
            "displayTimeUnit": "ms",
        }

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.export(), f, indent=1)


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    try:
        return int(v)          # numpy / device scalars
    except (TypeError, ValueError):
        return str(v)


# --------------------------------------------------------------------------- #
# module-level current tracer (the instrumented paths' single indirection)
# --------------------------------------------------------------------------- #

_TRACER: Tracer | None = None


def enable_tracing(tracer: Tracer | None = None) -> Tracer:
    """Install (and return) the active tracer; idempotent with an argument."""
    global _TRACER
    _TRACER = tracer if tracer is not None else Tracer()
    return _TRACER


def disable_tracing() -> None:
    global _TRACER
    _TRACER = None


def get_tracer() -> Tracer | None:
    return _TRACER


def span(name: str):
    """A span under the active tracer, or :data:`NULL_SPAN` when disabled.

    The disabled call is the whole hot-path cost: one global read, one
    ``is None`` check, and the shared singleton back -- no allocation.
    """
    if _TRACER is None:
        return NULL_SPAN
    return _TRACER.span(name)


# --------------------------------------------------------------------------- #
# trace analysis (acceptance checks, benchmarks)
# --------------------------------------------------------------------------- #

def span_coverage(trace_obj: dict, root_name: str,
                  child_prefixes: tuple[str, ...] | None = None) -> float:
    """Fraction of the root span's wall time covered by named child spans.

    The per-wave-tax attribution check: merge every non-root span's
    ``[ts, ts+dur)`` interval (optionally filtered to ``child_prefixes``),
    clip to the root span, and return covered/total.  A trace where this is
    low has anonymous wall time no span accounts for.
    """
    events = trace_obj["traceEvents"]
    roots = [e for e in events if e["name"] == root_name]
    if not roots:
        raise ValueError(f"no span named {root_name!r} in trace")
    root = max(roots, key=lambda e: e["dur"])
    r0, r1 = root["ts"], root["ts"] + root["dur"]
    if r1 <= r0:
        return 0.0
    ivals = []
    for e in events:
        if e is root or e["name"] == root_name:
            continue
        if child_prefixes is not None and \
                not e["name"].startswith(child_prefixes):
            continue
        lo = max(e["ts"], r0)
        hi = min(e["ts"] + e["dur"], r1)
        if hi > lo:
            ivals.append((lo, hi))
    ivals.sort()
    covered = 0.0
    cur_lo, cur_hi = None, None
    for lo, hi in ivals:
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                covered += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        covered += cur_hi - cur_lo
    return covered / (r1 - r0)
