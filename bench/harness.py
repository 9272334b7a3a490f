"""The benchmark harness: finds a cell's parts by name, runs one window,
checks it against the plain reference and assembles the result line.

Layout, relative to the directory that holds ``BENCHMARK.json`` (``root``)
and the benchmark's directory ``root/<paths[0]>`` (``bench``):

* a configuration is the ``file`` its ``configs`` entry names;
* a traffic mix is ``bench/traffic/<traffic>.json``; its ``runner`` key names
  ``bench/runners/<runner>.py``, whose ``run(cfg, mix, seed, seconds, window,
  devices)`` drives the window and returns a :class:`Cell`;
* a per-layer metric is ``bench/metrics/<name>.py``, whose ``read(ctx)``
  returns a number or ``None`` when it finds nothing to read;
* the device peaks are ``bench/peaks.json``, keyed by ``device_kind``.

Adding a configuration, a mix or a metric adds files and entries; no file
here changes.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

import trace_reduce

WINDOW = "bench.window"


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


@dataclass
class Cell:
    """What a runner's window measured."""
    metrics: dict                      # end-to-end name -> value
    attempted: int
    failed: int
    facts: dict                        # what the per-layer readers read
    check: object                      # () -> {name: (value, limit)}


# ------------------------------------------------------------- spec lookup
def load_spec(root: Path) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no {path}")
    return json.loads(path.read_text())


def bench_dir(root: Path, spec: dict) -> Path:
    return Path(root) / spec["paths"][0]


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_parts(root: Path, spec: dict, workload: str):
    """(cell entry, configuration dict, mix dict) of one workload."""
    cell = find(spec["workloads"], workload, "workload")
    centry = find(spec["configs"], cell["config"], "configuration")
    cfg_path = Path(root) / centry["file"]
    mix_path = bench_dir(root, spec) / "traffic" / f"{cell['traffic']}.json"
    for p in (cfg_path, mix_path):
        if not p.is_file():
            raise SpecError(f"missing {p}")
    return cell, json.loads(cfg_path.read_text()), json.loads(
        mix_path.read_text())


def cell_metrics(spec: dict, workload: str):
    """(end-to-end entries, per-layer entries) that this cell reports."""
    def applies(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m for m in spec["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    per = [m for m in spec["per_layer"]
           if (workload in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    return e2e, per


def load_module(root: Path, spec: dict, kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    path = bench_dir(root, spec) / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, spec: dict, name: str):
    return load_module(root, spec, "metrics", name).read


def load_runner(root: Path, spec: dict, name: str):
    return load_module(root, spec, "runners", name).run


def peaks_for(root: Path, spec: dict, kind: str) -> dict:
    table = json.loads((bench_dir(root, spec) / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


# ----------------------------------------------------------------- device
def require_chips(chips: int):
    """The device gate: a TPU backend with at least ``chips`` chips, or exit
    non-zero with no result."""
    import jax
    devices = jax.devices()
    if jax.default_backend() != "tpu" or devices[0].platform != "tpu":
        print(f"bench: no TPU (backend {jax.default_backend()!r}); "
              "refusing to measure another platform", file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} TPU chips, found "
              f"{len(devices)}", file=sys.stderr)
        sys.exit(2)
    return devices


def enable_cache() -> str:
    """The program's persistent compile cache, holding every program."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Backend compile requests (persistent-cache loads fire it too)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# ----------------------------------------------------------------- window
class Window:
    """The measured window: host clock, compile count and, traced, the
    profiler, the program's spans and counters."""

    def __init__(self, *, trace: bool, compiles: CompileCounter | None):
        self.trace = trace
        self.compiles = compiles
        self.t0 = self.t1 = None
        self.log_dir = None
        self.tracer = None
        self.registry = None
        self.span_list = []

    def __enter__(self):
        if self.trace:
            import jax
            from repro.obs import metrics as obs_metrics
            from repro.obs import trace as obs_trace
            self.registry = obs_metrics.MetricsRegistry()
            obs_metrics.set_registry(self.registry)
            self.tracer = obs_trace.enable_tracing()
            self.log_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(WINDOW)
            self._ann.__enter__()
        self.compiles_before = self.compiles.n if self.compiles else 0
        self.t0_ns = time.perf_counter_ns()
        self.t0 = self.t0_ns / 1e9
        return self

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def __exit__(self, *exc):
        self.t1_ns = time.perf_counter_ns()
        self.t1 = self.t1_ns / 1e9
        self.window_compiles = (self.compiles.n - self.compiles_before
                                if self.compiles else 0)
        if self.trace:
            import jax
            from repro.obs import metrics as obs_metrics
            from repro.obs import trace as obs_trace
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            obs_trace.disable_tracing()
            obs_metrics.set_registry(None)
        self.span_list = self.spans()
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def spans(self) -> list:
        """Program spans as (name, start_s, end_s), relative to the window."""
        if self.tracer is None:
            return []
        origin = self.tracer._t_origin
        out = []
        for e in self.tracer.export()["traceEvents"]:
            s = (origin + e["ts"] * 1e3 - self.t0_ns) / 1e9
            out.append((e["name"], s, s + e["dur"] / 1e6))
        return out

    def reduce_trace(self) -> dict:
        """The profiler trace of the window, with the program's spans."""
        return trace_reduce.reduce_profile(
            trace_reduce.find_xplane(self.log_dir), window=WINDOW,
            spans=self.span_list)

    def cleanup(self) -> None:
        if self.log_dir:
            shutil.rmtree(self.log_dir, ignore_errors=True)


# ------------------------------------------------------------------- run
def device_info(devices) -> dict:
    dev = devices[0]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(root: Path, workload: str, *, seed: int, seconds: float,
             trace: bool, t_start: float, devices=None,
             compiles: CompileCounter | None = None) -> dict:
    """One run of one cell; returns the result object (``checks`` last).

    ``setup_s`` runs from ``t_start`` to the window's first timed call."""
    import jax
    root = Path(root)
    spec = load_spec(root)
    cell, cfg, mix = cell_parts(root, spec, workload)
    e2e, per_layer = cell_metrics(spec, workload)
    devices = devices or jax.devices()[:int(cell["chips"])]
    if len(devices) != int(cell["chips"]):
        raise SpecError(f"{workload} asks for {cell['chips']} chips, "
                        f"{len(devices)} given")
    peaks = (peaks_for(root, spec, devices[0].device_kind)
             if devices[0].platform == "tpu" else None)
    drive = load_runner(root, spec, mix["runner"])
    window = Window(trace=trace, compiles=compiles)
    try:
        result = drive(cfg, mix, seed, seconds, window, devices)
        device = device_info(devices)
        red = window.reduce_trace() if trace else None
    finally:
        window.cleanup()
    values = dict(result.metrics, setup_s=window.t0 - t_start)
    print(f"bench: set-up {values['setup_s']:.3f} s, window "
          f"{window.seconds:.3f} s, {result.attempted} attempted",
          file=sys.stderr)
    t_check = time.perf_counter()
    try:
        checks = result.check()
    except Exception as e:                         # the check itself failed
        print(f"bench: reference check raised {e!r}", file=sys.stderr)
        checks = {"check_raised": (1, 0)}
    print(f"bench: reference check {time.perf_counter() - t_check:.3f} s",
          file=sys.stderr)
    correct = bool(checks) and all(v <= lim for v, lim in checks.values())
    out = {"correct": correct, "attempted": int(result.attempted),
           "failed": int(result.failed)}
    if trace:
        ctx = {"runner": mix["runner"], "facts": result.facts, "trace": red,
               "spans": window.span_list, "window_s": window.seconds,
               "peaks": peaks}
        metrics = {}
        for m in per_layer:
            v = load_reader(root, spec, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {
            "device_ops": trace_reduce.top(red["ops"]),
            "idle_gaps": [[n, s] for n, s in red["gaps"]]}
    else:
        out["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                      "unit": m["unit"]} for m in e2e}
        out["device"] = device
    out["window_compiles"] = window.window_compiles
    out["window_s"] = window.seconds
    out["checks"] = {n: {"value": v, "limit": lim}
                     for n, (v, lim) in checks.items()}
    return out


def report(out: dict) -> None:
    """Checks as the last lines of stderr, the result as the last line of
    stdout."""
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    sys.stderr.flush()


def main(argv, *, t_start: float, root: Path) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(root)
    cell = find(spec["workloads"], args.workload, "workload")
    devices = require_chips(int(cell["chips"]))[:int(cell["chips"])]
    print(f"bench: compile cache {enable_cache()}", file=sys.stderr)
    compiles = CompileCounter()
    out = run_cell(root, args.workload, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t_start=t_start, devices=devices,
                   compiles=compiles)
    report(out)
    return 0
