"""Wave-engine benchmark: wave count vs job throughput, fold policy, mesh waves.

    PYTHONPATH=src python -m benchmarks.run --waves

Measures the out-of-core tax: the same SUFFIX-sigma job over the same corpus
at several wave sizes (1 wave == the monolithic shape), reps *interleaved*
across all wave counts (the repo's interleaved-median protocol: host-load
transients hit every cell equally) and reduced by medians.  On top of the
wave-count sweep:

  * **accumulator cells** -- the same job at ``ACC_WAVES`` waves under both
    fold policies (``pairwise`` = every wave into one running segment,
    ``tiered`` = the LSM rung stack), recording wall time *and* the measured
    merge work (``fold_rows``: segment rows fed through ``merge_segments``);
  * **streaming cell** -- waves straight into the generational index;
  * **distributed cell** -- the same job with every wave sharded over a
    mesh: on the CPU an 8-way emulated host mesh, run in a subprocess (the
    device-count XLA flag must precede backend init); on an accelerator, in
    this process, over every device it holds (a chip belongs to one
    process, so no child could reach it).

Every run appends to ``BENCH_waves.json`` so regressions are diffable in
review.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

BENCH_JSON = "BENCH_waves.json"
WAVE_COUNTS = (1, 2, 4, 8)
ACC_WAVES = 16          # >= 16 waves: where the tiered fold-work win shows
MESH_DEVICES = 8        # emulated host devices of the CPU mesh cell
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mesh_cell(n_tokens: int, n_devices: int, reps: int) -> dict:
    """Median wall time of the mesh-wave job over this process's first
    ``n_devices`` devices, one wave per device."""
    from repro.core import NGramConfig
    from repro.data import corpus as corpus_mod
    from repro.launch.mesh import make_data_mesh
    from repro.pipeline import WaveExecutor
    mesh = make_data_mesh(n_devices)
    prof = corpus_mod.NYT
    tokens = corpus_mod.zipf_corpus(n_tokens, prof, seed=0,
                                    duplicate_frac=0.02)
    cfg = NGramConfig(sigma=5, tau=4, vocab_size=prof.vocab_size)
    wave = -(-len(tokens) // n_devices)
    ex = WaveExecutor(cfg, wave_tokens=wave, mesh=mesh)
    ex.run(tokens)                               # compile + cache warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        ex.run(tokens)
        ts.append(time.perf_counter() - t0)
    return {"us": float(np.median(ts) * 1e6), "n_tokens": len(tokens),
            "devices": n_devices}


_MESH_CELL = """
import json
from benchmarks.waves import mesh_cell
print(json.dumps(mesh_cell({n_tokens}, {devices}, {reps})))
"""


def _mesh_cell_emulated(n_tokens: int, reps: int) -> dict:
    """Time distributed waves in a subprocess (forced host device count).

    Never silently drops the cell: any failure comes back as
    ``{"skipped": reason}``, which lands in the benchmark record as an
    explicit skipped row -- ``BENCH_waves.json`` must never read as
    "covered" when the mesh cell actually died.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={MESH_DEVICES}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT, os.path.join(ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    code = _MESH_CELL.format(devices=MESH_DEVICES, n_tokens=n_tokens,
                             reps=reps)
    try:
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=1200, env=env, cwd=ROOT)
    except subprocess.TimeoutExpired:
        print("mesh wave cell timed out (skipped)", file=sys.stderr)
        return {"skipped": "subprocess timeout (1200s)"}
    if r.returncode != 0:
        print(f"mesh wave cell failed (skipped):\n{r.stderr[-2000:]}",
              file=sys.stderr)
        tail = (r.stderr.strip().splitlines() or ["no stderr"])[-1]
        return {"skipped": f"subprocess exit {r.returncode}: {tail[:300]}"}
    return json.loads(r.stdout.strip().splitlines()[-1])


def mesh_row_name(n_devices: int) -> str:
    return f"waves_mesh{n_devices}_{n_devices}"


def run(n_tokens: int = 60_000, *, reps: int = 3, mesh: bool = True,
        gate_mesh: float | None = None) -> list[dict]:
    from repro.core import NGramConfig, run_job
    from repro.data import corpus as corpus_mod
    from repro.pipeline import WaveExecutor

    prof = corpus_mod.NYT
    tokens = corpus_mod.zipf_corpus(n_tokens, prof, seed=0, duplicate_frac=0.02)
    n_tokens = len(tokens)              # zipf_corpus appends duplicated docs
    cfg = NGramConfig(sigma=5, tau=4, vocab_size=prof.vocab_size)

    cells: dict[object, callable] = {"mono": lambda: run_job(tokens, cfg)}
    for nw in WAVE_COUNTS:
        wave = -(-n_tokens // nw)
        cells[nw] = (lambda w=wave: WaveExecutor(cfg, wave_tokens=w)
                     .run(tokens))
    # fold-policy cells: same job, ACC_WAVES waves, both accumulators
    acc_wave = -(-n_tokens // ACC_WAVES)
    for strat in ("pairwise", "tiered"):
        cells[f"acc_{strat}"] = (
            lambda s=strat: WaveExecutor(cfg, wave_tokens=acc_wave,
                                         accumulator=s).run(tokens))
    lat: dict[object, list[float]] = {k: [] for k in cells}
    last: dict[object, object] = {}
    for k, fn in cells.items():
        last[k] = fn()                         # compile + cache warm
    for _ in range(reps):                      # interleaved: one rep per cell
        for k, fn in cells.items():
            t0 = time.perf_counter()
            last[k] = fn()
            lat[k].append(time.perf_counter() - t0)

    rows = []
    mono_us = float(np.median(lat["mono"]) * 1e6)
    rows.append({"name": "waves_monolithic", "us": mono_us,
                 "derived": f"tok_s={n_tokens / (mono_us / 1e6):.0f}"})
    for nw in WAVE_COUNTS:
        us = float(np.median(lat[nw]) * 1e6)
        rows.append({
            "name": f"waves_{nw}",
            "us": us,
            "derived": (f"tok_s={n_tokens / (us / 1e6):.0f};"
                        f"vs_mono={us / mono_us:.2f}x"),
        })
    for strat in ("pairwise", "tiered"):
        key = f"acc_{strat}"
        us = float(np.median(lat[key]) * 1e6)
        fold = int(last[key].counters["fold_rows"])
        rows.append({
            "name": f"waves_acc_{strat}_{ACC_WAVES}",
            "us": us,
            "derived": (f"fold_rows={fold};"
                        f"tok_s={n_tokens / (us / 1e6):.0f}"),
        })
    fp = int(last["acc_pairwise"].counters["fold_rows"])
    ft = int(last["acc_tiered"].counters["fold_rows"])
    rows.append({"name": f"waves_fold_work_win_{ACC_WAVES}",
                 "us": 0.0,
                 "derived": f"pairwise/tiered={fp / max(ft, 1):.2f}x"})

    # streaming cell: waves straight into the generational index
    cfg1 = NGramConfig(sigma=5, tau=1, vocab_size=prof.vocab_size)
    wave = -(-n_tokens // WAVE_COUNTS[-1])
    ex = WaveExecutor(cfg1, wave_tokens=wave)
    ex.run_streaming(tokens[: 2 * wave])       # warm
    t_s = []
    for _ in range(max(reps - 1, 1)):
        t0 = time.perf_counter()
        gen, _ = ex.run_streaming(tokens)
        t_s.append(time.perf_counter() - t0)
    us = float(np.median(t_s) * 1e6)
    rows.append({"name": f"waves_streaming_{WAVE_COUNTS[-1]}", "us": us,
                 "derived": (f"tok_s={n_tokens / (us / 1e6):.0f};"
                             f"segments={gen.n_segments}")})

    # distributed cell: every wave sharded over a mesh.  On the CPU it runs
    # on an emulated host mesh in a subprocess, and a skipped/failed cell
    # still lands as an explicit row -- the record must say WHY the mesh
    # number is missing, never just omit it.  On an accelerator it runs in
    # this process over the devices it holds, and a failure raises.
    import jax
    emulated = jax.default_backend() == "cpu"
    n_dev = MESH_DEVICES if emulated else jax.device_count()
    mesh_name = mesh_row_name(n_dev)
    if not mesh:
        mesh_row = {"skipped": "disabled (--no-mesh)"}
    elif emulated:
        mesh_row = _mesh_cell_emulated(n_tokens, max(reps - 1, 1))
    elif n_dev < 2:
        raise SystemExit(f"the mesh wave cell needs 2 or more devices, this "
                         f"process holds {n_dev}; pass --no-mesh")
    else:
        mesh_row = mesh_cell(n_tokens, n_dev, max(reps - 1, 1))
    gate_mesh_stamp = None
    if "skipped" in mesh_row:
        rows.append({"name": mesh_name, "us": 0.0,
                     "skipped": mesh_row["skipped"],
                     "derived": f"skipped={mesh_row['skipped']}"})
        if gate_mesh is not None:
            gate_mesh_stamp = {"limit": gate_mesh, "ratio": None,
                               "ok": False, "skipped": mesh_row["skipped"]}
    else:
        us = mesh_row["us"]
        ratio = us / mono_us
        rows.append({
            "name": mesh_name,
            "us": us,
            "derived": (f"tok_s={mesh_row['n_tokens'] / (us / 1e6):.0f};"
                        f"vs_mono={ratio:.2f}x"),
        })
        if gate_mesh is not None:
            gate_mesh_stamp = {"limit": gate_mesh, "ratio": round(ratio, 4),
                               "ok": ratio <= gate_mesh}

    # tracing-overhead cell: the same waves_N job with the tracer live.
    # Acceptance gates: overhead < 1.05x the untraced median, and >= 90% of
    # the root span's wall time attributed to named child spans.
    from repro.obs import trace as obs_trace
    nw = WAVE_COUNTS[-1]
    wave = -(-n_tokens // nw)
    t_tr = []
    tracer = None
    try:
        for _ in range(reps):
            tracer = obs_trace.enable_tracing()
            t0 = time.perf_counter()
            WaveExecutor(cfg, wave_tokens=wave).run(tokens)
            t_tr.append(time.perf_counter() - t0)
            obs_trace.disable_tracing()
    finally:
        obs_trace.disable_tracing()
    us = float(np.median(t_tr) * 1e6)
    base = float(np.median(lat[nw]) * 1e6)
    cov = obs_trace.span_coverage(tracer.export(), "wave.run")
    rows.append({"name": f"waves_traced_{nw}", "us": us,
                 "derived": (f"overhead={us / base:.3f}x;"
                             f"span_cov={cov:.3f}")})

    # per-run metric snapshot: the job counters of the cells review diffs
    # most (monolithic vs the deepest wave sweep), typed and env-stamped
    from repro.obs import metrics as obs_metrics
    from repro.obs import report as obs_report
    reg = obs_metrics.MetricsRegistry()
    reg.merge_job_counters(last["mono"].counters, prefix="mono.")
    reg.merge_job_counters(last[nw].counters, prefix=f"waves{nw}.")

    try:
        with open(BENCH_JSON) as f:
            prev = json.load(f).get("runs", [])
    except (FileNotFoundError, json.JSONDecodeError):
        prev = []
    record = {"n_tokens": n_tokens, "reps": reps, "rows": rows,
              "env": obs_report.environment_metadata(),
              "metrics": reg.snapshot()}
    if gate_mesh_stamp is not None:
        record["gate_mesh"] = gate_mesh_stamp
    prev.append(record)
    with open(BENCH_JSON, "w") as f:
        json.dump({"runs": prev}, f, indent=2)
    return rows
