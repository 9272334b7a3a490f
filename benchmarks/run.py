"""Benchmark harness: one entry per paper table/figure + kernel microbenches.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV rows (derived = the figure's headline
quantity) followed by the paper-claim validation block.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def _csv(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")


def bench_kernels() -> None:
    import jax.numpy as jnp
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    terms = jnp.asarray(np.sort(rng.integers(0, 50, (20_000, 5)), axis=0))
    toks = jnp.asarray(rng.integers(0, 300, 100_000).astype(np.int32))
    keys = jnp.asarray(rng.integers(0, 2 ** 31, 100_000).astype(np.uint32))
    valid = jnp.asarray(np.ones(100_000, bool))

    for name, fn in (
        ("kernel_lcp_boundary", lambda: ops.lcp_boundary(terms)),
        ("kernel_suffix_pack", lambda: ops.suffix_pack(toks, sigma=5,
                                                       vocab_size=300)),
        ("kernel_hash_partition", lambda: ops.hash_partition(keys, valid,
                                                             n_parts=64)),
    ):
        fn()  # compile (interpret mode on CPU)
        t0 = time.perf_counter()
        for _ in range(3):
            r = fn()
        [x.block_until_ready() for x in (r if isinstance(r, tuple) else (r,))]
        _csv(name, (time.perf_counter() - t0) / 3 * 1e6, "interpret-mode")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--waves", action="store_true",
                    help="only the wave-engine cells (wave count vs job "
                         "throughput, interleaved medians -> BENCH_waves.json)")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved reps per wave cell (--waves only)")
    ap.add_argument("--no-mesh", action="store_true",
                    help="skip the slow distributed-wave subprocess cell "
                         "(--waves only; CI smokes)")
    ap.add_argument("--gate", type=float, default=None, metavar="RATIO",
                    help="fail (exit 1) if the deepest wave sweep exceeds "
                         "RATIO x the monolithic median (--waves only)")
    ap.add_argument("--gate-mesh", type=float, default=None, metavar="RATIO",
                    help="fail (exit 1) if the fused-mesh cell exceeds RATIO "
                         "x the monolithic median OR was skipped (--waves "
                         "only; ratio is stamped into BENCH_waves.json)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    n = 20_000 if args.quick else 60_000

    if args.waves:
        from benchmarks import waves
        print("name,us_per_call,derived")
        rows = waves.run(n, reps=args.reps, mesh=not args.no_mesh,
                         gate_mesh=args.gate_mesh)
        for r in rows:
            _csv(r["name"], r["us"], r["derived"])
        failed = False
        by_name = {r["name"]: r for r in rows}
        if args.gate is not None:
            deepest = f"waves_{max(waves.WAVE_COUNTS)}"
            ratio = by_name[deepest]["us"] / by_name["waves_monolithic"]["us"]
            ok = ratio <= args.gate
            print(f"# perf gate: {deepest}/monolithic = {ratio:.2f}x "
                  f"(limit {args.gate:.2f}x) -> {'OK' if ok else 'FAIL'}")
            failed |= not ok
        if args.gate_mesh is not None:
            row = next((r for r in rows if r["name"].startswith("waves_mesh")),
                       None)
            name = row["name"] if row else waves.mesh_row_name(
                waves.MESH_DEVICES)
            if row is None or "skipped" in row:
                why = row["skipped"] if row else "row missing"
                print(f"# mesh perf gate: {name} SKIPPED ({why}) -> FAIL")
                failed = True
            else:
                ratio = row["us"] / by_name["waves_monolithic"]["us"]
                ok = ratio <= args.gate_mesh
                print(f"# mesh perf gate: {name}/monolithic = {ratio:.2f}x "
                      f"(limit {args.gate_mesh:.2f}x) -> "
                      f"{'OK' if ok else 'FAIL'}")
                failed |= not ok
        if failed:
            sys.exit(1)
        return

    from benchmarks import paper_figures as pf

    print("name,us_per_call,derived")
    t_all = time.time()

    rows3 = pf.fig3_usecases(n)
    for r in rows3:
        if not np.isfinite(r.get("wall_s", float("nan"))):
            _csv(f"fig3_{r['corpus']}_{r['case']}_{r['method']}", -1,
                 r.get("note", "dnf"))
        else:
            _csv(f"fig3_{r['corpus']}_{r['case']}_{r['method']}",
                 r["wall_s"] * 1e6, f"records={r['records']};bytes={r['bytes']}")

    rows4 = pf.fig4_tau(n)
    for r in rows4:
        _csv(f"fig4_{r['corpus']}_tau{r['tau']}_{r['method']}", r["wall_s"] * 1e6,
             f"records={r['records']};bytes={r['bytes']}")

    rows5 = pf.fig5_sigma(max(n * 2 // 3, 10_000))
    for r in rows5:
        _csv(f"fig5_{r['corpus']}_sigma{r['sigma']}_{r['method']}",
             r["wall_s"] * 1e6, f"records={r['records']};jobs={r['jobs']}")

    rows6 = pf.fig6_scale(n)
    for r in rows6:
        _csv(f"fig6_frac{int(r['frac']*100)}_{r['method']}", r["wall_s"] * 1e6,
             f"tokens={r['tokens']};records={r['records']}")

    rows7 = pf.fig7_resources(n // 2)
    for r in rows7:
        _csv(f"fig7_R{r['R']}_{r['method']}", r["wall_s"] * 1e6,
             f"ngrams={r['ngrams']}")

    bench_kernels()

    from benchmarks import serving
    for r in serving.run(max(n // 2, 10_000),
                         n_queries=4_000 if args.quick else 12_000,
                         compress=not args.quick):
        _csv(r["name"], r["us"], r["derived"])

    from benchmarks import ablations
    for r in ablations.run(max(n // 2, 10_000)):
        _csv(f"ablation_pack{int(r['pack'])}_combine{int(r['combine'])}",
             r["wall_s"] * 1e6,
             f"bytes={r['bytes']};bytes_x={r['bytes_x']};records={r['records']}")

    print("\n# paper-claim validation")
    for c in pf.validate_claims(rows4, rows5):
        print("#", c)
    print(f"# total bench time {time.time()-t_all:.1f}s")


if __name__ == "__main__":
    main()
