"""n-gram statistics job launcher -- the paper's CLI.

    PYTHONPATH=src python -m repro.launch.ngram --method suffix_sigma \
        --sigma 5 --tau 10 --tokens 500000 --profile nyt

Runs the selected method on a synthetic corpus with the paper's measurement
counters (wallclock / records / bytes), optionally with maximality/closedness
post-filtering and time-series aggregation.  ``--wave-tokens`` streams the
job out of core through the wave engine; ``--devices N`` runs it distributed
on an N-way host mesh -- combined, every wave's stage pipeline shards over
the mesh (the distributed-waves path).
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--method", default="suffix_sigma",
                    choices=["suffix_sigma", "naive", "apriori_scan",
                             "apriori_index"])
    ap.add_argument("--sigma", type=int, default=5)
    ap.add_argument("--tau", type=int, default=10)
    ap.add_argument("--tokens", type=int, default=200_000)
    ap.add_argument("--profile", default="nyt", choices=["nyt", "cw"])
    ap.add_argument("--split-docs", action="store_true")
    ap.add_argument("--filter", default=None, choices=[None, "max", "closed"])
    ap.add_argument("--series", action="store_true",
                    help="aggregate per-year n-gram time series (SSVI-B)")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--wave-tokens", type=int, default=None,
                    help="out-of-core: run the job in fixed-size token waves "
                         "(repro.pipeline.WaveExecutor); output is "
                         "bit-identical to the monolithic run")
    ap.add_argument("--no-overlap", action="store_true",
                    help="serialize the per-wave fold with wave dispatch "
                         "instead of overlapping it on the fold thread "
                         "(debugging / single-thread environments)")
    ap.add_argument("--devices", type=int, default=0,
                    help=">1: run distributed on an N-way host mesh (sets "
                         "XLA_FLAGS; with --wave-tokens, shards every wave)")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="export a Chrome/Perfetto trace_event JSON of the run")
    ap.add_argument("--metrics", default=None, metavar="FILE",
                    help="append a metrics snapshot (JSONL) and print the "
                         "summary table")
    args = ap.parse_args()
    if args.devices > 1:
        from repro.launch.mesh import pin_host_device_count
        pin_host_device_count(args.devices)   # before the first backend init
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.core import NGramConfig, extensions_filter, run_job
    from repro.data import corpus as corpus_mod
    from repro.obs import metrics as obs_metrics
    from repro.obs import report as obs_report

    finish_obs = obs_report.setup(args.trace, args.metrics)

    mesh = None
    if args.devices > 1:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(args.devices)

    prof = corpus_mod.PROFILES[args.profile]
    if args.series:
        tokens, years = corpus_mod.zipf_corpus(args.tokens, prof, seed=0,
                                               duplicate_frac=0.02, with_years=True)
    else:
        tokens = corpus_mod.zipf_corpus(args.tokens, prof, seed=0,
                                        duplicate_frac=0.02)
        years = None
    if args.split_docs:
        tokens, removed = corpus_mod.split_at_infrequent(tokens, args.tau,
                                                         prof.vocab_size)
        print(f"document splitting removed {removed} infrequent term occurrences")

    cfg = NGramConfig(sigma=args.sigma, tau=args.tau, vocab_size=prof.vocab_size,
                      method=args.method, n_buckets=21 if args.series else 0)
    t0 = time.time()
    if args.wave_tokens is not None:
        from repro.pipeline import WaveExecutor
        if args.series:
            raise SystemExit("--wave-tokens does not support --series "
                             "(bucketed counts need a single-wave job)")
        stats = WaveExecutor(cfg, wave_tokens=args.wave_tokens,
                             overlap=not args.no_overlap,
                             mesh=mesh).run(tokens)
    else:
        kw = {"bucket_ids": years} if args.series else {}
        stats = run_job(tokens, cfg, mesh=mesh, **kw)
    dt = time.time() - t0
    if args.filter:
        stats = extensions_filter(stats, args.filter)
    obs_metrics.get_registry().merge_job_counters(stats.counters)
    print(f"method={args.method} sigma={args.sigma} tau={args.tau} "
          f"tokens={args.tokens}: {len(stats)} n-grams in {dt:.2f}s")
    print("counters:", {k: int(v) for k, v in stats.counters.items()})
    d = stats.to_dict()
    top = sorted(d.items(), key=lambda kv: -kv[1])[: args.top]
    for g, c in top:
        print(f"  cf={c:8d}  {g}")
    finish_obs({"driver": "ngram", "method": args.method,
                "tokens": args.tokens, "wall_s": dt})


if __name__ == "__main__":
    main()
