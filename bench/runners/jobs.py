"""Whole suffix-sigma jobs back to back through ``WaveExecutor.run``.

The mix (``bench/traffic/<mix>.json``) gives ``corpora``: how many corpora of
the configuration's profile are drawn from the seed and cycled.  On more
than one chip every wave runs as one sharded program over a data mesh of
the cell's chips (the program's own mesh-wave path).
"""
from __future__ import annotations

import sys
import time

import numpy as np

import corpus
import reference as ref
from harness import Cell


def ngram_config(cfg: dict):
    from repro.core import NGramConfig
    return NGramConfig(sigma=int(cfg["sigma"]), tau=int(cfg["tau"]),
                       vocab_size=int(cfg["vocab_size"]))


def draw_corpora(cfg: dict, mix: dict, seed: int) -> list:
    return [corpus.generate(int(cfg["n_tokens"]), corpus.profile(cfg),
                            corpus.rng_for(seed, "job", i))
            for i in range(int(mix["corpora"]))]


def reference_stats(cfg: dict, tokens) -> dict:
    return ref.count_ngrams(tokens, sigma=int(cfg["sigma"]),
                            tau=int(cfg["tau"]),
                            vocab_size=int(cfg["vocab_size"]))


def stats_dict(st) -> dict:
    return {"grams": np.asarray(st.grams), "lengths": np.asarray(st.lengths),
            "counts": np.asarray(st.counts, np.int64)}


def run(cfg: dict, mix: dict, seed: int, seconds: float, window,
        devices) -> Cell:
    """Set-up draws the corpora and runs one wave of the first, which
    compiles (or loads) the wave program.  The window runs whole jobs until
    ``seconds`` have passed and ends with the last job to finish."""
    from repro.pipeline import WaveExecutor
    mesh = None
    if len(devices) > 1:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(len(devices))
    n_tok = int(cfg["n_tokens"])
    wave = int(cfg["wave_tokens"])
    t = time.perf_counter()
    corpora = draw_corpora(cfg, mix, seed)
    t_draw = time.perf_counter() - t
    ex = WaveExecutor(ngram_config(cfg), wave_tokens=wave, mesh=mesh)
    ex.run(corpora[0][:wave])                       # warm: one whole wave
    print(f"bench: corpora {t_draw:.3f} s, warm wave "
          f"{time.perf_counter() - t - t_draw:.3f} s", file=sys.stderr)
    outputs = []
    with window:
        while True:
            i = len(outputs) % len(corpora)
            outputs.append((i, ex.run(corpora[i])))
            if window.elapsed() >= seconds:
                break
    jobs = len(outputs)
    tokens = jobs * n_tok
    waves = sum(int(st.counters["waves"]) for _, st in outputs)
    fold_rows = sum(int(st.counters["fold_rows"]) for _, st in outputs)

    def check():
        # one reference per corpus the window used, side by side (numpy's
        # sorts release the interpreter lock)
        from concurrent.futures import ThreadPoolExecutor
        used = sorted({i for i, _ in outputs})
        with ThreadPoolExecutor(len(used)) as pool:
            refs = dict(zip(used, pool.map(
                lambda i: reference_stats(cfg, corpora[i]), used)))
        bad = sum(ref.stats_mismatches(stats_dict(st), refs[i])
                  for i, st in outputs)
        return {"rows_mismatched": (bad, 0)}

    return Cell(metrics={"job_tokens_per_s": tokens / window.seconds},
                attempted=jobs, failed=0,
                facts={"runner": "jobs", "jobs": jobs, "tokens": tokens,
                       "waves": waves, "fold_rows": fold_rows,
                       "wave_tokens": wave, "lanes": int(cfg["lanes"])},
                check=check)
