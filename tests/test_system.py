"""End-to-end behaviour tests for the paper's system."""
import subprocess
import sys

import numpy as np
import pytest


def test_end_to_end_ngram_to_lm_pipeline():
    """The paper's use case (a) compressed: SUFFIX-sigma statistics -> frequency
    vocabulary -> short LM training run that reduces loss."""
    import jax
    import jax.numpy as jnp
    from repro.core import NGramConfig, run_job
    from repro.data import corpus as corpus_mod
    from repro.data.loader import LMBatchLoader
    from repro.models.transformer import (AttentionConfig, LMConfig, init_params,
                                          loss_fn)
    from repro.training.optimizer import OptimizerConfig, init_state
    from repro.training.train_loop import make_train_step

    prof = corpus_mod.CorpusProfile("e2e", 2000, 1.2, 20, 8)
    stream = corpus_mod.zipf_corpus(30_000, prof, seed=0)
    stats = run_job(stream, NGramConfig(sigma=3, tau=5, vocab_size=prof.vocab_size))
    assert len(stats) > 50
    uni = sorted(((g[0], c) for g, c in stats.to_dict().items() if len(g) == 1),
                 key=lambda kv: -kv[1])
    remap = np.zeros(prof.vocab_size + 1, np.int32)
    for new_id, (old, _) in enumerate(uni, start=2):
        remap[old] = new_id
    encoded = np.where(remap[stream] == 0, 1, remap[stream])
    cfg = LMConfig("e2e", 2, 64, len(uni) + 2, 128,
                   AttentionConfig("gqa", 4, 2, 16), dtype=jnp.float32,
                   remat=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = init_state(params)
    step = jax.jit(make_train_step(lambda p, b: loss_fn(p, b, cfg),
                                   OptimizerConfig(peak_lr=1e-3, warmup_steps=2,
                                                   decay_steps=40)))
    loader = LMBatchLoader(encoded, 32, 4, seed=0)
    losses = []
    for i in range(40):
        batch = {k: jnp.asarray(v) for k, v in loader.batch_at(i).items()}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def _run_cli(args):
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    return subprocess.run([sys.executable, "-m", "repro.launch.ngram"] + args,
                          capture_output=True, text=True, timeout=560, env=env,
                          cwd="/root/repo")


def test_ngram_cli_runs():
    r = _run_cli(["--method", "suffix_sigma", "--sigma", "4", "--tau", "5",
                  "--tokens", "20000", "--split-docs"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "n-grams in" in r.stdout and "counters" in r.stdout


def test_ngram_cli_splits_a_wide_wave_job():
    """The analytics setting through the CLI's wave path (sigma 100 on the
    ClueWeb profile: 50 lanes) runs as the head/tail split, by itself."""
    import ast
    r = _run_cli(["--method", "suffix_sigma", "--sigma", "100", "--tau", "5",
                  "--tokens", "40000", "--profile", "cw",
                  "--wave-tokens", "10000"])
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("counters:")][0]
    counters = ast.literal_eval(line.split(":", 1)[1].strip())
    assert counters["waves"] >= 3
    assert counters["head_dict_rows"] > 0 and counters["tail_positions"] > 0


def test_methods_cli_agree():
    """All four methods via the CLI produce the same number of frequent n-grams."""
    counts = {}
    for m in ("suffix_sigma", "naive", "apriori_scan", "apriori_index"):
        r = _run_cli(["--method", m, "--sigma", "3", "--tau", "8",
                      "--tokens", "8000"])
        assert r.returncode == 0, (m, r.stderr[-1500:])
        line = [l for l in r.stdout.splitlines() if "n-grams in" in l][0]
        counts[m] = int(line.split("n-grams in")[0].split(":")[-1].strip())
    assert len(set(counts.values())) == 1, counts


def test_imports_start_no_backend():
    """Importing the package claims no device: a parent that imports repro
    must still leave the chip to whoever creates the first array."""
    import os
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    code = ("import repro, repro.core, repro.pipeline, repro.index, "
            "repro.serve, repro.kernels.ops, repro.launch.ngram\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "print('OK')")
    env = dict(os.environ, PYTHONPATH=str(root / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]


def test_compile_cache_location(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise one fixed,
    git-ignored directory inside the checkout."""
    from pathlib import Path

    import jax
    from repro.launch import compile_cache
    root = Path(__file__).resolve().parents[1]
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path   # fixed
        ignored = (root / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
