"""Multi-device behaviour, run in subprocesses so the main pytest process keeps its
single CPU device (the dry-run is the only place that pins 512)."""
import json
import subprocess
import sys
import textwrap

import pytest

PY = sys.executable


def run_with_devices(code: str, n: int = 8, timeout: int = 560) -> str:
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={n}",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    import os
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    env["PYTHONPATH"] = "src"
    r = subprocess.run([PY, "-c", textwrap.dedent(code)], capture_output=True,
                       text=True, timeout=timeout, env=env, cwd="/root/repo")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_distributed_methods_match_oracle():
    out = run_with_devices("""
        import numpy as np, jax
        from repro.core import run_job, oracle
        from repro.core.stats import NGramConfig
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(0)
        toks = rng.integers(0, 60, 900)
        exp = oracle.ngram_counts(toks, 4, 2)
        for m in ("suffix_sigma", "naive", "apriori_scan", "apriori_index"):
            cfg = NGramConfig(sigma=4, tau=2, vocab_size=59, method=m)
            got = run_job(toks, cfg, mesh=mesh).to_dict()
            assert got == exp, m
        print("OK")
    """)
    assert "OK" in out


def test_shuffle_overflow_retry_and_counters():
    out = run_with_devices("""
        import numpy as np, jax
        from repro.core import suffix_sigma, oracle
        from repro.core.stats import NGramConfig
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(1)
        # heavy skew: tiny vocab concentrates lead terms -> forces capacity retry
        # (combine=False: the map-side combiner would dedupe the tiny-vocab
        # suffixes down to a handful of records and dodge the overflow)
        toks = rng.integers(0, 3, 4000)
        cfg = NGramConfig(sigma=3, tau=1, vocab_size=2, capacity_factor=0.05,
                          combine=False)
        st = suffix_sigma.run(toks, cfg, mesh=mesh)
        assert st.to_dict() == oracle.ngram_counts(toks, 3, 1)
        assert st.counters["retries"] >= 1     # capacity doubled at least once
        assert st.counters["overflow"] == 0    # final run clean
        print("OK retries=", st.counters["retries"])
    """)
    assert "OK" in out


def test_checkpoint_resharding_across_meshes():
    out = run_with_devices("""
        import tempfile, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.training.checkpoint import CheckpointManager
        m8 = jax.make_mesh((8,), ("data",),
                           axis_types=(jax.sharding.AxisType.Auto,))
        m24 = jax.make_mesh((2, 4), ("data", "model"),
                            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        x = jnp.arange(64 * 16, dtype=jnp.float32).reshape(64, 16)
        xs = jax.device_put(x, NamedSharding(m8, P("data", None)))
        with tempfile.TemporaryDirectory() as d:
            ck = CheckpointManager(d, async_save=False)
            ck.save(1, {"w": xs})
            # restore onto a DIFFERENT mesh/sharding (elastic scaling path)
            tgt = jax.ShapeDtypeStruct((64, 16), jnp.float32)
            restored, _ = ck.restore(
                1, {"w": tgt},
                shardings={"w": NamedSharding(m24, P("model", "data"))})
            np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(x))
            assert restored["w"].sharding.mesh.shape == {"data": 2, "model": 4}
        print("OK")
    """)
    assert "OK" in out


def test_compressed_psum_unbiased():
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.training.compression import compressed_psum_exact_scale
        mesh = jax.make_mesh((4,), ("pod",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = jnp.asarray(np.random.default_rng(0).standard_normal((4, 256)),
                        jnp.float32)

        def f(gs, key):
            return compressed_psum_exact_scale({"g": gs[0]}, "pod", key)["g"]

        fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("pod", None), P()),
                                   out_specs=P(), check_vma=False))
        # average over many rounding keys -> unbiased estimate of the true mean
        acc = 0
        n = 50
        for i in range(n):
            out = fn(g, jax.random.PRNGKey(i))
            acc = acc + np.asarray(out)
        approx = acc / n
        true = np.asarray(g).mean(0)
        err = np.abs(approx - true).max()
        scale = np.abs(np.asarray(g)).max() / 127
        assert err < 3 * scale / np.sqrt(n) + 1e-6, (err, scale)
        print("OK err=", err)
    """)
    assert "OK" in out


def test_moe_sharded_matches_local():
    """shard_map MoE (sort dispatch + EP/ffTP) == single-device moe_ffn."""
    out = run_with_devices("""
        import dataclasses, numpy as np, jax, jax.numpy as jnp
        from repro.models.moe import MoEConfig, init_moe_params, moe_ffn, moe_ffn_sharded
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        for n_exp, shared in ((8, 0), (4, 2)):   # EP (8%4==0) and EP+shared
            cfg = MoEConfig(n_exp, 2, 32, n_shared=shared, d_ff_shared=24,
                            capacity_factor=float(n_exp),  # drop-free
                            mesh=mesh, dp_axes="data")
            params = init_moe_params(jax.random.PRNGKey(0), 16, cfg, jnp.float32)
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16), jnp.float32)
            with mesh:
                y_sh, aux_sh = jax.jit(lambda xx, pp: moe_ffn_sharded(xx, pp, cfg))(x, params)
            cfg0 = dataclasses.replace(cfg, mesh=None)
            y0, aux0 = moe_ffn(x, params, dataclasses.replace(cfg0, dispatch="sort"))
            err = float(jnp.max(jnp.abs(y_sh - y0)))
            assert err < 1e-4, (n_exp, shared, err)
        print("OK")
    """)
    assert "OK" in out


def test_gnn_dst_partitioned_matches_local():
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.models import gnn
        from repro.data import graph as gdata
        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        cfg = gnn.GINConfig("t", n_layers=3, d_hidden=16, d_feat=8, n_classes=4,
                            comm_dtype=jnp.float32)
        n_nodes = 64
        g = gdata.random_graph(n_nodes, 400, 8, 4, seed=0)
        params = gnn.init_params(jax.random.PRNGKey(0), cfg)
        src, dst, emask = gdata.partition_edges_by_dst(g, 4, pad_factor=4.0)
        batch = {"features": jnp.asarray(g.features),
                 "edge_src": jnp.asarray(src), "edge_dst": jnp.asarray(dst),
                 "edge_mask": jnp.asarray(emask),
                 "labels": jnp.asarray(g.labels),
                 "label_mask": jnp.ones((n_nodes,), bool)}
        with mesh:
            loss_d, _ = jax.jit(lambda p, b: gnn.loss_fn_dst_partitioned(
                p, b, cfg, mesh, "data"))(params, batch)
        loss_l, _ = gnn.loss_fn(params, batch, cfg)
        assert abs(float(loss_d) - float(loss_l)) < 1e-4, (float(loss_d), float(loss_l))
        print("OK", float(loss_d))
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_index_serving_matches_oracle():
    """>=100k-token corpus: every oracle gram answered through the mesh-sharded
    index (hash-routed all_to_all round trip), plus a miss-heavy batch and
    top-k continuations."""
    out = run_with_devices("""
        import numpy as np, jax
        from repro.core import run_job, oracle
        from repro.core.stats import NGramConfig
        from repro.data import corpus as corpus_mod
        from repro.index import build_sharded_index, serve_queries
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        prof = corpus_mod.NYT
        toks = corpus_mod.zipf_corpus(110_000, prof, seed=11, duplicate_frac=0.05)
        sigma, tau = 4, 4
        stats = run_job(toks, NGramConfig(sigma=sigma, tau=tau,
                                          vocab_size=prof.vocab_size))
        exp = oracle.ngram_counts(toks, sigma, tau)
        sh = build_sharded_index(stats, vocab_size=prof.vocab_size, mesh=mesh)

        gram_tuples = sorted(exp)
        g = np.zeros((len(gram_tuples), sigma), np.int32)
        ln = np.zeros(len(gram_tuples), np.int32)
        for i, t in enumerate(gram_tuples):
            g[i, :len(t)] = t; ln[i] = len(t)
        got = serve_queries(sh, g, ln)
        assert (got == np.array([exp[t] for t in gram_tuples])).all()

        rng = np.random.default_rng(0)
        lm = rng.integers(1, sigma + 1, 4000).astype(np.int32)
        gm = rng.integers(1, prof.vocab_size + 1, (4000, sigma)).astype(np.int32)
        gm *= np.arange(sigma)[None, :] < lm[:, None]
        gotm = serve_queries(sh, gm, lm)
        wantm = np.array([exp.get(tuple(int(x) for x in r[:l]), 0)
                          for r, l in zip(gm, lm)])
        assert (wantm > 0).mean() < 0.5       # miss-heavy
        assert (gotm == wantm).all()

        k = 8
        pool = [t[:-1] for t in gram_tuples if len(t) >= 2]
        prefixes = [pool[i] for i in rng.choice(len(pool), 30)]
        pg = np.zeros((len(prefixes), sigma), np.int32)
        pl = np.zeros(len(prefixes), np.int32)
        for i, t in enumerate(prefixes):
            pg[i, :len(t)] = t; pl[i] = len(t)
        res = serve_queries(sh, pg, pl, mode="continuations", k=k)
        for i, p in enumerate(prefixes):
            ext = {t[-1]: c for t, c in exp.items()
                   if len(t) == len(p) + 1 and t[:len(p)] == p}
            assert res[i, 0] == len(ext) and res[i, 1] == sum(ext.values())
            cnts = res[i, 2 + k:]
            assert [c for c in cnts if c > 0] == sorted(ext.values(),
                                                        reverse=True)[:k]
            for t_, c_ in zip(res[i, 2:2 + k], cnts):
                if c_ > 0:
                    assert ext[int(t_)] == int(c_)
        print("OK", len(gram_tuples))
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_empty_prefix_matches_single_device():
    """ROADMAP gap closed: len-0 (unigram top-k) prefixes through the sharded
    path -- per-shard top-k gathered and merged on the host -- must agree with
    the single-device answer on an 8-way mesh, for both layouts, mixed into a
    batch with ordinary prefixes."""
    out = run_with_devices("""
        import numpy as np, jax
        from repro.core import run_job, oracle
        from repro.core.stats import NGramConfig
        from repro.data import corpus as corpus_mod
        from repro.index import (build_index, build_sharded_index,
                                 continuations, serve_queries)
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        prof = corpus_mod.NYT
        toks = corpus_mod.zipf_corpus(60_000, prof, seed=13, duplicate_frac=0.05)
        sigma, tau, k = 4, 4, 8
        stats = run_job(toks, NGramConfig(sigma=sigma, tau=tau,
                                          vocab_size=prof.vocab_size))
        exp = oracle.ngram_counts(toks, sigma, tau)
        idx = build_index(stats, vocab_size=prof.vocab_size)

        gram_tuples = sorted(exp)
        pool = [t[:-1] for t in gram_tuples if len(t) >= 2]
        rng = np.random.default_rng(0)
        # empty prefixes interleaved with real ones (the mixed-batch path)
        prefixes = [(), pool[0], (), pool[1]] + \\
            [pool[i] for i in rng.choice(len(pool), 12)] + [()]
        pg = np.zeros((len(prefixes), sigma), np.int32)
        pl = np.zeros(len(prefixes), np.int32)
        for i, t in enumerate(prefixes):
            pg[i, :len(t)] = t; pl[i] = len(t)
        nd, tot, terms, counts = [np.asarray(x) for x in
                                  continuations(idx, pg, pl, k=k)]
        for compress in (False, True):
            sh = build_sharded_index(stats, vocab_size=prof.vocab_size,
                                     mesh=mesh, compress=compress)
            res = serve_queries(sh, pg, pl, mode="continuations", k=k)
            assert (res[:, 0] == nd).all(), compress
            assert (res[:, 1] == tot).all(), compress
            assert (res[:, 2 + k:] == counts).all(), compress   # cf descending
            # term ids may reorder inside equal-count ties; the (term -> cf)
            # mapping must still be real
            for i, p in enumerate(prefixes):
                ext = {t[-1]: c for t, c in exp.items()
                       if len(t) == len(p) + 1 and t[:len(p)] == p}
                for t_, c_ in zip(res[i, 2:2 + k], res[i, 2 + k:]):
                    if c_ > 0:
                        assert ext[int(t_)] == int(c_), (compress, i)
        print("OK", len(prefixes))
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_compressed_index_matches_oracle():
    """Acceptance: the compressed layout answers bit-identically through the
    8-way hash-routed all_to_all path -- every oracle gram plus a miss-heavy
    batch, ref and kernel routes."""
    out = run_with_devices("""
        import numpy as np, jax
        from repro.core import run_job, oracle
        from repro.core.stats import NGramConfig
        from repro.data import corpus as corpus_mod
        from repro.index import build_sharded_index, serve_queries
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        prof = corpus_mod.NYT
        toks = corpus_mod.zipf_corpus(110_000, prof, seed=17, duplicate_frac=0.05)
        sigma, tau = 4, 4
        stats = run_job(toks, NGramConfig(sigma=sigma, tau=tau,
                                          vocab_size=prof.vocab_size))
        exp = oracle.ngram_counts(toks, sigma, tau)
        sh_u = build_sharded_index(stats, vocab_size=prof.vocab_size, mesh=mesh)
        sh_c = build_sharded_index(stats, vocab_size=prof.vocab_size, mesh=mesh,
                                   compress=True)
        # the size contract holds on the at-rest artifact (the decoded query
        # caches are resident-only acceleration state, not stored bytes)
        assert sh_c.index.nbytes_at_rest * 2 <= sh_u.index.nbytes

        gram_tuples = sorted(exp)
        g = np.zeros((len(gram_tuples), sigma), np.int32)
        ln = np.zeros(len(gram_tuples), np.int32)
        for i, t in enumerate(gram_tuples):
            g[i, :len(t)] = t; ln[i] = len(t)
        want = np.array([exp[t] for t in gram_tuples])

        rng = np.random.default_rng(0)
        lm = rng.integers(1, sigma + 1, 4000).astype(np.int32)
        gm = rng.integers(1, prof.vocab_size + 1, (4000, sigma)).astype(np.int32)
        gm *= np.arange(sigma)[None, :] < lm[:, None]
        wantm = np.array([exp.get(tuple(int(x) for x in r[:l]), 0)
                          for r, l in zip(gm, lm)])
        assert (wantm > 0).mean() < 0.5       # really miss-heavy
        for uk in (False, True):
            assert (serve_queries(sh_c, g, ln, use_kernels=uk) == want).all(), uk
            assert (serve_queries(sh_c, gm, lm, use_kernels=uk) == wantm).all(), uk
        print("OK", len(gram_tuples))
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_generational_matches_single_device():
    """Acceptance: a GenerationalIndex grown through >=3 ingests (with a
    compaction) serves bit-identically through the 8-way sharded path -- point
    lookups summed across per-segment shard stacks, continuation candidate
    sets folded on the host -- for both layouts."""
    out = run_with_devices("""
        import numpy as np, jax
        from repro.core import run_job
        from repro.core.stats import NGramConfig
        from repro.index import (GenerationalIndex, build_index, continuations,
                                 lookup, serve_queries, shard_generational,
                                 stats_union)
        from tests.test_compress import make_corpus
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        vocab, sigma, k = 40, 4, 8
        cfg = NGramConfig(sigma=sigma, tau=1, vocab_size=vocab)
        slices = [make_corpus(n, vocab, "zipf", 40 + i)
                  for i, n in enumerate((5000, 1100, 1100, 1100))]
        all_stats = [run_job(t, cfg) for t in slices]
        for compress in (False, True):
            gen = GenerationalIndex(sigma=sigma, vocab_size=vocab,
                                    compress=compress)
            merges = sum(gen.ingest(s)["merges"] for s in all_stats)
            assert merges >= 1 and gen.n_segments >= 2, (merges, gen)
            sh = shard_generational(gen, mesh=mesh)
            assert sh.n_segments == gen.n_segments

            union = stats_union(*all_stats)
            exp = union.to_dict()
            target = build_index(union, vocab_size=vocab)
            gram_tuples = sorted(exp)
            g = np.zeros((len(gram_tuples), sigma), np.int32)
            ln = np.zeros(len(gram_tuples), np.int32)
            for i, t in enumerate(gram_tuples):
                g[i, :len(t)] = t; ln[i] = len(t)
            got = serve_queries(sh, g, ln)
            assert (got == np.asarray(lookup(target, g, ln))).all(), compress
            assert (got == [exp[t] for t in gram_tuples]).all(), compress

            rng = np.random.default_rng(0)
            lm = rng.integers(1, sigma + 1, 2000).astype(np.int32)
            gm = rng.integers(1, vocab + 1, (2000, sigma)).astype(np.int32)
            gm *= np.arange(sigma)[None, :] < lm[:, None]
            assert (serve_queries(sh, gm, lm)
                    == np.asarray(lookup(target, gm, lm))).all(), compress

            pool = [t[:-1] for t in gram_tuples if len(t) >= 2]
            prefixes = [(), pool[0], ()] + \\
                [pool[i] for i in rng.choice(len(pool), 12)]
            pg = np.zeros((len(prefixes), sigma), np.int32)
            pl = np.zeros(len(prefixes), np.int32)
            for i, t in enumerate(prefixes):
                pg[i, :len(t)] = t; pl[i] = len(t)
            res = serve_queries(sh, pg, pl, mode="continuations", k=k)
            nd, tot, terms, cfs = [np.asarray(x) for x in
                                   continuations(target, pg, pl, k=k)]
            assert (res[:, 0] == nd).all(), compress
            assert (res[:, 1] == tot).all(), compress
            assert (res[:, 2:2 + k] == terms).all(), compress
            assert (res[:, 2 + k:] == cfs).all(), compress
        print("OK", len(gram_tuples))
    """)
    assert "OK" in out


@pytest.mark.slow
def test_mesh_waves_match_single_device_and_monolithic():
    """Distributed waves: every wave running as one fused shard_map dispatch
    over an 8-way mesh (ppermute halo + all_to_all shuffle + device-side
    segment collect) must be bit-identical to BOTH the single-device wave
    run and the monolithic job -- all four methods, each across the partial-
    final-wave, wave-smaller-than-mesh, and one-wave degenerate shapes."""
    out = run_with_devices("""
        import numpy as np, jax
        from repro.core import run_job
        from repro.core.stats import NGramConfig
        from repro.pipeline import WaveExecutor
        from tests.test_compress import make_corpus
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))

        def check(toks, mono, cfg, wave):
            single = WaveExecutor(cfg, wave_tokens=wave).run(toks)
            dist = WaveExecutor(cfg, wave_tokens=wave, mesh=mesh).run(toks)
            for got in (single, dist):
                assert np.array_equal(got.grams, mono.grams), cfg.method
                assert np.array_equal(got.lengths, mono.lengths), cfg.method
                assert np.array_equal(got.counts, mono.counts), cfg.method
            assert dist.counters["waves"] == single.counters["waves"]
            return dist

        toks = make_corpus(400, 23, "zipf", seed=7)
        for m in ("suffix_sigma", "naive", "apriori_scan", "apriori_index"):
            cfg = NGramConfig(sigma=4, tau=2, vocab_size=23, method=m,
                              apriori_index_k=2)
            mono = run_job(toks, cfg)
            d = check(toks, mono, cfg, 97)    # partial final wave included
            assert d.counters["waves"] == -(-len(toks) // 97)
            check(toks, mono, cfg, 5)         # wave smaller than the mesh
            check(toks, mono, cfg, len(toks) + 5)   # one-wave degenerate
        print("OK")
    """)
    assert "OK" in out


def test_fused_mesh_one_dispatch_per_wave():
    """The fused mesh-wave program really is ONE sharded dispatch per wave:
    a traced 8-wave multi-round run emits exactly one ``wave.mesh.dispatch``
    span per wave (rounds fused inside the shard_map program, not looped on
    the host), one collect per wave, no overflow retries -- the mesh twin of
    ``test_fused_wave_one_stage_dispatch_per_wave``."""
    out = run_with_devices("""
        import numpy as np, jax
        from repro.core.stats import NGramConfig
        from repro.pipeline import WaveExecutor
        from repro.pipeline.plan import plan_for
        from repro.obs import trace as obs_trace
        from tests.test_compress import make_corpus
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        toks = make_corpus(400, 23, "zipf", seed=5)
        n_waves = 8
        wave = -(-len(toks) // n_waves)
        cfg = NGramConfig(sigma=4, tau=2, vocab_size=23,
                          method="apriori_scan")
        assert plan_for(cfg).rounds > 1
        ex = WaveExecutor(cfg, wave_tokens=wave, mesh=mesh)
        ex.run(toks)                   # warm the per-shape program cache
        tracer = obs_trace.enable_tracing()
        try:
            ex.run(toks)
        finally:
            obs_trace.disable_tracing()
        names = [e["name"] for e in tracer.events]
        assert names.count("wave.mesh.dispatch") == n_waves, names
        assert names.count("wave.mesh.collect") == n_waves
        assert names.count("wave.mesh.retry") == 0
        assert names.count("wave.fold") == n_waves
        assert names.count("wave.run") == 1
        for name in ("wave.mesh.dispatch", "wave.mesh.collect",
                     "wave.collect.wait", "wave.collect.sort",
                     "wave.feed.wait"):
            assert sorted(e["args"]["wave"] for e in tracer.events
                          if e["name"] == name) == list(range(n_waves)), name
        print("OK")
    """)
    assert "OK" in out


def test_mesh_skew_histogram_gated_by_metrics():
    """The per-round skew histogram (a psum'd bincount) must stay out of the
    fused mesh program when metrics are off: disabled runs report
    ``shuffle_skew == 0.0`` (the collective never runs), enabled runs
    measure a real skew -- and the gram set plus every additive counter is
    identical either way (observability must not change results)."""
    out = run_with_devices("""
        import numpy as np, jax
        from repro.core.stats import NGramConfig
        from repro.pipeline import WaveExecutor
        from repro.obs import metrics as obs_metrics
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(3)
        toks = rng.integers(1, 40, 800).astype(np.int32)
        cfg = NGramConfig(sigma=3, tau=1, vocab_size=64)
        off = WaveExecutor(cfg, wave_tokens=200, mesh=mesh).run(toks)
        assert off.counters["shuffle_skew"] == 0.0   # psum skipped outright
        obs_metrics.set_registry(obs_metrics.MetricsRegistry())
        try:
            on = WaveExecutor(cfg, wave_tokens=200, mesh=mesh).run(toks)
        finally:
            obs_metrics.set_registry(None)
        assert on.counters["shuffle_skew"] > 0.0
        assert on.to_dict() == off.to_dict()
        for k in ("jobs", "map_records", "shuffle_records", "shuffle_bytes",
                  "waves", "retries"):
            assert on.counters[k] == off.counters[k], k
        print("OK skew=", on.counters["shuffle_skew"])
    """)
    assert "OK" in out


def test_shard_generational_incremental_reuse():
    """A small delta over a big base must not re-shard untouched elder rungs:
    their shard stacks are reused by level identity (same objects), only the
    new L0 pays a build, and the refreshed stack still answers exactly.  Runs
    in-process on a 1-device mesh -- identity reuse is mesh-width independent."""
    import numpy as np
    import jax
    from repro.core import run_job
    from repro.core.stats import NGramConfig
    from repro.index import (GenerationalIndex, build_index, lookup,
                             serve_queries, shard_generational, stats_union)
    from tests.test_compress import make_corpus

    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    vocab, sigma = 40, 4
    cfg = NGramConfig(sigma=sigma, tau=1, vocab_size=vocab)
    base = [run_job(make_corpus(n, vocab, "zipf", 60 + i), cfg)
            for i, n in enumerate((4000, 900, 900))]
    gen = GenerationalIndex(sigma=sigma, vocab_size=vocab, compress=True)
    for s in base:
        gen.ingest(s)
    sh1 = shard_generational(gen, mesh=mesh)
    assert sh1.n_segments == gen.n_segments

    delta = run_job(make_corpus(120, vocab, "zipf", 99), cfg)
    assert gen.ingest(delta)["merges"] == 0    # small delta: no compaction
    sh2 = shard_generational(gen, mesh=mesh, prev=sh1)
    assert sh2.n_segments == sh1.n_segments + 1
    # elder stacks reused verbatim; only the new L0 was built
    assert all(a is b for a, b in zip(sh2.shards[1:], sh1.shards))
    assert all(sh2.shards[0] is not s for s in sh1.shards)
    assert sh2.level_ids[1:] == sh1.level_ids

    union = stats_union(*base, delta)
    target = build_index(union, vocab_size=vocab)
    exp = union.to_dict()
    gram_tuples = sorted(exp)[:600]
    g = np.zeros((len(gram_tuples), sigma), np.int32)
    ln = np.zeros(len(gram_tuples), np.int32)
    for i, t in enumerate(gram_tuples):
        g[i, :len(t)] = t
        ln[i] = len(t)
    got = serve_queries(sh2, g, ln)
    np.testing.assert_array_equal(got, np.asarray(lookup(target, g, ln)))

    # a layout change invalidates the whole cache: nothing may be reused
    sh3 = shard_generational(gen, mesh=mesh, prev=sh2, block_size=8)
    assert all(a is not b for a in sh3.shards for b in sh2.shards)


def test_sigma_split_exact():
    """Two-phase sigma split (SSPerf H3) is exact vs the single job."""
    import numpy as np
    from repro.core import suffix_sigma
    from repro.core.stats import NGramConfig
    from repro.data import corpus as corpus_mod
    toks = corpus_mod.zipf_corpus(3000, corpus_mod.NYT, seed=5, duplicate_frac=0.3)
    cfg = NGramConfig(sigma=20, tau=2, vocab_size=corpus_mod.NYT.vocab_size)
    full = suffix_sigma.run(toks, cfg).to_dict()
    assert suffix_sigma.sigma_split(toks, cfg, 6, 1 / 8).to_dict() == full
    # undersized survivor buffer recovers via retry
    assert suffix_sigma.sigma_split(toks, cfg, 4, 1 / 512).to_dict() == full


def test_moe_sort_dispatch_under_mesh():
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.transformer import AttentionConfig, LMConfig, init_params, loss_fn
        from repro.models.moe import MoEConfig
        import dataclasses
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = LMConfig("m", 2, 32, 97, 64, AttentionConfig("gqa", 8, 4, 4),
                       moe=MoEConfig(8, 2, 32, capacity_factor=8.0),
                       dtype=jnp.float32, remat=False,
                       shard_activations="data")
        params = init_params(jax.random.PRNGKey(0), cfg)
        from repro.configs.base import lm_param_pspecs, named
        pspecs = lm_param_pspecs(cfg, mesh)
        params = jax.device_put(params, named(mesh, pspecs))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 1, 97)
        batch = {"tokens": jax.device_put(toks, NamedSharding(mesh, P("data", None))),
                 "labels": jax.device_put(toks, NamedSharding(mesh, P("data", None)))}
        with mesh:
            loss, _ = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, batch)
        # compare against single-device value
        cfg0 = dataclasses.replace(cfg, shard_activations=None)
        p0 = jax.device_put(params, jax.devices()[0])
        loss0, _ = loss_fn(p0, jax.device_put(batch, jax.devices()[0]), cfg0)
        assert abs(float(loss) - float(loss0)) < 1e-4, (float(loss), float(loss0))
        print("OK", float(loss))
    """)
    assert "OK" in out


def test_mesh_wave_capacity_retry_counters_not_double_counted():
    """Mesh-wave capacity retries rerun the whole round program, so a naive
    fold of every attempt's stats would double-count map/shuffle records.
    Regression: only the successful attempt's stats may land -- the tight-
    and ample-capacity runs must agree on every additive counter (and on the
    output), differing only in ``retries``."""
    out = run_with_devices("""
        import dataclasses, numpy as np, jax
        from repro.core import run_job
        from repro.core.stats import NGramConfig
        from repro.pipeline import WaveExecutor
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(1)
        # heavy skew: tiny vocab concentrates lead terms; combine=False keeps
        # the duplicate records that actually overflow the (src, dst) buckets
        toks = rng.integers(0, 3, 2400)
        ample_cfg = NGramConfig(sigma=3, tau=1, vocab_size=2, combine=False,
                                capacity_factor=50.0)
        tight_cfg = dataclasses.replace(ample_cfg, capacity_factor=0.05)
        ample = WaveExecutor(ample_cfg, wave_tokens=600, mesh=mesh).run(toks)
        tight = WaveExecutor(tight_cfg, wave_tokens=600, mesh=mesh).run(toks)
        assert ample.counters.get("retries", 0) == 0
        assert tight.counters["retries"] >= 1
        assert tight.counters["overflow"] == 0     # final attempts clean
        for k in ("jobs", "map_records", "shuffle_records", "shuffle_bytes",
                  "waves", "fold_rows"):
            assert tight.counters[k] == ample.counters[k], k
        assert tight.to_dict() == ample.to_dict()
        assert tight.to_dict() == run_job(toks, ample_cfg).to_dict()
        print("OK retries=", tight.counters["retries"])
    """)
    assert "OK" in out


def test_chip_smoke_mesh_phase_on_cpu():
    """``chip_smoke.py --chips 4``'s phase -- mesh waves, the four mesh jobs
    and the sharded index, each against one device -- on a 4-way host mesh
    at a tiny size: the same code the four-chip run executes."""
    out = run_with_devices("""
        import sys
        sys.path.insert(0, ".")
        import chip_smoke
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(4)
        stats, info = chip_smoke.phase_mesh_waves(mesh, wave_corpus=8000,
                                                  wave_tokens=2000)
        assert info["waves"] == 4 and info["grams"] == len(stats), info
        chip_smoke.phase_mesh_index(mesh, stats, n_lookups=64, n_prefixes=32)
        done = []
        info = chip_smoke.phase_mesh_jobs(mesh, job_tokens=6000,
                                          report=done.append)
        assert done == list(chip_smoke.METHODS), done
        print("OK", info)
    """, n=4)
    assert "OK" in out
