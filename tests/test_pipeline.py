"""Wave-vs-monolithic parity for the unified job engine (repro.pipeline).

The contract is the acceptance bar of the engine: for every method and every
wave size, ``WaveExecutor.run`` must be **bit-identical** (grams / lengths /
counts leaf-exact) to the monolithic single-job run -- per-wave partials are
kept at tau=1 and folded through the segment-merge path, so nothing may be
lost or reordered at wave boundaries (the halo + emit-side-carry machinery
under test).  On top: ``run_streaming`` over waves must answer point and
top-k queries exactly like a from-scratch generational build over the full
corpus, the hash-slot combiner route must not change any job output, and the
engine's restrictions (bucketed series) must refuse loudly.

Corpus generation is hypothesis-driven where available and degrades to the
same generator over fixed parametrized draws without it (repo convention).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.core import METHODS, NGramConfig, oracle, run_job
from repro.pipeline import WaveExecutor, canonical_stats, plan_for
from tests.test_compress import make_corpus


def assert_stats_equal(got, want):
    np.testing.assert_array_equal(np.asarray(got.grams), np.asarray(want.grams))
    np.testing.assert_array_equal(np.asarray(got.lengths),
                                  np.asarray(want.lengths))
    np.testing.assert_array_equal(np.asarray(got.counts),
                                  np.asarray(want.counts))


def check_wave_parity(toks, cfg, wave):
    mono = run_job(toks, cfg)
    got = WaveExecutor(cfg, wave_tokens=wave).run(toks)
    assert_stats_equal(got, mono)
    # and the engine really ran out-of-core when asked to
    if wave is not None and wave < len(toks):
        assert got.counters["waves"] == -(-len(toks) // wave)
    return got


def doc_wave(toks) -> int:
    """A wave of roughly one document (the PAD-separated unit)."""
    bounds = np.flatnonzero(np.asarray(toks) == 0)
    if bounds.size == 0:
        return max(1, len(toks) // 4)
    return max(1, int(np.median(np.diff(np.concatenate([[0], bounds])))))


# ------------------------------------------------------ parametrized parity
@pytest.mark.parametrize("method", sorted(METHODS))
@pytest.mark.parametrize("wave", ["corpus", "doc", 17])
def test_wave_parity(method, wave):
    rng = np.random.default_rng(hash(method) % 2**31)
    toks = make_corpus(400, 23, "zipf", seed=7)
    cfg = NGramConfig(sigma=4, tau=2, vocab_size=23, method=method,
                      apriori_index_k=2)
    w = {"corpus": len(toks) + 5, "doc": doc_wave(toks)}.get(wave, wave)
    check_wave_parity(toks, cfg, w)


@pytest.mark.parametrize("method", sorted(METHODS))
def test_wave_parity_single_token_waves(method):
    """wave=1: every token is its own wave -- maximal boundary stress."""
    toks = make_corpus(60, 9, "uniform", seed=3)
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=9, method=method,
                      apriori_index_k=1)
    got = check_wave_parity(toks, cfg, 1)
    assert got.to_dict() == oracle.ngram_counts(toks, 3, 2)


def test_wave_parity_sigma_exceeds_wave():
    """Halo longer than the wave itself (sigma - 1 > wave) must still be
    exact -- suffixes span several wave boundaries."""
    toks = make_corpus(120, 7, "zipf", seed=11)
    cfg = NGramConfig(sigma=6, tau=1, vocab_size=7)
    check_wave_parity(toks, cfg, 3)


@pytest.mark.parametrize("tail", [1, 2, 16])
def test_wave_parity_corpus_not_multiple_of_wave(tail):
    """The final partial wave carries a true live count (not the full wave):
    a corpus of k*wave + tail tokens must stay bit-identical, down to a
    single-token final wave."""
    wave = 64
    toks = np.asarray(make_corpus(400, 19, "zipf", seed=21))[: 5 * wave + tail]
    assert len(toks) % wave == tail
    cfg = NGramConfig(sigma=4, tau=2, vocab_size=19)
    got = check_wave_parity(toks, cfg, wave)
    assert got.counters["waves"] == 6


def test_wave_halo_spans_corpus_tail():
    """A halo reaching past the end of the corpus (the final wave's halo is
    all padding) must neither truncate nor fabricate tail grams."""
    wave = 7
    toks = np.asarray(make_corpus(200, 11, "zipf", seed=23))
    toks = toks[: (len(toks) // wave) * wave + 1]   # 1 live token + 4-pad halo
    cfg = NGramConfig(sigma=5, tau=1, vocab_size=11)
    got = check_wave_parity(toks, cfg, wave)
    assert got.to_dict() == oracle.ngram_counts(toks, 5, 1)


def test_wave_empty_corpus():
    """Zero tokens: one empty wave, empty output, and a queryable (empty)
    streaming index -- no crashes anywhere on the path."""
    from repro.index import lookup

    empty = np.zeros((0,), np.int32)
    for method in ("suffix_sigma", "naive"):
        cfg = NGramConfig(sigma=3, tau=1, vocab_size=9, method=method)
        got = WaveExecutor(cfg, wave_tokens=8).run(empty)
        assert len(got) == 0
        assert got.counters["waves"] == 1
    cfg = NGramConfig(sigma=3, tau=1, vocab_size=9)
    gen, reports = WaveExecutor(cfg, wave_tokens=8).run_streaming(empty)
    assert len(reports) == 1 and gen.generation == 1
    assert gen.n_segments == 0      # empty deltas must not pile up segments
    g = np.asarray([[1, 2, 0]], np.int32)
    assert np.asarray(lookup(gen, g, np.asarray([2], np.int32)))[0] == 0


# ------------------------------------------------------------ wave fold
def test_accumulator_parity_and_fold_work_win():
    """The deferred fold is bit-identical to the monolithic job at 16 waves,
    and its merge work is exactly one pass over the wave partials:
    ``fold_rows`` is the sum of the rows the 16 ``wave.fold`` spans pushed."""
    from repro.obs import trace as obs_trace
    toks = make_corpus(2500, 50, "zipf", seed=31)
    cfg = NGramConfig(sigma=4, tau=2, vocab_size=50)
    wave = -(-len(toks) // 16)
    mono = run_job(toks, cfg)
    tracer = obs_trace.enable_tracing()
    try:
        got = WaveExecutor(cfg, wave_tokens=wave).run(toks)
    finally:
        obs_trace.disable_tracing()
    assert_stats_equal(got, mono)
    folds = [e for e in tracer.export()["traceEvents"]
             if e["name"] == "wave.fold"]
    assert len(folds) == got.counters["waves"] == 16
    assert got.counters["fold_rows"] == \
        sum(e["args"]["rows"] for e in folds) > 0


def test_merge_route_device_matches_monolithic():
    """The blocked device fold route (``merge_route="device"``, the
    accelerator's default) is bit-identical to the monolithic job."""
    toks = make_corpus(2000, 40, "zipf", seed=41)
    cfg = NGramConfig(sigma=4, tau=2, vocab_size=40)
    wave = -(-len(toks) // 8)
    got = WaveExecutor(cfg, wave_tokens=wave, merge_route="device").run(toks)
    assert_stats_equal(got, run_job(toks, cfg))


@pytest.mark.parametrize("tau", [1, 2, 10])
@pytest.mark.parametrize("n_waves", [1, 3, 8])
def test_device_finalize_matches_kway_and_monolithic(monkeypatch, tau,
                                                      n_waves):
    """The blocked device finalize (``merge_route="device"``, the chip's
    default), with blocks shrunk so each fold spans many: bit-identical to
    the monolithic job and to the host k-way route at every tau, for one
    segment as for many; ``finalize_blocks`` counts its blocks, and stays 0
    on the host route and where a lone segment needs no fold."""
    from repro.index import merge as merge_mod
    monkeypatch.setattr(merge_mod, "DEVICE_BLOCK_ROWS", 64)
    toks = make_corpus(1500, 30, "zipf", seed=43 + n_waves)
    cfg = NGramConfig(sigma=3, tau=tau, vocab_size=30)
    wave = -(-len(toks) // n_waves)
    mono = run_job(toks, cfg)
    kway = WaveExecutor(cfg, wave_tokens=wave, merge_route="kway").run(toks)
    dev = WaveExecutor(cfg, wave_tokens=wave, merge_route="device").run(toks)
    assert_stats_equal(dev, mono)
    assert_stats_equal(kway, mono)
    assert dev.counters["waves"] == n_waves
    assert kway.counters["finalize_blocks"] == 0
    if n_waves > 1:
        assert dev.counters["finalize_blocks"] > 1
    else:
        assert dev.counters["finalize_blocks"] == 0
    assert dev.counters["fold_rows"] >= kway.counters["fold_rows"]


def test_device_finalize_of_an_empty_job():
    """An empty corpus folds to empty statistics on both routes."""
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=9)
    empty = np.zeros((0,), np.int32)
    for route in ("device", "kway"):
        got = WaveExecutor(cfg, wave_tokens=8, merge_route=route).run(empty)
        assert len(got) == 0
        assert got.counters["finalize_blocks"] == 0


def test_default_merge_route_follows_backend(monkeypatch):
    """``merge_route=None`` picks the host k-way fold on the CPU backend and
    the blocked device fold on an accelerator; an explicit route wins, and
    any other route is refused before a wave runs."""
    import jax
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=9)
    assert WaveExecutor(cfg, wave_tokens=8).merge_route == "kway"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert WaveExecutor(cfg, wave_tokens=8).merge_route == "device"
    assert WaveExecutor(cfg, wave_tokens=8,
                        merge_route="kway").merge_route == "kway"
    for route in ("sort", "merge"):
        with pytest.raises(ValueError, match="merge route"):
            WaveExecutor(cfg, wave_tokens=8, merge_route=route)


def test_device_route_readies_one_block_program_on_first_run(monkeypatch,
                                                             caplog):
    """On an accelerator every device fold runs blocks of one shape.  A
    one-wave first run folds nothing, yet returns with that shape's block
    programs compiled; a later many-wave run of another size folds in
    several blocks and compiles neither program again."""
    import logging

    import jax
    from repro.index import merge as merge_mod
    monkeypatch.setattr(merge_mod, "DEVICE_BLOCK_ROWS", 512)
    monkeypatch.setattr(merge_mod, "_SURVIVOR_CHUNK", 64)
    monkeypatch.setattr(merge_mod, "_block_loads", {})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=12)
    toks = make_corpus(300, 12, "zipf", seed=1)
    more = np.concatenate([toks, make_corpus(900, 12, "zipf", seed=2)])
    ex = WaveExecutor(cfg, wave_tokens=toks.size)
    assert ex.merge_route == "device"
    one = ex.run(toks)
    assert one.counters["finalize_blocks"] == 0
    assert [f.done() for f in merge_mod._block_loads.values()] == [True]
    with jax.log_compiles(True), caplog.at_level(logging.WARNING):
        many = ex.run(more)
    assert "Compiling jit(_merge_block)" not in caplog.text
    assert "Compiling jit(_survivor_chunk)" not in caplog.text
    assert many.counters["finalize_blocks"] > 1
    assert_stats_equal(many, run_job(more, cfg))


def test_segment_accumulators_match_merge_oracle():
    """Unit level: pushing per-wave segments through the deferred fold, on
    either route, gives the segment a one-shot merge of everything would."""
    from repro.index import merge_segments, segment_from_stats, segment_to_stats
    from repro.index.merge import DeferredSegmentAccumulator

    cfg = NGramConfig(sigma=3, tau=1, vocab_size=15)
    segs = []
    for seed in range(6):
        stats = run_job(make_corpus(150, 15, "zipf", seed=seed), cfg)
        segs.append(segment_from_stats(stats, vocab_size=15))
    want = segment_to_stats(merge_segments(segs, route="kway"))
    for route in ("kway", "device"):
        acc = DeferredSegmentAccumulator(route=route)
        for s in segs:
            acc.push(s)
        got = segment_to_stats(acc.result())
        assert_stats_equal(got, want)
        assert acc.fold_rows == sum(s.n_rows for s in segs)
    with pytest.raises(ValueError):
        DeferredSegmentAccumulator().result()


# -------------------------------------------------------- reserved-id-0 guard
def test_validate_tokens_rejects_out_of_range_ids():
    """Ids past vocab_size would overflow their packed lane field and
    fabricate grams; negative ids alias through the uint32 casts.  Both must
    fail loudly at the wave-engine door (PAD id 0 stays legal)."""
    cfg = NGramConfig(sigma=3, tau=1, vocab_size=9)
    cfg.validate_tokens(np.asarray([0, 1, 9, 0, 3]))        # in range: fine
    with pytest.raises(ValueError, match="reserved PAD"):
        cfg.validate_tokens(np.asarray([1, 10, 2]))
    with pytest.raises(ValueError, match="reserved PAD"):
        cfg.validate_tokens(np.asarray([-1, 2, 3]))
    ex = WaveExecutor(cfg, wave_tokens=4)
    with pytest.raises(ValueError, match="token ids"):
        ex.run(np.asarray([1, 2, 10]))
    with pytest.raises(ValueError, match="token ids"):
        ex.run_streaming(np.asarray([1, -2, 3]))


# ------------------------------------------------------------- stage cache
def test_stage_cache_keyed_by_backend_with_reset(monkeypatch):
    """The jitted stage program's donation choice depends on the backend, so
    the cache must key by it (never freeze the first caller's backend) and be
    resettable for tests/reconfiguration."""
    from repro.pipeline import executor, reset_stage_cache

    toks = make_corpus(60, 9, "uniform", seed=1)
    cfg = NGramConfig(sigma=3, tau=1, vocab_size=9)
    run_job(toks, cfg)
    real = jax_backend = executor.jax.default_backend()
    assert jax_backend in executor._STAGE_CORE
    cpu_fn = executor._STAGE_CORE[real]
    monkeypatch.setattr(executor.jax, "default_backend", lambda: "faketpu")
    # a "new backend" must get its own program, not reuse the frozen one
    run_job(toks, cfg)
    assert "faketpu" in executor._STAGE_CORE
    assert executor._STAGE_CORE["faketpu"] is not cpu_fn
    assert executor._STAGE_CORE[real] is cpu_fn    # old entry untouched
    reset_stage_cache()
    assert executor._STAGE_CORE == {}
    monkeypatch.undo()
    assert_stats_equal(run_job(toks, cfg),
                       WaveExecutor(cfg, wave_tokens=13).run(toks))


def test_generational_ingest_skips_empty_delta():
    """An empty delta bumps the generation (cache invalidation) but must not
    insert an all-sentinel segment that every later query pays for."""
    from repro.core.stats import NGramStats
    from repro.index import GenerationalIndex

    gen = GenerationalIndex(sigma=3, vocab_size=9)
    stats = run_job(make_corpus(200, 9, "zipf", seed=2),
                    NGramConfig(sigma=3, tau=1, vocab_size=9))
    gen.ingest(stats)
    n_seg, g0 = gen.n_segments, gen.generation
    empty = NGramStats(np.zeros((0, 3), np.int32), np.zeros((0,), np.int32),
                       np.zeros((0,), np.int64))
    rep = gen.ingest(empty)
    assert rep["ingested_rows"] == 0 and rep["merges"] == 0
    assert gen.n_segments == n_seg
    assert gen.generation == g0 + 1


# ----------------------------------------------------- randomized corpora
def _parity_draw(method, vocab, dist, sigma, tau, wave_frac, seed):
    toks = make_corpus(350, vocab, dist, seed)
    cfg = NGramConfig(sigma=sigma, tau=tau, vocab_size=vocab, method=method,
                      combine=bool(seed % 2), apriori_index_k=1 + seed % 3)
    wave = max(1, int(len(toks) * wave_frac))
    check_wave_parity(toks, cfg, wave)


FALLBACK_DRAWS = [
    ("suffix_sigma", 50, "zipf", 5, 1, 0.31, 0),
    ("naive", 11, "uniform", 3, 2, 0.09, 1),
    ("apriori_scan", 200, "zipf", 4, 3, 0.5, 2),
    ("apriori_index", 30, "uniform", 5, 2, 0.13, 3),
]


@pytest.mark.parametrize("draw", FALLBACK_DRAWS,
                         ids=[d[0] for d in FALLBACK_DRAWS])
def test_wave_parity_fixed_draws(draw):
    _parity_draw(*draw)


if HAS_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(method=st.sampled_from(sorted(METHODS)),
           vocab=st.integers(5, 500),
           dist=st.sampled_from(["zipf", "uniform"]),
           sigma=st.integers(1, 6), tau=st.integers(1, 4),
           wave_frac=st.floats(0.02, 1.2), seed=st.integers(0, 2**20))
    def test_wave_parity_hypothesis(method, vocab, dist, sigma, tau,
                                    wave_frac, seed):
        _parity_draw(method, vocab, dist, sigma, tau, wave_frac, seed)


# ------------------------------------------------------- streaming serving
def test_streaming_ingest_equals_batch_build():
    """Waves -> GenerationalIndex must answer point + top-k queries exactly
    like a from-scratch generational build over the whole corpus."""
    from repro.index import continuations, generational_from_stats, lookup

    rng = np.random.default_rng(5)
    toks = make_corpus(3000, 40, "zipf", seed=5)
    cfg = NGramConfig(sigma=4, tau=1, vocab_size=40)
    gen, reports = WaveExecutor(cfg, wave_tokens=512).run_streaming(toks)
    assert len(reports) == -(-len(toks) // 512)
    assert gen.generation == len(reports)

    stats = run_job(toks, cfg)
    want = generational_from_stats(stats, vocab_size=40)

    q = 96
    grams = np.zeros((q, 4), np.int32)
    lengths = np.zeros((q,), np.int32)
    rows = rng.choice(len(stats), q - 16)
    grams[: q - 16] = stats.grams[rows]
    lengths[: q - 16] = stats.lengths[rows]
    grams[q - 16:] = rng.integers(1, 46, (16, 4))      # misses / OOV
    lengths[q - 16:] = rng.integers(1, 5, 16)

    np.testing.assert_array_equal(np.asarray(lookup(gen, grams, lengths)),
                                  np.asarray(lookup(want, grams, lengths)))
    p_len = np.maximum(lengths - 1, 0)
    got_c = continuations(gen, grams, p_len, k=6)
    want_c = continuations(want, grams, p_len, k=6)
    for g, w in zip(got_c, want_c):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_streaming_service_wave_ingest_matches_monolithic():
    """serve_ngrams' service with wave_tokens set serves identical counts."""
    from repro.launch.serve_ngrams import StreamingNGramService

    toks = make_corpus(1200, 25, "zipf", seed=9)
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=25)
    a = StreamingNGramService(cfg, cache_capacity=64)
    b = StreamingNGramService(cfg, cache_capacity=64, wave_tokens=200)
    ra = a.ingest(toks)
    rb = b.ingest(toks)
    assert ra["ingested_rows"] == rb["ingested_rows"]
    assert rb["waves"] == -(-len(toks) // 200) and ra["waves"] == 1
    stats = run_job(toks, cfg)
    g = np.asarray(stats.grams)[:64]
    ln = np.asarray(stats.lengths)[:64]
    np.testing.assert_array_equal(a.lookup(g, ln), b.lookup(g, ln))


# --------------------------------------------------------- engine contract
def test_run_job_output_is_canonical():
    """Single-device jobs now emit canonical (segment-ordered, deduped) rows;
    canonical_stats must be a fixed point of their output."""
    toks = make_corpus(500, 15, "zipf", seed=1)
    for method in METHODS:
        stats = run_job(toks, NGramConfig(sigma=3, tau=2, vocab_size=15,
                                          method=method))
        assert_stats_equal(canonical_stats(stats), stats)


def test_hash_combine_route_preserves_output():
    """The sort-free combiner may only *redistribute* weights -- job output
    (and the oracle) must be untouched, kernel and jnp routes alike."""
    toks = make_corpus(600, 18, "zipf", seed=2)
    want = oracle.ngram_counts(toks, 4, 2)
    for use_kernels in (False, True):
        cfg = NGramConfig(sigma=4, tau=2, vocab_size=18,
                          combine_route="hash", use_kernels=use_kernels)
        assert run_job(toks, cfg).to_dict() == want
        got = WaveExecutor(cfg, wave_tokens=150).run(toks)
        assert got.to_dict() == want


def test_hash_combine_actually_combines():
    """On a duplicate-heavy stream the hash route must shrink the shuffle
    (the whole point of a combiner), not just pass records through."""
    toks = np.asarray([1, 2, 3] * 200, np.int32)
    on = run_job(toks, NGramConfig(sigma=3, tau=1, vocab_size=3,
                                   combine_route="hash"))
    off = run_job(toks, NGramConfig(sigma=3, tau=1, vocab_size=3,
                                    combine=False))
    assert on.counters["shuffle_records"] < off.counters["shuffle_records"]
    assert on.to_dict() == off.to_dict()


def test_plan_registry_covers_methods():
    for method in METHODS:
        plan = plan_for(NGramConfig(sigma=3, tau=1, vocab_size=9,
                                    method=method))
        assert plan.name == method
    with pytest.raises(ValueError):
        plan_for(NGramConfig(sigma=3, tau=1, vocab_size=9, method="nope"))


def test_wave_rejects_buckets():
    cfg = NGramConfig(sigma=3, tau=1, vocab_size=9, n_buckets=4)
    with pytest.raises(ValueError, match="n_buckets"):
        WaveExecutor(cfg, wave_tokens=8)
    with pytest.raises(ValueError, match="n_buckets"):
        WaveExecutor(cfg)               # one-wave mode can't carry buckets either


@pytest.mark.slow
def test_wave_parity_acceptance_scale():
    """Acceptance-sized corpus (>=30k tokens, zipf skew, 6 waves): the
    bit-identity contract and the streaming path at a size where padding /
    capacity-rounding bugs would actually bite."""
    from repro.index import generational_from_stats, lookup

    toks = make_corpus(30_000, 2_000, "zipf", seed=13)
    cfg = NGramConfig(sigma=5, tau=4, vocab_size=2_000)
    wave = -(-len(toks) // 6)
    got = check_wave_parity(toks, cfg, wave)
    assert got.counters["waves"] == 6

    cfg1 = NGramConfig(sigma=5, tau=1, vocab_size=2_000)
    gen, _ = WaveExecutor(cfg1, wave_tokens=wave).run_streaming(toks)
    want = generational_from_stats(run_job(toks, cfg1), vocab_size=2_000)
    stats = run_job(toks, cfg1)
    rng = np.random.default_rng(13)
    rows = rng.choice(len(stats), 256)
    g = np.asarray(stats.grams)[rows]
    ln = np.asarray(stats.lengths)[rows]
    np.testing.assert_array_equal(np.asarray(lookup(gen, g, ln)),
                                  np.asarray(lookup(want, g, ln)))


def test_suffix_map_record_invariant_across_waves():
    """SSIV: one record per token occurrence, wave-split or not."""
    toks = make_corpus(500, 20, "uniform", seed=8)
    n_tok = int((np.asarray(toks) != 0).sum())
    cfg = NGramConfig(sigma=4, tau=1, vocab_size=20, combine=False)
    got = WaveExecutor(cfg, wave_tokens=97).run(toks)
    assert got.counters["map_records"] == n_tok
    assert got.counters["shuffle_records"] == n_tok


# ------------------------------------------------------------ fused dispatch
def test_fused_wave_one_stage_dispatch_per_wave():
    """The whole-wave program really is ONE dispatch: a traced 8-wave run
    emits exactly one ``round.stages`` span per wave even for a multi-round
    plan (the rounds are fused inside the program, not looped on the host),
    and every wave passes through exactly one collect and one fold."""
    from repro.obs import trace as obs_trace

    toks = make_corpus(400, 23, "zipf", seed=5)
    n_waves = 8
    wave = -(-len(toks) // n_waves)
    cfg = NGramConfig(sigma=4, tau=2, vocab_size=23, method="apriori_scan")
    assert plan_for(cfg).rounds > 1
    WaveExecutor(cfg, wave_tokens=wave).run(toks)   # warm the program caches
    tracer = obs_trace.enable_tracing()
    try:
        WaveExecutor(cfg, wave_tokens=wave).run(toks)
    finally:
        obs_trace.disable_tracing()
    names = [e["name"] for e in tracer.events]
    assert names.count("round.stages") == n_waves
    assert names.count("wave.collect") == n_waves
    assert names.count("wave.fold") == n_waves
    assert names.count("wave.run") == 1


def test_direct_segment_collect_matches_stats_route():
    """The segment collect (``_collect_wave_segment`` over the wave
    program's segment form: rows built on the device as ``length | lanes &
    prefix_mask[len]``) must produce the exact segment of the stats detour
    (``segment_from_wave_stats(_collect_wave(...))`` over the dense form)
    -- per wave, every method.  Each form gets the wave once."""
    from repro.index.build import segment_from_wave_stats

    toks = make_corpus(300, 23, "zipf", seed=9)
    for method in sorted(METHODS):
        cfg = NGramConfig(sigma=4, tau=2, vocab_size=23, method=method,
                          apriori_index_k=2)
        ex = WaveExecutor(cfg, wave_tokens=61)
        assert ex._direct
        for tok_ext, n_live in ex._windows(np.asarray(toks, np.int32)):
            part = ex._collect_wave_segment(
                ex._submit_segment_wave(tok_ext, n_live))
            want = segment_from_wave_stats(
                ex._collect_wave(ex._submit_wave(tok_ext, n_live)),
                vocab_size=cfg.vocab_size)
            assert part.n_rows == want.n_rows, method
            np.testing.assert_array_equal(np.asarray(part.segment.keys),
                                          np.asarray(want.keys))
            np.testing.assert_array_equal(np.asarray(part.segment.counts),
                                          np.asarray(want.counts))


def host_segment_rows(ex, tok_ext, n_live):
    """The reference: a wave's segment rows built on the host from the
    dense program's reducer output -- each round's kept cells through
    ``_prefix_rows``, the rounds concatenated, then a stable byte-view
    argsort."""
    from repro.index._layout import row_bytes_view
    from repro.pipeline.executor import _prefix_rows

    masks = ex._prefix_masks()
    keys, cnts = [], []
    for ((_, flags, counts), lanes), *_ in ex._submit_wave(
            tok_ext, n_live)["rounds"]:
        flags, counts, lanes = (np.asarray(x) for x in (flags, counts, lanes))
        k, c = _prefix_rows((flags != 0) & (counts >= 1), counts, lanes,
                            masks)
        keys.append(k)
        cnts.append(c)
    keys, cnts = np.concatenate(keys), np.concatenate(cnts)
    order = np.argsort(row_bytes_view(keys), kind="stable")
    return keys[order], cnts[order]


@pytest.mark.parametrize("chunk", [16, 1 << 22])
@pytest.mark.parametrize("method", sorted(METHODS))
def test_device_segment_rows_match_host_reference(method, chunk,
                                                  monkeypatch):
    """The rows the wave program builds on the device, collected, equal
    the host path's bit for bit (single- and multi-round plans; with
    16-row chunks a wave's rows cross many chunk boundaries), and no wave
    needed a host sort: every plan's rounds emit rising lengths."""
    from repro.pipeline import executor
    monkeypatch.setattr(executor, "SEGMENT_CHUNK_ROWS", chunk)
    toks = make_corpus(400, 29, "zipf", seed=21)
    cfg = NGramConfig(sigma=4, tau=2, vocab_size=29, method=method,
                      apriori_index_k=2)
    ex = WaveExecutor(cfg, wave_tokens=97)
    rows = []
    for w, (tok_ext, n_live) in enumerate(
            ex._windows(np.asarray(toks, np.int32))):
        part = ex._collect_wave_segment(
            ex._submit_segment_wave(tok_ext, n_live, w))
        keys, cnts = host_segment_rows(ex, tok_ext, n_live)
        np.testing.assert_array_equal(part.segment.keys, keys)
        np.testing.assert_array_equal(part.segment.counts, cnts)
        assert part.counters["collect_host_sorts"] == 0
        assert part.n_rows == len(keys)
        rows.append(part.n_rows)
    assert max(rows) > 4 * 16      # 16-row chunks: several a wave
    got = ex.run(toks)
    assert got.counters["collect_host_sorts"] == 0
    assert_stats_equal(got, run_job(toks, cfg))


@pytest.mark.parametrize("chunk", [7, 16, 1 << 22])
def test_segment_table_fills_its_capacity(chunk):
    """A block of all-distinct full-length grams keeps every cell of the
    suffix reducer's [N, sigma] grid, so the device table is full to the
    last row of its last chunk; its rows equal the host reference's."""
    import jax.numpy as jnp
    from repro.mapreduce import pack as packing
    from repro.pipeline import stages
    from repro.pipeline.executor import _prefix_rows, _table_chunks, \
        _table_rows

    sigma, vocab, n = 4, 200, 48
    rng = np.random.default_rng(4)
    terms = rng.integers(1, vocab + 1, size=(n, sigma)).astype(np.int32)
    terms[:, 0] = rng.permutation(np.arange(1, vocab + 1))[:n]
    lanes = packing.pack_terms_np(terms, vocab_size=vocab)
    rec = np.concatenate([lanes, np.ones((n, 1), np.uint32)], axis=1)
    rec = rec[np.lexsort(lanes.T[::-1])]
    masks = packing.prefix_lane_masks(sigma, vocab)
    _, flags, counts = stages.reduce_suffix(jnp.asarray(rec), sigma=sigma,
                                            vocab_size=vocab)
    n_l = lanes.shape[1]
    per_len, chunks = stages.segment_table(
        flags, counts, jnp.asarray(rec[:, :n_l]), jnp.asarray(masks),
        sigma=sigma, reduce_kind="suffix", chunk=chunk)
    per_len = np.asarray(per_len)
    assert per_len.tolist() == [n] * sigma
    need = _table_chunks(chunks, n * sigma, n_l + 1)
    assert len(need) == len(chunks) == -(-n * sigma // chunk)
    keys, cnts, starts = _table_rows([per_len],
                                     [[np.asarray(c) for c in need]], n_l)
    want_keys, want_cnts = _prefix_rows(
        (np.asarray(flags) != 0) & (np.asarray(counts) >= 1),
        np.asarray(counts), rec[:, :n_l], masks)
    np.testing.assert_array_equal(keys, want_keys)
    np.testing.assert_array_equal(cnts, want_cnts)
    assert starts.tolist() == [0]


def test_segment_collect_sorts_rounds_out_of_order():
    """Where a round's rows do not follow the previous round's, the collect
    falls back to the stable host sort, still equal to the reference, and
    counts the wave in ``collect_host_sorts``."""
    toks = make_corpus(300, 23, "zipf", seed=9)
    cfg = NGramConfig(sigma=4, tau=2, vocab_size=23, method="apriori_scan")
    ex = WaveExecutor(cfg)
    (tok_ext, n_live), = ex._windows(np.asarray(toks, np.int32))
    pend = ex._submit_segment_wave(tok_ext, n_live)
    assert len(pend["rounds"]) > 1
    pend["rounds"].reverse()
    part = ex._collect_wave_segment(pend)
    keys, cnts = host_segment_rows(ex, tok_ext, n_live)
    np.testing.assert_array_equal(part.segment.keys, keys)
    np.testing.assert_array_equal(part.segment.counts, cnts)
    assert part.counters["collect_host_sorts"] == 1


def test_wave_parity_unpacked_lane_fallback():
    """``pack=False`` packs lanes with a vocabulary other than the segment's,
    so the direct-segment collect must disable itself and route through the
    stats collect -- still bit-identical to the monolithic job."""
    toks = make_corpus(200, 11, "zipf", seed=13)
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=11, pack=False)
    assert not WaveExecutor(cfg, wave_tokens=37)._direct
    check_wave_parity(toks, cfg, 37)


def test_overlap_off_matches_overlap_on():
    """The background fold thread is a scheduling choice, not a semantic one:
    overlap on/off must agree bit-for-bit on stats, counters, and the
    streaming ingest reports."""
    toks = make_corpus(300, 19, "zipf", seed=17)
    cfg = NGramConfig(sigma=4, tau=2, vocab_size=19)
    on = WaveExecutor(cfg, wave_tokens=41).run(toks)
    off = WaveExecutor(cfg, wave_tokens=41, overlap=False).run(toks)
    assert_stats_equal(on, off)
    assert on.counters == off.counters
    cfg1 = NGramConfig(sigma=4, tau=1, vocab_size=19)
    g_on, r_on = WaveExecutor(cfg1, wave_tokens=41).run_streaming(toks)
    g_off, r_off = WaveExecutor(cfg1, wave_tokens=41,
                                overlap=False).run_streaming(toks)
    assert r_on == r_off
    assert g_on.generation == g_off.generation
