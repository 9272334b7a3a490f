"""The wave engine's head/tail split against the plain references.

A job whose records are wider than ``executor.SPLIT_HEAD_LANES`` lanes runs
as two passes over the same waves: the head pass counts every gram of at
most the head width, the tail pass only the longer grams, at the positions
whose head is frequent over the whole job.  The head width is shrunk here so
the split engages at a small sigma; every result must equal the oracle
(``core/oracle.py``) and the same job run without the split.
"""
import types

import numpy as np
import pytest

from repro.core import NGramConfig, oracle
from repro.pipeline import WaveExecutor
from repro.pipeline import executor

DEFAULT_HEAD_LANES = executor.SPLIT_HEAD_LANES
HEAD_LANES = 3
BIG_VOCAB = 200_000          # 18-bit terms: one term per lane, head = 3 terms


@pytest.fixture(autouse=True)
def small_head(monkeypatch):
    monkeypatch.setattr(executor, "SPLIT_HEAD_LANES", HEAD_LANES)


def corpus(n_sent: int, vocab: int, seed: int, quotes=(), every: int = 4,
           mean_len: int = 9):
    """Zipf sentences separated by PAD, with each of ``quotes`` repeated
    verbatim as a sentence of its own after every ``every`` sentences."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -1.1
    p /= p.sum()
    parts = []
    for i in range(n_sent):
        n = int(rng.integers(1, 2 * mean_len))
        parts += [rng.choice(vocab, size=n, p=p) + 1, [0]]
        if i % every == 0:
            for q in quotes:
                parts += [q, [0]]
    return np.concatenate(parts).astype(np.int32)


def as_dict(stats) -> dict:
    return stats.to_dict()


def run_both(toks, cfg, wave, monkeypatch):
    """(split run, unsplit run) of one job."""
    split_ex = WaveExecutor(cfg, wave_tokens=wave)
    assert split_ex._head_ex is not None
    got = split_ex.run(toks)
    monkeypatch.setattr(executor, "SPLIT_HEAD_LANES", 1 << 10)
    plain_ex = WaveExecutor(cfg, wave_tokens=wave)
    assert plain_ex._head_ex is None
    plain = plain_ex.run(toks)
    monkeypatch.setattr(executor, "SPLIT_HEAD_LANES", HEAD_LANES)
    return got, plain


def assert_same_stats(a, b):
    np.testing.assert_array_equal(np.asarray(a.grams), np.asarray(b.grams))
    np.testing.assert_array_equal(np.asarray(a.lengths), np.asarray(b.lengths))
    np.testing.assert_array_equal(np.asarray(a.counts), np.asarray(b.counts))


@pytest.mark.parametrize("method,vocab,n_waves", [
    ("suffix_sigma", BIG_VOCAB, 1),
    ("suffix_sigma", BIG_VOCAB, 3),
    ("suffix_sigma", BIG_VOCAB, 6),
    ("suffix_sigma", 1000, 4),       # 10-bit terms: three to a lane
    ("naive", BIG_VOCAB, 3),
])
def test_split_matches_oracle_and_unsplit_job(monkeypatch, method, vocab,
                                              n_waves):
    rng = np.random.default_rng(vocab + n_waves)
    quotes = [rng.integers(1, vocab + 1, n) for n in (5, 9, 14)]
    toks = corpus(160, vocab, seed=n_waves, quotes=quotes)
    cfg = NGramConfig(sigma=12, tau=3, vocab_size=vocab, method=method)
    wave = -(-toks.size // n_waves)
    got, plain = run_both(toks, cfg, wave, monkeypatch)
    assert_same_stats(got, plain)
    assert as_dict(got) == oracle.ngram_counts(toks, 12, 3)
    assert got.counters["waves"] == n_waves
    assert got.counters["head_dict_rows"] > 0
    assert got.counters["tail_positions"] > 0
    assert int(got.lengths.max()) == 12
    assert np.asarray(got.grams).shape[1] == 12


def test_long_gram_frequent_over_the_job_but_in_no_wave(monkeypatch):
    """A quotation once per wave: below tau in every wave, at tau over the
    job; every one of its grams longer than the head is counted exactly."""
    rng = np.random.default_rng(5)
    quote = rng.integers(1, BIG_VOCAB + 1, 10).astype(np.int32)
    n_waves = 4
    waves = [np.concatenate([corpus(30, BIG_VOCAB, seed=10 + w), quote, [0]])
             for w in range(n_waves)]
    wave = max(w.size for w in waves)
    toks = np.concatenate([np.pad(w, (0, wave - w.size)) for w in waves])
    cfg = NGramConfig(sigma=10, tau=n_waves, vocab_size=BIG_VOCAB)
    got, plain = run_both(toks, cfg, wave, monkeypatch)
    assert got.counters["waves"] == n_waves
    assert_same_stats(got, plain)
    d = as_dict(got)
    assert d == oracle.ngram_counts(toks, 10, n_waves)
    for n in range(HEAD_LANES + 1, 11):
        assert d[tuple(quote[:n].tolist())] == n_waves


@pytest.mark.parametrize("length", [HEAD_LANES, HEAD_LANES + 1])
def test_grams_at_and_just_past_the_head_width(monkeypatch, length):
    """A repeated sentence exactly the head long, or one term longer: the
    former is the head pass's alone, the latter's full gram the tail's."""
    rng = np.random.default_rng(length)
    quote = rng.integers(1, BIG_VOCAB + 1, length).astype(np.int32)
    toks = corpus(60, BIG_VOCAB, seed=length, quotes=[quote], every=6)
    cfg = NGramConfig(sigma=8, tau=3, vocab_size=BIG_VOCAB)
    got, plain = run_both(toks, cfg, -(-toks.size // 3), monkeypatch)
    assert_same_stats(got, plain)
    d = as_dict(got)
    assert d == oracle.ngram_counts(toks, 8, 3)
    assert d[tuple(quote.tolist())] >= 3
    assert int(got.lengths.max()) == length


def test_sentences_longer_than_sigma(monkeypatch):
    """Quotations and sentences far longer than sigma: grams stop at sigma,
    and windows run into the next wave's tokens through the halo."""
    rng = np.random.default_rng(7)
    quotes = [rng.integers(1, BIG_VOCAB + 1, n) for n in (23, 31)]
    toks = corpus(50, BIG_VOCAB, seed=7, quotes=quotes, every=5, mean_len=25)
    cfg = NGramConfig(sigma=9, tau=2, vocab_size=BIG_VOCAB)
    got, plain = run_both(toks, cfg, 97, monkeypatch)
    assert_same_stats(got, plain)
    assert as_dict(got) == oracle.ngram_counts(toks, 9, 2)
    assert int(got.lengths.max()) == 9


def test_survivor_buffer_overflow_reruns_exactly(monkeypatch):
    """A survivor buffer far too small: each wave's tail reruns with a
    buffer that holds its survivors, ``tail_retries`` counts the reruns, the
    larger buffer sticks, and the result stays exact."""
    monkeypatch.setattr(executor, "_TAIL_SHARE", 1 << 20)   # 8-row buffers
    rng = np.random.default_rng(11)
    quotes = [rng.integers(1, BIG_VOCAB + 1, n) for n in (8, 12)]
    toks = corpus(120, BIG_VOCAB, seed=11, quotes=quotes, every=3)
    cfg = NGramConfig(sigma=10, tau=3, vocab_size=BIG_VOCAB)
    ex = WaveExecutor(cfg, wave_tokens=-(-toks.size // 4))
    got = ex.run(toks)
    assert got.counters["tail_retries"] >= 1
    assert got.counters["tail_positions"] > 8
    assert ex._tail_scale > 1
    assert as_dict(got) == oracle.ngram_counts(toks, 10, 3)
    again = ex.run(toks)                 # the buffer that held them sticks
    assert again.counters["tail_retries"] == 0
    assert_same_stats(again, got)


def test_no_frequent_head_leaves_the_tail_empty(monkeypatch):
    """No head reaches tau: the tail pass does not run, and the output is
    the head pass's alone."""
    toks = corpus(80, BIG_VOCAB, seed=3)
    cfg = NGramConfig(sigma=8, tau=4, vocab_size=BIG_VOCAB)
    got, plain = run_both(toks, cfg, -(-toks.size // 3), monkeypatch)
    assert got.counters["head_dict_rows"] == 0
    assert got.counters["tail_positions"] == 0
    assert_same_stats(got, plain)
    assert as_dict(got) == oracle.ngram_counts(toks, 8, 4)
    assert np.asarray(got.grams).shape[1] == 8


def test_split_engages_by_record_width_only_without_a_mesh():
    """The split follows the lanes that sigma and the vocabulary give: at
    the language-model job's five lanes it never engages, nor with a mesh,
    whose waves keep one full-width pass."""
    wide = NGramConfig(sigma=HEAD_LANES + 1, tau=2, vocab_size=BIG_VOCAB)
    assert WaveExecutor(wide, wave_tokens=64)._head_ex is not None
    # 10-bit terms pack three to a lane: sigma 9 is three lanes, no split
    assert WaveExecutor(NGramConfig(sigma=9, tau=2, vocab_size=1000),
                        wave_tokens=64)._head_ex is None
    head = WaveExecutor(NGramConfig(sigma=10, tau=2, vocab_size=1000),
                        wave_tokens=64)._head_ex
    assert head.cfg.sigma == 3 * HEAD_LANES
    mesh = types.SimpleNamespace(size=4)
    assert WaveExecutor(wide, wave_tokens=64, mesh=mesh)._head_ex is None


def test_default_head_width_splits_the_analytics_job_only(monkeypatch):
    """At the default head width the language-model job (sigma 5, 19-bit
    terms: five lanes) keeps its one wave pass, and the analytics job
    (sigma 100, 20-bit terms: 100 lanes) splits at that many terms."""
    monkeypatch.setattr(executor, "SPLIT_HEAD_LANES", DEFAULT_HEAD_LANES)
    lm = NGramConfig(sigma=5, tau=10, vocab_size=345_827)
    assert WaveExecutor(lm, wave_tokens=1 << 10)._head_ex is None
    cw = NGramConfig(sigma=100, tau=100, vocab_size=979_935)
    head = WaveExecutor(cw, wave_tokens=1 << 10)._head_ex
    assert head.cfg.sigma == DEFAULT_HEAD_LANES < 100


def test_split_on_the_device_finalize_route(monkeypatch):
    """Both passes fold through the blocked device finalize (the chip's
    route), blocks shrunk so each fold spans several: the same statistics
    as the host k-way route and the oracle."""
    from repro.index import merge as merge_mod
    monkeypatch.setattr(merge_mod, "DEVICE_BLOCK_ROWS", 64)
    rng = np.random.default_rng(13)
    quotes = [rng.integers(1, BIG_VOCAB + 1, n) for n in (6, 11)]
    toks = corpus(100, BIG_VOCAB, seed=13, quotes=quotes, every=2)
    cfg = NGramConfig(sigma=10, tau=3, vocab_size=BIG_VOCAB)
    wave = -(-toks.size // 4)
    dev = WaveExecutor(cfg, wave_tokens=wave, merge_route="device").run(toks)
    kway = WaveExecutor(cfg, wave_tokens=wave, merge_route="kway").run(toks)
    assert_same_stats(dev, kway)
    assert as_dict(dev) == oracle.ngram_counts(toks, 10, 3)
    assert dev.counters["finalize_blocks"] > 2
    assert dev.counters["tail_positions"] > 0


def test_streaming_service_ingests_through_the_split():
    """``StreamingNGramService.ingest`` with waves takes the split like any
    caller of ``WaveExecutor.run``: every delta's frequent grams, long ones
    included, are served with their counts."""
    from repro.serve.service import StreamingNGramService
    rng = np.random.default_rng(21)
    quote = rng.integers(1, BIG_VOCAB + 1, 9).astype(np.int32)
    cfg = NGramConfig(sigma=9, tau=2, vocab_size=BIG_VOCAB)
    svc = StreamingNGramService(cfg, wave_tokens=300)
    deltas = [corpus(60, BIG_VOCAB, seed=21 + i, quotes=[quote], every=8)
              for i in range(2)]
    for d in deltas:
        report = svc.ingest(d)
        assert report["waves"] > 1
    assert svc._wave_ex._head_ex is not None
    want = {}
    for d in deltas:
        for g, c in oracle.ngram_counts(d, 9, 2).items():
            want[g] = want.get(g, 0) + c
    grams = sorted(want)
    g = np.zeros((len(grams), 9), np.int32)
    ln = np.array([len(t) for t in grams], np.int32)
    for i, t in enumerate(grams):
        g[i, :len(t)] = t
    np.testing.assert_array_equal(svc.lookup(g, ln), [want[t] for t in grams])
    assert want[tuple(quote.tolist())] >= 4
