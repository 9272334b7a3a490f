"""Differential parity harness for the compressed index layout.

The compressed layout (``repro.index.compress``) is a pure re-encoding, so the
bar is *bit-exact* agreement -- no tolerance -- along three axes:

  structural : Elias-Fano select / decode_all round-trip every encoded value;
               front-coded blocks decode back to the exact term matrices
  functional : ``lookup`` / ``continuations`` answers on the compressed index
               == uncompressed index == pure-Python oracle, over hit-heavy,
               miss-heavy, malformed, duplicate, and empty-prefix batches
  kernel     : the Pallas ``block_decode`` route agrees with the jnp ref route
               on every one of those batches (per-kernel randomized sweeps live
               in test_kernels.py)

Corpus generation is hypothesis-driven where available (vocab 2..5k, zipf and
uniform token sources) and degrades to the same generator over fixed
parametrized draws without it.  The >=100k-token acceptance corpus runs in the
slow tier (``-m "not slow"`` skips it).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.core import oracle, run_job
from repro.core.stats import NGramConfig, NGramStats
from repro.data import corpus as corpus_mod
from repro.index import build_index, compress_index, continuations, lookup
from repro.index.compress import EliasFano, decode_view
from repro.mapreduce import pack as packing


def grams_matrix(gram_tuples, sigma):
    g = np.zeros((len(gram_tuples), sigma), np.int32)
    ln = np.zeros(len(gram_tuples), np.int32)
    for i, t in enumerate(gram_tuples):
        g[i, : len(t)] = t
        ln[i] = len(t)
    return g, ln


def make_corpus(n_tokens: int, vocab: int, dist: str, seed: int) -> np.ndarray:
    """Token stream with PAD separators; zipf or uniform term source."""
    rng = np.random.default_rng(seed)
    if dist == "zipf":
        p = np.arange(1, vocab + 1, dtype=np.float64) ** -1.3
        p /= p.sum()
        toks = rng.choice(vocab, size=n_tokens, p=p).astype(np.int32) + 1
    else:
        toks = rng.integers(1, vocab + 1, n_tokens).astype(np.int32)
    toks[rng.random(n_tokens) < 0.05] = 0            # sentence separators
    return toks


def query_batches(exp, idx, rng):
    """(grams, lengths, expected) triples covering the paper-worthy mixes."""
    sigma, vocab = idx.sigma, idx.vocab_size
    batches = []
    gram_tuples = sorted(exp)
    if gram_tuples:
        g, ln = grams_matrix(gram_tuples, sigma)
        batches.append((g, ln, np.array([exp[t] for t in gram_tuples])))
    # miss-heavy + malformed (len 0, len > sigma, out-of-vocab, PAD inside)
    n = 600
    lnm = rng.integers(0, sigma + 3, n).astype(np.int32)
    gm = rng.integers(0, vocab + 4, (n, sigma)).astype(np.int32)
    gm *= np.arange(sigma)[None, :] < lnm[:, None]
    gm[: n // 10, 0] = 0                              # PAD at the lead
    want = np.array([
        exp.get(tuple(int(x) for x in r[:l]), 0)
        if 1 <= l <= sigma and all(1 <= int(x) <= vocab for x in r[:l]) else 0
        for r, l in zip(gm, lnm)])
    batches.append((gm, lnm, want))
    # duplicate-query batch: same rows repeated, answers must repeat too
    if gram_tuples:
        picks = rng.choice(len(gram_tuples), 40)
        dup = [gram_tuples[i] for i in picks] * 3
        g, ln = grams_matrix(dup, sigma)
        batches.append((g, ln, np.array([exp[t] for t in dup])))
    return batches


def assert_index_parity(exp, idx, cidx, seed=0, k=8):
    """The whole differential contract for one (corpus, layout) pair."""
    rng = np.random.default_rng(seed)
    for g, ln, want in query_batches(exp, idx, rng):
        got_u = np.asarray(lookup(idx, g, ln))
        np.testing.assert_array_equal(got_u, want)
        for uk in (False, True):
            got_c = np.asarray(lookup(cidx, g, ln, use_kernels=uk))
            np.testing.assert_array_equal(got_c, want)

    # continuations: empty prefix + real prefixes + junk prefixes, duplicated
    sigma = idx.sigma
    pool = sorted({t[:-1] for t in exp if len(t) >= 2})
    picks = [pool[i] for i in rng.choice(len(pool), min(30, len(pool)))] \
        if pool else []
    prefixes = [(), ()] + picks + [(idx.vocab_size + 2,)] + picks[:5]
    pg, pl = grams_matrix(prefixes, sigma)
    res_u = [np.asarray(x) for x in continuations(idx, pg, pl, k=k)]
    for uk in (False, True):
        res_c = [np.asarray(x) for x in continuations(cidx, pg, pl, k=k,
                                                      use_kernels=uk)]
        for a, b in zip(res_u, res_c):
            np.testing.assert_array_equal(a, b)
    # and the uncompressed reference itself against the oracle
    for i, p in enumerate(prefixes):
        ext = {t[-1]: c for t, c in exp.items()
               if len(t) == len(p) + 1 and t[: len(p)] == p}
        assert res_u[0][i] == len(ext)
        assert res_u[1][i] == sum(ext.values())
        got = [int(c) for c in res_u[3][i] if c > 0]
        assert got == sorted(ext.values(), reverse=True)[:k]


def assert_structural(idx, cidx):
    """Lossless re-encoding: EF values and term matrices round-trip exactly."""
    import jax.numpy as jnp
    for ef, want in (
        (cidx.ef_section, np.asarray(idx.section_start)),
        (cidx.ef_cont_fanout, np.asarray(idx.cont_fanout).reshape(-1)),
        (cidx.ef_cumsum, np.asarray(idx.cont_cumsum)),
    ):
        idxs = jnp.arange(ef.n)
        np.testing.assert_array_equal(np.asarray(ef.select(idxs)),
                                      want.astype(np.uint32))
        np.testing.assert_array_equal(np.asarray(ef.decode_all()),
                                      want.astype(np.uint32))
    # the decoded query caches are pure functions of the same structures
    np.testing.assert_array_equal(np.asarray(cidx.sec_cache),
                                  np.asarray(idx.section_start, np.int32))
    np.testing.assert_array_equal(np.asarray(cidx.cumsum_cache),
                                  np.asarray(idx.cont_cumsum, np.uint32))
    np.testing.assert_array_equal(
        np.asarray(cidx.fan_cache, np.int64),
        np.asarray(idx.fanout, np.int64).reshape(-1) // cidx.block_size)
    np.testing.assert_array_equal(
        np.asarray(cidx.cont_fan_cache, np.int64),
        np.asarray(idx.cont_fanout, np.int64).reshape(-1) // cidx.block_size)
    sigma, vocab = idx.sigma, idx.vocab_size
    sec = np.asarray(idx.section_start)
    row_len = np.searchsorted(sec, np.arange(idx.size), side="right")
    for view, lanes, off in (("point", idx.lanes, 0),
                             ("cont", idx.cont_prefix, 1)):
        terms = np.asarray(packing.unpack_terms(
            lanes, vocab_size=vocab, sigma=sigma))
        keep = np.arange(sigma)[None, :] < np.clip(row_len - off, 0,
                                                   sigma)[:, None]
        np.testing.assert_array_equal(decode_view(cidx, view),
                                      np.where(keep, terms, 0))


# --------------------------------------------------------------------------- #
# fast tier: small corpora, every dist/vocab/block-size corner
# --------------------------------------------------------------------------- #

CORPUS_DRAWS = [  # (vocab, dist, sigma, tau, block_size, seed)
    (5, "uniform", 3, 2, 4, 0),
    (40, "zipf", 5, 2, 4, 1),
    (40, "zipf", 5, 2, 16, 1),      # same corpus, different block geometry
    (700, "uniform", 4, 3, 8, 2),
    (5000, "zipf", 4, 2, 4, 3),
]


@pytest.mark.parametrize("vocab,dist,sigma,tau,block,seed", CORPUS_DRAWS)
def test_parity_generated_corpora(vocab, dist, sigma, tau, block, seed):
    toks = make_corpus(5000, vocab, dist, seed)
    stats = run_job(toks, NGramConfig(sigma=sigma, tau=tau, vocab_size=vocab))
    exp = oracle.ngram_counts(toks, sigma, tau)
    idx = build_index(stats, vocab_size=vocab)
    cidx = compress_index(idx, block_size=block)
    assert_structural(idx, cidx)
    assert_index_parity(exp, idx, cidx, seed=seed)


def test_empty_and_tiny_compressed_index():
    empty = NGramStats(np.zeros((0, 3), np.int32), np.zeros(0, np.int32),
                       np.zeros(0, np.int64))
    idx = build_index(empty, vocab_size=10)
    cidx = compress_index(idx)
    assert_structural(idx, cidx)
    assert_index_parity({}, idx, cidx)
    one = NGramStats(np.array([[5, 0, 0]], np.int32), np.array([1], np.int32),
                     np.array([7], np.int64))
    idx1 = build_index(one, vocab_size=10)
    cidx1 = compress_index(idx1)
    assert_index_parity({(5,): 7}, idx1, cidx1)


def test_huge_counts_need_full_width():
    """A single cf >= 2^31 forces count_width=32; the packer must take it."""
    big = np.uint32(2**31 + 5)
    stats = NGramStats(np.array([[5, 0, 0], [6, 0, 0]], np.int32),
                       np.array([1, 1], np.int32),
                       np.array([int(big), 7], np.int64))
    idx = build_index(stats, vocab_size=10)
    cidx = compress_index(idx)
    assert cidx.count_width == 32
    assert_index_parity({(5,): int(big), (6,): 7}, idx, cidx)


def test_compressed_lookup_decodes_one_block_per_query(monkeypatch):
    """A compressed lookup batch decodes exactly one block of
    ``block_size`` rows per query -- never the whole table -- and answers
    like the flat index.  Every front-coded decode (the lookup's rank and
    the table-wide ``decode_segment`` alike) runs through
    ``block_expand_ref``, which is counted here with jit off."""
    import jax
    from repro.kernels import ref as kref

    cfg = NGramConfig(sigma=3, tau=1, vocab_size=60)
    stats = run_job(make_corpus(3000, 60, "zipf", 17), cfg)
    idx = build_index(stats, vocab_size=60)
    cidx = compress_index(idx, block_size=8)
    pick = np.arange(64) * (len(stats) // 64)  # 64 hits across the table
    g, ln = np.asarray(stats.grams)[pick], np.asarray(stats.lengths)[pick]
    q = g.shape[0]
    assert q == 64 and q * cidx.block_size < cidx.n_rows

    decoded = []
    expand = kref.block_expand_ref

    def counted(lcps, payload, block_base, sec, blk, *, block_size, **kw):
        decoded.append(int(blk.shape[0]) * block_size)
        return expand(lcps, payload, block_base, sec, blk,
                      block_size=block_size, **kw)

    monkeypatch.setattr(kref, "block_expand_ref", counted)
    with jax.disable_jit():
        got = np.asarray(lookup(cidx, g, ln))
    assert decoded == [q * cidx.block_size]
    np.testing.assert_array_equal(got, np.asarray(stats.counts)[pick])


def test_elias_fano_adversarial_sequences():
    rng = np.random.default_rng(0)
    seqs = [
        np.zeros(5, np.int64),                        # all equal (all zeros)
        np.full(7, 1000, np.int64),                   # all equal, large
        np.arange(100, dtype=np.int64),               # dense
        np.sort(rng.integers(0, 2**31 - 1, 1000)),    # sparse, huge universe
        np.repeat(rng.integers(0, 50, 20).cumsum(), rng.integers(1, 5, 20)),
    ]
    import jax.numpy as jnp
    for s in seqs:
        for universe in (None, int(s.max()) * 2 + 10):
            ef = EliasFano.encode(s, universe=universe)
            np.testing.assert_array_equal(
                np.asarray(ef.select(jnp.arange(ef.n))), s.astype(np.uint32))
            np.testing.assert_array_equal(
                np.asarray(ef.decode_all()), s.astype(np.uint32))
    with pytest.raises(ValueError):
        EliasFano.encode(np.array([3, 2, 1]))
    with pytest.raises(ValueError):
        EliasFano.encode(np.array([], np.int64))


if HAS_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(vocab=st.integers(2, 5000),
           dist=st.sampled_from(["zipf", "uniform"]),
           sigma=st.integers(1, 6), tau=st.integers(1, 4),
           block=st.sampled_from([4, 8, 16]),
           seed=st.integers(0, 2**16))
    def test_parity_hypothesis(vocab, dist, sigma, tau, block, seed):
        toks = make_corpus(2500, vocab, dist, seed)
        stats = run_job(toks, NGramConfig(sigma=sigma, tau=tau,
                                          vocab_size=vocab))
        exp = oracle.ngram_counts(toks, sigma, tau)
        idx = build_index(stats, vocab_size=vocab)
        cidx = compress_index(idx, block_size=block)
        assert_index_parity(exp, idx, cidx, seed=seed)


# --------------------------------------------------------------------------- #
# slow tier: acceptance-sized corpus + the size contract
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def big_corpus_index():
    """>=100k tokens through job -> uncompressed + compressed index."""
    prof = corpus_mod.NYT
    toks = corpus_mod.zipf_corpus(110_000, prof, seed=7, duplicate_frac=0.05)
    sigma, tau = 4, 4
    stats = run_job(toks, NGramConfig(sigma=sigma, tau=tau,
                                      vocab_size=prof.vocab_size))
    exp = oracle.ngram_counts(toks, sigma, tau)
    idx = build_index(stats, vocab_size=prof.vocab_size)
    return exp, idx, compress_index(idx)


@pytest.mark.slow
def test_big_corpus_parity(big_corpus_index):
    exp, idx, cidx = big_corpus_index
    assert_structural(idx, cidx)
    assert_index_parity(exp, idx, cidx, seed=11)


@pytest.mark.slow
def test_compression_ratio_contract(big_corpus_index):
    """The acceptance bar: >= 2x smaller on a zipf corpus at default settings.

    The contract is on the *at-rest* artifact (streams + EF directories); the
    resident footprint additionally carries the decoded query caches, which
    must stay bounded relative to the at-rest bytes."""
    _, idx, cidx = big_corpus_index
    assert cidx.size == idx.size
    assert idx.nbytes / cidx.nbytes_at_rest >= 2.0, \
        (idx.nbytes, cidx.nbytes_at_rest)
    assert cidx.nbytes_at_rest < cidx.nbytes <= 2 * cidx.nbytes_at_rest, \
        (cidx.nbytes, cidx.nbytes_at_rest)
