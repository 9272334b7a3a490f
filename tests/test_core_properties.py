"""Hypothesis property tests on the system's invariants."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import NGramConfig, extensions_filter, oracle, run_job, suffix_sigma
from repro.data import corpus as corpus_mod
from repro.mapreduce import pack as packing

corpora = st.lists(st.integers(0, 12), min_size=1, max_size=200).map(
    lambda xs: np.asarray(xs, np.int32))


@settings(max_examples=25, deadline=None)
@given(toks=corpora, sigma=st.integers(1, 6), tau=st.integers(1, 4))
def test_suffix_sigma_equals_oracle(toks, sigma, tau):
    cfg = NGramConfig(sigma=sigma, tau=tau, vocab_size=12)
    assert run_job(toks, cfg).to_dict() == oracle.ngram_counts(toks, sigma, tau)


@settings(max_examples=25, deadline=None)
@given(toks=corpora, sigma=st.integers(1, 5))
def test_apriori_monotonicity(toks, sigma):
    """cf(r) >= cf(s) for every prefix r of s -- the APRIORI principle the
    methods rely on for pruning and document splitting."""
    counts = oracle.ngram_counts(toks, sigma, 1)
    for g, c in counts.items():
        for l in range(1, len(g)):
            assert counts[g[:l]] >= c


@settings(max_examples=20, deadline=None)
@given(toks=corpora, tau=st.integers(1, 4), sigma=st.integers(1, 5))
def test_document_splitting_preserves_output(toks, tau, sigma):
    """SSV: masking infrequent terms never changes the frequent n-grams."""
    cfg = NGramConfig(sigma=sigma, tau=tau, vocab_size=12)
    base = run_job(toks, cfg).to_dict()
    split, _ = corpus_mod.split_at_infrequent(toks, tau, 12)
    assert run_job(split, cfg).to_dict() == base


@settings(max_examples=20, deadline=None)
@given(toks=corpora, tau=st.integers(1, 3))
def test_maximal_closed_are_subsets(toks, tau):
    cfg = NGramConfig(sigma=4, tau=tau, vocab_size=12)
    stats = run_job(toks, cfg)
    full = stats.to_dict()
    mx = extensions_filter(stats, "max").to_dict()
    cl = extensions_filter(stats, "closed").to_dict()
    assert set(mx) <= set(full) and set(cl) <= set(full)
    assert set(mx) <= set(cl)  # maximal implies closed... (superset dir: closed set contains maximal)
    assert mx == oracle.maximal_ngrams(full)
    assert cl == oracle.closed_ngrams(full)


@settings(max_examples=30, deadline=None)
@given(terms=st.lists(st.lists(st.integers(0, 200), min_size=1, max_size=7),
                      min_size=1, max_size=20),
       vocab=st.integers(200, 70000))
def test_pack_unpack_roundtrip(terms, vocab):
    sigma = max(len(t) for t in terms)
    mat = np.zeros((len(terms), sigma), np.int32)
    for i, t in enumerate(terms):
        mat[i, : len(t)] = t
    lanes = packing.pack_terms(np.asarray(mat), vocab_size=vocab)
    back = packing.unpack_terms(lanes, vocab_size=vocab, sigma=sigma)
    assert np.array_equal(np.asarray(back), mat)


@settings(max_examples=20, deadline=None)
@given(rows=st.lists(st.lists(st.integers(0, 6), min_size=3, max_size=3),
                     min_size=2, max_size=40))
def test_packed_sort_is_lexicographic(rows):
    mat = np.asarray(rows, np.int32)
    lanes = packing.pack_terms(mat, vocab_size=6)
    import jax.numpy as jnp
    from repro.mapreduce import sort
    rec = jnp.concatenate([jnp.asarray(lanes),
                           jnp.zeros((mat.shape[0], 1), jnp.uint32)], axis=1)
    out = sort.sort_records(rec, n_keys=lanes.shape[1])
    back = packing.unpack_terms(out[:, :lanes.shape[1]], vocab_size=6, sigma=3)
    py = sorted(map(tuple, mat.tolist()))
    assert [tuple(r) for r in np.asarray(back).tolist()] == py


@pytest.mark.parametrize("width", [5, 15, 17, 40, 101])
def test_wide_sorts_are_lexicographic(width):
    """Records wider than one ``lax.sort`` takes sort through the permutation
    of stable passes: the same rows in the same order as numpy's lexsort,
    with the payload lane riding along, at every width."""
    import jax.numpy as jnp
    from repro.mapreduce import sort
    rng = np.random.default_rng(width)
    keys = rng.integers(0, 3, (300, width)).astype(np.uint32)
    keys[::7] = keys[3]                    # runs of equal rows
    payload = np.arange(300, dtype=np.uint32)
    rec = np.concatenate([keys, payload[:, None]], axis=1)
    want = np.lexsort(tuple(keys[:, j] for j in range(width - 1, -1, -1)))
    out = np.asarray(sort.sort_records(jnp.asarray(rec), n_keys=width))
    np.testing.assert_array_equal(out[:, :width], keys[want])
    np.testing.assert_array_equal(np.sort(out[:, width]), payload)
    cols = sort.sort_columns([jnp.asarray(keys[:, j]) for j in range(width)]
                             + [jnp.asarray(payload)], num_keys=width)
    np.testing.assert_array_equal(
        np.stack([np.asarray(c) for c in cols[:width]], axis=1), keys[want])
    if width + 1 > sort._SORT_OPERANDS:    # the permutation path is stable
        np.testing.assert_array_equal(np.asarray(cols[-1]), want)


@settings(max_examples=15, deadline=None)
@given(toks=corpora, n_buckets=st.integers(1, 5))
def test_series_sums_to_counts(toks, n_buckets):
    """Time-series aggregation marginalizes to plain collection frequencies."""
    rng = np.random.default_rng(0)
    buckets = rng.integers(0, n_buckets, toks.shape[0])
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=12, n_buckets=n_buckets)
    st_ = suffix_sigma.run(toks, cfg, bucket_ids=buckets)
    plain = run_job(toks, NGramConfig(sigma=3, tau=2, vocab_size=12)).to_dict()
    assert {g: int(s.sum()) for g, s in st_.to_series_dict().items()} == plain
