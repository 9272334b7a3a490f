"""The vectorised corpus generator reproduces its configuration."""
import json

import numpy as np
import pytest

import corpus
from conftest import BENCH

N = 1 << 21


@pytest.fixture(scope="module", params=["nyt-lm"])
def drawn(request):
    cfg = json.loads((BENCH / "configs" / f"{request.param}.json").read_text())
    prof = corpus.profile(cfg)
    return prof, corpus.generate(N, prof, np.random.default_rng(2**40 + 3))


def _sentences(tokens):
    ends = np.flatnonzero(tokens == corpus.PAD)
    starts = np.concatenate([[0], ends[:-1] + 1])
    return [tokens[s:e] for s, e in zip(starts, ends)]


def test_length_and_terms(drawn):
    prof, toks = drawn
    assert toks.size == N and toks[-1] == corpus.PAD
    assert toks.dtype == np.int32
    assert 0 <= toks.min() and toks.max() <= prof["vocab_size"]


def test_sentence_moments_and_duplicate_share(drawn):
    prof, toks = drawn
    sents = _sentences(toks)[:-1]            # the last one is cut to fit
    quotes = {}
    for s in sents:
        if len(s) >= corpus.MIN_QUOTE_LEN:
            quotes[s.tobytes()] = quotes.get(s.tobytes(), 0) + 1
    repeated = {k for k, c in quotes.items() if c >= 20}
    is_dup = np.array([s.tobytes() in repeated for s in sents])
    share = is_dup.mean()
    n = len(sents)
    p = prof["duplicate_frac"]
    assert abs(share - p) < 5 * np.sqrt(p * (1 - p) / n)
    lens = np.array([len(s) for s in sents])[~is_dup]
    mean, std = prof["mean_sentence_len"], prof["std_sentence_len"]
    assert abs(lens.mean() - mean) < 5 * std / np.sqrt(lens.size)
    # the sample standard deviation's own error is about std / sqrt(2n) for
    # a normal law; the negative binomial's heavier tail needs more room
    assert abs(lens.std() - std) < 0.03 * std


def test_unigram_rank_frequency_slope(drawn):
    prof, toks = drawn
    cf = np.bincount(toks, minlength=prof["vocab_size"] + 1)[1:]
    ranks = np.arange(10, 1000)
    slope = np.polyfit(np.log(ranks), np.log(cf[ranks - 1]), 1)[0]
    assert abs(-slope - prof["zipf_a"]) < 0.05


def test_same_seed_same_corpus():
    prof = {"vocab_size": 345827, "zipf_a": 1.2, "mean_sentence_len": 18.96,
            "std_sentence_len": 14.05, "duplicate_frac": 0.02}
    a = corpus.generate(50000, prof, np.random.default_rng(2**33 + 1))
    b = corpus.generate(50000, prof, np.random.default_rng(2**33 + 1))
    assert np.array_equal(a, b)
