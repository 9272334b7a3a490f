"""The plain reference against brute force, and its control."""
import collections

import numpy as np
import pytest

import corpus
import reference as ref

PROF = {"vocab_size": 40, "zipf_a": 1.1, "mean_sentence_len": 6.0,
        "std_sentence_len": 4.0, "duplicate_frac": 0.05}


def brute(tokens, sigma, tau):
    c = collections.Counter()
    for s in np.split(tokens, np.flatnonzero(tokens == 0) + 1):
        s = [int(t) for t in s if t]
        for i in range(len(s)):
            for n in range(1, sigma + 1):
                if i + n <= len(s):
                    c[tuple(s[i:i + n])] += 1
    return {g: n for g, n in c.items() if n >= tau}


def as_dict(st):
    return {tuple(int(t) for t in g[:ln]): int(n) for g, ln, n in
            zip(st["grams"], st["lengths"], st["counts"])}


@pytest.mark.parametrize("seed,sigma,tau", [(1, 5, 2), (2, 3, 4), (3, 5, 1)])
def test_count_matches_brute_force(seed, sigma, tau):
    toks = corpus.generate(3000, PROF, np.random.default_rng(seed))
    st = ref.count_ngrams(toks, sigma=sigma, tau=tau, vocab_size=40)
    assert as_dict(st) == brute(toks, sigma, tau)
    assert ref.stats_mismatches(st, st) == 0


def test_union_and_answers_match_brute_force():
    rng = np.random.default_rng(4)
    parts = [corpus.generate(2000, PROF, rng) for _ in range(3)]
    want = collections.Counter()
    for p in parts:
        want.update(brute(p, 4, 2))
    u = ref.union(*(ref.count_ngrams(p, sigma=4, tau=2, vocab_size=40)
                    for p in parts))
    assert as_dict(u) == dict(want)
    ans = ref.Answers(u)
    grams = list(want)[:50] + [(39, 39, 39, 39)]
    g = np.zeros((len(grams), 4), np.int32)
    ln = np.array([len(x) for x in grams], np.int32)
    for i, x in enumerate(grams):
        g[i, :len(x)] = x
    assert list(ans.lookup(g, ln)) == [want.get(x, 0) for x in grams]
    k = 3
    rows = ans.topk(g[:20], np.maximum(ln[:20] - 1, 1), k=k)
    for (x, r) in zip(grams[:20], rows):
        p = x[:max(len(x) - 1, 1)]
        cont = sorted(((c, t[-1]) for t, c in want.items()
                       if len(t) == len(p) + 1 and t[:-1] == p),
                      key=lambda ct: (-ct[0], ct[1]))
        assert r[0] == len(cont) and r[1] == sum(c for c, _ in cont)
        assert list(r[2:2 + len(cont[:k])]) == [t for _, t in cont[:k]]
        assert list(r[2 + k:2 + k + len(cont[:k])]) == [c for c, _ in
                                                        cont[:k]]


def test_mismatch_count():
    toks = corpus.generate(3000, PROF, np.random.default_rng(5))
    st = ref.count_ngrams(toks, sigma=3, tau=2, vocab_size=40)
    bad = {k: v.copy() for k, v in st.items()}
    bad["counts"][3] += 1
    assert ref.stats_mismatches(bad, st) == 1
    short = {k: v[1:] for k, v in st.items()}
    assert ref.stats_mismatches(short, st) == 1


def test_control_fails_the_job_comparison():
    """The control -- the reference over terms folded into a 16-bit lane,
    in the program's place -- must come out not correct."""
    prof = dict(PROF, vocab_size=345827, zipf_a=1.2)
    toks = corpus.generate(200000, prof, np.random.default_rng(6))
    want = ref.count_ngrams(toks, sigma=5, tau=2, vocab_size=345827)
    control = ref.count_ngrams(ref.narrowed(toks, 16), sigma=5, tau=2,
                               vocab_size=345827)
    assert ref.stats_mismatches(control, want) > 0
