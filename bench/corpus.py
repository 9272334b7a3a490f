"""Vectorised corpus generator: the benchmark's own copy of the Zipf corpus.

The distribution follows the program's ``data/corpus.zipf_corpus``: terms are
drawn i.i.d. from a Zipf law over ``1..vocab_size``, sentences are separated
by PAD (0), and a share of the sentences are verbatim copies of a small pool
of quotations (the long frequent n-grams of the paper's Fig. 2).  Two things
differ, on purpose:

* no per-sentence Python loop: every array is built in bulk, so an
  8,388,608-token corpus takes about a second of set-up;
* sentence lengths are ``1 + NegativeBinomial`` with exactly the configured
  mean and standard deviation (Table I of the paper gives the moments, and a
  clipped normal would not reproduce them).

The stream has exactly ``n_tokens`` tokens and ends with a PAD.
"""
from __future__ import annotations

import zlib

import numpy as np

PAD = 0
N_QUOTES = 12            # size of the quotation pool, as in the program
MIN_QUOTE_LEN = 8


PROFILE_KEYS = ("vocab_size", "zipf_a", "mean_sentence_len",
                "std_sentence_len", "duplicate_frac")


def profile(cfg: dict) -> dict:
    """The corpus keys of a configuration."""
    return {k: cfg[k] for k in PROFILE_KEYS}


def rng_for(seed: int, *stream) -> np.random.Generator:
    """An independent generator per (seed, stream) pair; any whole seed."""
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF]
    words += [zlib.crc32(str(s).encode()) for s in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def zipf_cdf(vocab_size: int, zipf_a: float) -> np.ndarray:
    p = np.arange(1, vocab_size + 1, dtype=np.float64) ** (-zipf_a)
    cdf = np.cumsum(p)
    return cdf / cdf[-1]


def draw_terms(rng: np.random.Generator, cdf: np.ndarray, n: int) -> np.ndarray:
    """``n`` term ids in ``1..len(cdf)``, rank r drawn with p ~ r**-a."""
    ids = np.searchsorted(cdf, rng.random(n), side="right")
    return (np.minimum(ids, cdf.size - 1) + 1).astype(np.int32)


def draw_lengths(rng: np.random.Generator, n: int, mean: float,
                 std: float) -> np.ndarray:
    """Sentence lengths >= 1 with mean ``mean`` and standard deviation
    ``std``: 1 + NB(r, p) with NB's mean mean-1 and variance std**2."""
    m = mean - 1.0
    var = std * std
    if not 0 < m < var:
        raise ValueError(f"need 1 < mean and (mean-1) < std**2, got "
                         f"{mean}, {std}")
    p = m / var
    r = m * p / (1.0 - p)
    return 1 + rng.negative_binomial(r, p, n).astype(np.int64)


def generate(n_tokens: int, profile: dict, rng: np.random.Generator):
    """Exactly ``n_tokens`` int32 tokens ending in PAD.

    ``profile`` keys: ``vocab_size``, ``zipf_a``, ``mean_sentence_len``,
    ``std_sentence_len``, ``duplicate_frac``.  Returns the stream.
    """
    v = int(profile["vocab_size"])
    cdf = zipf_cdf(v, float(profile["zipf_a"]))
    mean = float(profile["mean_sentence_len"])
    std = float(profile["std_sentence_len"])
    dup = float(profile["duplicate_frac"])

    quote_len = np.maximum(draw_lengths(rng, N_QUOTES, mean, std),
                           MIN_QUOTE_LEN)
    quotes = draw_terms(rng, cdf, int(quote_len.sum()))
    quote_off = np.concatenate([[0], np.cumsum(quote_len)[:-1]])

    # enough sentences to cover the stream (each takes its length + 1 PAD)
    n_sent = int(n_tokens / (mean + 1) * 1.5) + 64
    lens = draw_lengths(rng, n_sent, mean, std)
    is_dup = rng.random(n_sent) < dup
    which = rng.integers(0, N_QUOTES, n_sent)
    lens = np.where(is_dup, quote_len[which], lens)
    ends = np.cumsum(lens + 1)
    if ends[-1] < n_tokens:       # 50% head-room: tens of sigmas at any size
        raise RuntimeError("sentence draw fell short of the stream length")
    n_sent = int(np.searchsorted(ends, n_tokens)) + 1
    lens, is_dup, which = lens[:n_sent], is_dup[:n_sent], which[:n_sent]
    starts = np.concatenate([[0], np.cumsum(lens + 1)[:-1]])
    total = int(starts[-1] + lens[-1] + 1)

    sent = np.repeat(np.arange(n_sent), lens + 1)
    off = np.arange(total) - starts[sent]
    pad = off == lens[sent]
    from_quote = is_dup[sent] & ~pad
    fresh = ~is_dup[sent] & ~pad
    out = np.zeros(total, np.int32)
    out[fresh] = draw_terms(rng, cdf, int(fresh.sum()))
    q = quote_off[which[sent[from_quote]]] + off[from_quote]
    out[from_quote] = quotes[q]
    out = out[:n_tokens]
    out[-1] = PAD
    return out
