"""Plain host reference of the n-gram statistics, the index answers and the
comparison that decides ``correct``.

Nothing here imports the program under test.  The counter is the textbook
APRIORI level-wise count (an n-gram can reach ``tau`` only if its
(n-1)-prefix does), on numpy int64 keys: the n-gram starting at position i
is keyed ``id(prefix) * (V + 1) + term``, where ``id(prefix)`` is the dense
rank of its frequent prefix.  No n-gram spans a PAD (0).

Statistics are plain dicts of arrays: ``grams`` [R, sigma] int32 (zero
padded), ``lengths`` [R] int32, ``counts`` [R] int64, in canonical order
(length-major, then terms).
"""
from __future__ import annotations

import numpy as np


def count_ngrams(tokens: np.ndarray, *, sigma: int, tau: int,
                 vocab_size: int) -> dict:
    """Every n-gram (n <= sigma) of ``tokens`` with count >= ``tau``."""
    tok = np.asarray(tokens, np.int64)
    n = tok.size
    base = vocab_size + 1
    grams, lengths, counts = [], [], []
    # level 1: the id of a frequent unigram is its term id
    cf = np.bincount(tok, minlength=base)
    cf[0] = 0
    freq_terms = np.flatnonzero(cf >= tau)
    ids = np.full(base, -1, np.int64)
    ids[freq_terms] = np.arange(freq_terms.size)
    node = ids[tok]                                  # -1: no frequent gram
    rows = freq_terms[:, None].astype(np.int32)
    grams.append(_pad(rows, sigma))
    lengths.append(np.full(freq_terms.size, 1, np.int32))
    counts.append(cf[freq_terms])
    pos = np.flatnonzero(node >= 0)                  # live start positions
    for level in range(2, sigma + 1):
        pos = pos[pos + level - 1 < n]
        nxt = tok[pos + level - 1]
        keep = nxt > 0
        pos, nxt = pos[keep], nxt[keep]
        keys = node[pos] * base + nxt
        uniq, first, inv, cnt = np.unique(keys, return_index=True,
                                          return_inverse=True,
                                          return_counts=True)
        frequent = cnt >= tau
        dense = np.full(uniq.size, -1, np.int64)
        dense[frequent] = np.arange(int(frequent.sum()))
        node = np.full(n, -1, np.int64)
        node[pos] = dense[inv]
        starts = pos[first[frequent]]
        win = tok[starts[:, None] + np.arange(level)[None, :]]
        grams.append(_pad(win.astype(np.int32), sigma))
        lengths.append(np.full(starts.size, level, np.int32))
        counts.append(cnt[frequent].astype(np.int64))
        pos = pos[node[pos] >= 0]
        if pos.size == 0:
            break
    return canonical(np.concatenate(grams), np.concatenate(lengths),
                     np.concatenate(counts))


def _pad(rows: np.ndarray, sigma: int) -> np.ndarray:
    out = np.zeros((rows.shape[0], sigma), np.int32)
    out[:, :rows.shape[1]] = rows
    return out


def canonical(grams, lengths, counts) -> dict:
    """Rows sorted by (length, term 1, ..., term sigma)."""
    grams = np.asarray(grams, np.int32)
    lengths = np.asarray(lengths, np.int32)
    counts = np.asarray(counts, np.int64)
    order = np.lexsort(tuple(grams[:, j] for j in
                             range(grams.shape[1] - 1, -1, -1)) + (lengths,))
    return {"grams": grams[order], "lengths": lengths[order],
            "counts": counts[order]}


def union(*parts: dict) -> dict:
    """Per-gram sums over several statistics (the generational index's view:
    each delta's frequent grams, their counts added across deltas)."""
    grams = np.concatenate([p["grams"] for p in parts])
    lengths = np.concatenate([p["lengths"] for p in parts])
    counts = np.concatenate([p["counts"] for p in parts])
    keys = row_keys(grams, lengths)
    uniq, first, inv = np.unique(keys, return_index=True, return_inverse=True)
    summed = np.bincount(inv.ravel(), weights=counts, minlength=uniq.size)
    return canonical(grams[first], lengths[first], summed.astype(np.int64))


def row_keys(grams, lengths=None) -> np.ndarray:
    """[N] byte-comparable keys (big-endian uint32 columns, optionally led by
    a length column): byte order == lexicographic order of the columns."""
    cols = np.asarray(grams, np.int64).astype(np.uint32)
    if lengths is not None:
        cols = np.concatenate(
            [np.asarray(lengths).astype(np.uint32)[:, None], cols], axis=1)
    cols = np.ascontiguousarray(cols.astype(">u4"))
    return cols.view(np.dtype((np.void, 4 * cols.shape[1]))).ravel()


def narrowed(tokens: np.ndarray, bits: int) -> np.ndarray:
    """Term ids folded into ``bits`` bits (PAD stays PAD): what a record
    packed at ``bits`` per term would hold.  The control of the job and
    index checks: a lane narrower than the vocabulary merges terms."""
    t = np.asarray(tokens, np.int64)
    folded = ((t - 1) & ((1 << bits) - 1)) + 1
    return np.where(t == 0, 0, folded).astype(np.int32)


# --------------------------------------------------------------- comparison
def stats_mismatches(got: dict, want: dict) -> int:
    """Rows present on one side only, plus shared rows whose counts differ."""
    g_keys = row_keys(got["grams"], got["lengths"])
    w_keys = row_keys(want["grams"], want["lengths"])
    if g_keys.size == w_keys.size and np.array_equal(g_keys, w_keys):
        return int(np.count_nonzero(np.asarray(got["counts"], np.int64)
                                    != np.asarray(want["counts"], np.int64)))
    common, gi, wi = np.intersect1d(g_keys, w_keys, assume_unique=False,
                                    return_indices=True)
    differ = np.count_nonzero(np.asarray(got["counts"], np.int64)[gi]
                              != np.asarray(want["counts"], np.int64)[wi])
    return int(g_keys.size + w_keys.size - 2 * common.size + differ)


# ------------------------------------------------------------ index answers
class Answers:
    """Exact lookup and top-k answers over one set of statistics."""

    def __init__(self, stats: dict):
        self.sigma = stats["grams"].shape[1]
        keys = row_keys(stats["grams"], stats["lengths"])
        order = np.argsort(keys)
        self._keys = keys[order]
        self._counts = np.asarray(stats["counts"], np.int64)[order]
        # continuation groups: rows keyed by (prefix length, prefix terms),
        # ranked by count descending, then term ascending
        lengths = np.asarray(stats["lengths"])
        rows = np.flatnonzero(lengths >= 1)
        plen = lengths[rows] - 1
        pg = stats["grams"][rows].copy()
        pg[np.arange(rows.size), plen] = 0
        last = stats["grams"][rows, plen].astype(np.int64)
        cnt = np.asarray(stats["counts"], np.int64)[rows]
        pkey = row_keys(pg, plen)
        uniq, inv = np.unique(pkey, return_inverse=True)
        order = np.lexsort((last, -cnt, inv))
        inv_s = inv[order]
        self._groups = uniq
        self._g_start = np.searchsorted(inv_s, np.arange(uniq.size))
        self._g_end = np.searchsorted(inv_s, np.arange(uniq.size), "right")
        self._c_last = last[order]
        self._c_cnt = cnt[order]
        self._c_cum = np.concatenate([[0], np.cumsum(self._c_cnt)])

    def lookup(self, grams, lengths) -> np.ndarray:
        """Counts [Q] of the queried grams (0 where absent)."""
        q = _masked(grams, lengths, self.sigma)
        keys = row_keys(q, lengths)
        pos = np.minimum(np.searchsorted(self._keys, keys),
                         self._keys.size - 1)
        hit = self._keys[pos] == keys
        return np.where(hit, self._counts[pos], 0)

    def max_continuations(self) -> int:
        """The largest number of continuations any prefix has."""
        return int((self._g_end - self._g_start).max(initial=0))

    def topk(self, prefixes, p_len, *, k: int) -> np.ndarray:
        """Rows [Q, 2 + 2k] int64: n_distinct | total | k terms | k counts."""
        q = _masked(prefixes, p_len, self.sigma)
        keys = row_keys(q, p_len)
        gid = np.minimum(np.searchsorted(self._groups, keys),
                         self._groups.size - 1)
        hit = self._groups[gid] == keys
        s = np.where(hit, self._g_start[gid], 0)
        e = np.where(hit, self._g_end[gid], 0)
        out = np.zeros((q.shape[0], 2 + 2 * k), np.int64)
        out[:, 0] = e - s
        out[:, 1] = self._c_cum[e] - self._c_cum[s]
        take = s[:, None] + np.arange(k)[None, :]
        inside = take < e[:, None]
        take = np.minimum(take, max(self._c_last.size - 1, 0))
        if self._c_last.size:
            out[:, 2:2 + k] = np.where(inside, self._c_last[take], 0)
            out[:, 2 + k:] = np.where(inside, self._c_cnt[take], 0)
        return out


def _masked(grams, lengths, sigma: int) -> np.ndarray:
    g = np.asarray(grams, np.int64)[:, :sigma]
    return (g * (np.arange(sigma)[None, :]
                 < np.asarray(lengths)[:, None])).astype(np.int32)
