"""Differential suite for segment merge + the generational (LSM) index.

The contract is the strongest one available: ``merge(build(A), build(B))`` must
be *bit-identical* -- every pytree leaf -- to ``build(A ∪ B)`` (dedup-summed
union), for both layouts and both merge routes, because ``index_from_segment``
is shared and the continuation order is a pure function of the row set.  On
top: the uint32 overflow guard trips loudly, the generational index answers
queries over >=3 ingests (with compactions) exactly like a from-scratch build,
and the streaming-serving pieces (LRU cache, double-buffered driver) behave.

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

import jax

from repro.core import oracle, run_job
from repro.core.stats import NGramConfig, NGramStats
from repro.index import (GenerationalIndex, build_compressed_index,
                         build_index, continuations, generational_from_stats,
                         lookup, merge_indexes, merge_segments,
                         segment_to_stats, stats_union)
from repro.index.build import IndexSegment, segment_from_stats
from tests.test_compress import make_corpus


def assert_trees_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def job_pair(vocab, dist, sigma, tau, seed, n=2500):
    cfg = NGramConfig(sigma=sigma, tau=tau, vocab_size=vocab)
    sa = run_job(make_corpus(n, vocab, dist, seed), cfg)
    sb = run_job(make_corpus(n, vocab, dist, seed + 1000), cfg)
    return sa, sb


def check_merge_parity(sa, sb, vocab, *, block=4):
    union = stats_union(sa, sb)
    # flat: both routes
    want = build_index(union, vocab_size=vocab)
    for kw in (dict(route="kway"), dict(route="device")):
        got = merge_indexes([build_index(sa, vocab_size=vocab),
                             build_index(sb, vocab_size=vocab)], **kw)
        assert_trees_equal(got, want)
    # compressed layout, same bar
    cwant = build_compressed_index(union, vocab_size=vocab, block_size=block)
    cgot = merge_indexes(
        [build_compressed_index(sa, vocab_size=vocab, block_size=block),
         build_compressed_index(sb, vocab_size=vocab, block_size=block)])
    assert_trees_equal(cgot, cwant)


MERGE_DRAWS = [  # (vocab, dist, sigma, tau, seed)
    (5, "uniform", 3, 1, 0),
    (40, "zipf", 5, 2, 1),
    (700, "uniform", 4, 1, 2),
    (5000, "zipf", 4, 2, 3),
]


@pytest.mark.parametrize("vocab,dist,sigma,tau,seed", MERGE_DRAWS)
def test_merge_parity_generated_corpora(vocab, dist, sigma, tau, seed):
    sa, sb = job_pair(vocab, dist, sigma, tau, seed)
    check_merge_parity(sa, sb, vocab)


def test_kway_merge_and_edge_segments():
    """3-way merge == union build; empty and singleton segments fold away."""
    vocab = 30
    cfg = NGramConfig(sigma=3, tau=1, vocab_size=vocab)
    stats = [run_job(make_corpus(800, vocab, "zipf", s), cfg)
             for s in range(3)]
    empty = NGramStats(np.zeros((0, 3), np.int32), np.zeros(0, np.int32),
                       np.zeros(0, np.int64))
    ixs = [build_index(s, vocab_size=vocab) for s in stats]
    ixs.append(build_index(empty, vocab_size=vocab))
    want = build_index(stats_union(*stats), vocab_size=vocab)
    for kw in (dict(route="kway"), dict(route="device")):
        assert_trees_equal(merge_indexes(ixs, **kw), want)


def test_merge_validation_errors():
    a = segment_from_stats(NGramStats(np.array([[1, 0]], np.int32),
                                      np.array([1], np.int32),
                                      np.array([3], np.int64)), vocab_size=9)
    b = segment_from_stats(NGramStats(np.array([[1, 0, 0]], np.int32),
                                      np.array([1], np.int32),
                                      np.array([3], np.int64)), vocab_size=9)
    with pytest.raises(ValueError):
        merge_segments([])
    with pytest.raises(ValueError):
        merge_segments([a, b])             # sigma mismatch
    with pytest.raises(ValueError):
        merge_segments([a], route="bogus")
    s = NGramStats(np.array([[1, 0]], np.int32), np.array([1], np.int32),
                   np.array([3], np.int64))
    with pytest.raises(ValueError):        # mixed layouts
        merge_indexes([build_index(s, vocab_size=9),
                       build_compressed_index(s, vocab_size=9)])


def test_merged_count_overflow_guard_trips():
    """Summed uint32 counts past 2^32 must refuse loudly, not wrap."""
    big = 2**31 + 5                        # fits uint32 alone, wraps summed
    mk = lambda: NGramStats(np.array([[7, 0, 0]], np.int32),
                            np.array([1], np.int32),
                            np.array([big], np.int64))
    segs = [segment_from_stats(mk(), vocab_size=9) for _ in range(2)]
    for kw in (dict(route="kway"), dict(route="device")):
        with pytest.raises(ValueError, match="overflow"):
            merge_segments(segs, **kw)
    # just-below-the-edge sums must still merge exactly
    small = NGramStats(np.array([[7, 0, 0]], np.int32),
                       np.array([1], np.int32), np.array([10], np.int64))
    seg = merge_segments([segs[0], segment_from_stats(small, vocab_size=9)])
    assert np.asarray(seg.counts)[0] == np.uint32(big + 10)


@pytest.mark.parametrize("route", ["device"])
def test_device_fold_host_fallback_parity(monkeypatch, route):
    """Runs longer than the limbed device budget must replay on the host
    with identical output: force the fallback by shrinking the threshold and
    compare whole segments against every block's device fold."""
    from repro.index import merge as merge_mod

    sa, sb = job_pair(40, "zipf", 4, 2, seed=3, n=1500)
    segs = [segment_from_stats(s, vocab_size=40) for s in (sa, sb)]
    want = merge_segments(segs, route=route)           # device fold
    monkeypatch.setattr(merge_mod, "_MAX_DEVICE_RUN", 1)
    got = merge_segments(segs, route=route)            # host replay
    np.testing.assert_array_equal(np.asarray(got.keys), np.asarray(want.keys))
    np.testing.assert_array_equal(np.asarray(got.counts),
                                  np.asarray(want.counts))


def test_device_merge_route_many_blocks(monkeypatch):
    """The ``device`` route's blocked fold: with ``DEVICE_BLOCK_ROWS`` shrunk,
    a merge spans many blocks, one of them cut again (a segment dense in one
    key range overfills the block its splitters gave it), every copy of a
    gram lands in one block, and the result equals the host k-way fold at
    every ``min_count``; the accumulator counts the blocks it ran."""
    from repro.index import merge as merge_mod
    from repro.index.merge import DeferredSegmentAccumulator

    vocab = 30
    cfg = NGramConfig(sigma=3, tau=1, vocab_size=vocab)
    stats = [run_job(make_corpus(900, vocab, "zipf", s), cfg)
             for s in range(3)]
    segs = [segment_from_stats(s, vocab_size=vocab) for s in stats]
    # the grams of the densest length-1 range, repeated in a fourth segment
    # with counts of its own: its rows all fall into the first blocks
    dense = NGramStats(stats[0].grams[stats[0].lengths == 1],
                       stats[0].lengths[stats[0].lengths == 1],
                       stats[0].counts[stats[0].lengths == 1] + 7)
    segs.append(segment_from_stats(dense, vocab_size=vocab))
    rows = 16
    monkeypatch.setattr(merge_mod, "DEVICE_BLOCK_ROWS", rows)
    views = [np.asarray(s.keys)[:s.n_rows] for s in segs]
    blocks = merge_mod._block_cuts(views, rows)
    total = sum(len(v) for v in views)
    assert len(blocks) > -(-total // (rows - rows // 16))   # some cut again
    seen, last = 0, None
    for blk in blocks:
        n = sum(hi - lo for lo, hi in blk)
        assert 0 < n <= rows
        seen += n
        keys = [tuple(r) for v, (lo, hi) in zip(views, blk)
                for r in v[lo:hi].tolist()]
        # blocks are disjoint key ranges in order: a gram never straddles
        assert last is None or min(keys) > last
        last = max(keys)
    assert seen == total
    for min_count in (None, 2, 10):
        want = merge_segments(segs, route="kway", min_count=min_count)
        got = merge_segments(segs, route="device", min_count=min_count)
        np.testing.assert_array_equal(np.asarray(got.keys),
                                      np.asarray(want.keys))
        np.testing.assert_array_equal(np.asarray(got.counts),
                                      np.asarray(want.counts))
    acc = DeferredSegmentAccumulator(route="device")
    for seg in segs:
        acc.push(seg)
    acc.result(min_count=10)
    assert acc.finalize_blocks == len(blocks)
    assert acc.fold_rows == total


def test_device_block_rows_follow_the_key_width(monkeypatch):
    """On an accelerator a block of six-column keys (the sigma-5 job's)
    holds exactly 2**24 rows whatever the input's size; wider keys get
    fewer rows under the same byte budget, one shape per width."""
    from repro.index import merge as merge_mod
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert merge_mod._block_rows(10, 2, 6) == 1 << 24
    assert merge_mod._block_rows(1 << 30, 9, 6) == 1 << 24
    rows = [merge_mod._block_rows(10, 2, c) for c in (6, 9, 17, 18, 101)]
    assert rows == [1 << 24, 1 << 23, 1 << 22, 1 << 22, 1 << 19]
    for c, r in zip((6, 9, 17, 18, 101), rows):
        assert r * 4 * c <= merge_mod.DEVICE_BLOCK_BYTES < 2 * r * 4 * c


def test_device_fold_of_wide_keys_matches_kway(monkeypatch):
    """Keys 18 columns wide (sigma 17, one term a lane), with the byte
    budget shrunk to 64 rows of them: the blocked fold spans many blocks
    and equals the host k-way route at every ``min_count``."""
    from repro.index import merge as merge_mod
    from repro.index.merge import DeferredSegmentAccumulator
    vocab = 1 << 17
    rng = np.random.default_rng(17)
    quote = rng.integers(1, vocab, 30).astype(np.int32)
    cfg = NGramConfig(sigma=17, tau=1, vocab_size=vocab)
    segs = []
    for s in range(3):
        toks = np.concatenate([make_corpus(300, 40, "zipf", s), [0], quote,
                               [0]]).astype(np.int32)
        segs.append(segment_from_stats(run_job(toks, cfg), vocab_size=vocab))
    assert np.asarray(segs[0].keys).shape[1] == 18
    monkeypatch.setattr(merge_mod, "DEVICE_BLOCK_BYTES", 4 * 18 * 64)
    assert merge_mod._block_rows(10 ** 6, 3, 18) == 64
    for min_count in (None, 2, 3):
        want = merge_segments(segs, route="kway", min_count=min_count)
        got = merge_segments(segs, route="device", min_count=min_count)
        np.testing.assert_array_equal(np.asarray(got.keys),
                                      np.asarray(want.keys))
        np.testing.assert_array_equal(np.asarray(got.counts),
                                      np.asarray(want.counts))
    acc = DeferredSegmentAccumulator(route="device")
    for seg in segs:
        acc.push(seg)
    acc.result(min_count=3)
    assert acc.finalize_blocks > 1


def test_generational_query_overflow_guard_trips():
    """Counts split across live segments must not silently wrap at query time
    (the lookup-side mirror of the merge fold's guard)."""
    big = 2**31 + 5
    mk = lambda seed: NGramStats(np.array([[7, 0, 0]], np.int32),
                                 np.array([1], np.int32),
                                 np.array([big], np.int64))
    gen = GenerationalIndex(sigma=3, vocab_size=9, size_ratio=1)
    gen.levels = [build_index(mk(0), vocab_size=9),
                  build_index(mk(1), vocab_size=9)]   # bypass compaction
    g = np.array([[7, 0, 0]], np.int32)
    ln = np.array([1], np.int32)
    with pytest.raises(ValueError, match="overflow"):
        lookup(gen, g, ln)
    with pytest.raises(ValueError, match="overflow"):
        continuations(gen, np.zeros((1, 3), np.int32),
                      np.zeros(1, np.int32), k=2)


def test_segment_round_trips():
    """to_segment() of both layouts reproduces the built segment bit-exactly."""
    toks = make_corpus(3000, 50, "zipf", 4)
    stats = run_job(toks, NGramConfig(sigma=4, tau=2, vocab_size=50))
    seg = segment_from_stats(stats, vocab_size=50)
    idx = build_index(stats, vocab_size=50)
    assert_trees_equal(idx.to_segment(), seg)
    cidx = build_compressed_index(stats, vocab_size=50)
    assert_trees_equal(cidx.to_segment(), seg)
    # and stats survive the segment view (dict equality; row order may differ)
    assert segment_to_stats(seg).to_dict() == stats.to_dict()


# --------------------------------------------------------------------------- #
# compressed-native merge (streamed block decode)
# --------------------------------------------------------------------------- #

def test_compressed_native_merge_edge_segments():
    """Empty, singleton, and partial-final-block compressed inputs all merge
    bit-identically to the union build through the streamed decode."""
    vocab, sigma = 30, 3
    cfg = NGramConfig(sigma=sigma, tau=1, vocab_size=vocab)
    empty = NGramStats(np.zeros((0, sigma), np.int32), np.zeros(0, np.int32),
                       np.zeros(0, np.int64))
    single = NGramStats(np.array([[5, 0, 0]], np.int32),
                        np.array([1], np.int32), np.array([7], np.int64))
    big = run_job(make_corpus(900, vocab, "zipf", 5), cfg)
    block = 64                              # row counts below won't divide it
    assert build_compressed_index(big, vocab_size=vocab,
                                  block_size=block).n_rows % block != 0
    for parts in ([empty, big], [single, big], [empty, single, big]):
        want = build_compressed_index(stats_union(*parts), vocab_size=vocab,
                                      block_size=block)
        for route in ("kway", "device"):
            got = merge_indexes(
                [build_compressed_index(s, vocab_size=vocab, block_size=block)
                 for s in parts], route=route)
            assert_trees_equal(got, want)


def test_compressed_native_merge_overflow_guard():
    """The uint32 fold guard fires through the compressed-native path too."""
    big = 2**31 + 5
    mk = lambda: NGramStats(np.array([[7, 0, 0]], np.int32),
                            np.array([1], np.int32),
                            np.array([big], np.int64))
    cixs = [build_compressed_index(mk(), vocab_size=9) for _ in range(2)]
    for route in ("kway", "device"):
        with pytest.raises(ValueError, match="overflow"):
            merge_indexes(cixs, route=route)


def test_compressed_merge_working_set_is_block_batches(monkeypatch):
    """Compaction must never materialize a whole decoded table: with the chunk
    shrunk to 64 rows, the decode high-water mark stays at the chunk size while
    merging inputs hundreds of rows deep -- and the output is still exact."""
    from repro.index import compress as compress_mod

    vocab = 40
    sa, sb = job_pair(vocab, "zipf", 4, 1, seed=21, n=3000)
    ca, cb = (build_compressed_index(s, vocab_size=vocab) for s in (sa, sb))
    assert min(ca.n_rows, cb.n_rows) > 64   # inputs dwarf the chunk
    monkeypatch.setattr(compress_mod, "_DECODE_CHUNK_ROWS", 64)
    monkeypatch.setitem(compress_mod._DECODE_WATERMARK, "rows", 0)
    got = merge_indexes([ca, cb], route="kway")
    peak = compress_mod._DECODE_WATERMARK["rows"]
    assert 0 < peak <= 64                   # O(block batch), not O(table)
    want = build_compressed_index(stats_union(sa, sb), vocab_size=vocab)
    assert_trees_equal(got, want)


def test_decode_segment_chunk_sweep():
    """decode_segment is chunk-size invariant and equals the unpadded truth."""
    from repro.index.compress import decode_segment
    # tiny corpus: chunk=1 walks every row in its own dispatch round, so the
    # sweep cost is n_rows * n_chunk_sizes host round-trips -- keep rows low
    vocab = 20
    stats = run_job(make_corpus(200, vocab, "zipf", 9),
                    NGramConfig(sigma=3, tau=1, vocab_size=vocab))
    seg = segment_from_stats(stats, vocab_size=vocab)
    r = seg.n_rows
    cidx = build_compressed_index(stats, vocab_size=vocab, block_size=4)
    for chunk in (1, 3, 64, 10**9):
        got = decode_segment(cidx, chunk_rows=chunk)
        assert got.n_rows == r == int(got.keys.shape[0])   # unpadded
        np.testing.assert_array_equal(np.asarray(got.keys),
                                      np.asarray(seg.keys)[:r])
        np.testing.assert_array_equal(np.asarray(got.counts),
                                      np.asarray(seg.counts)[:r])


if HAS_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(vocab=st.integers(2, 5000),
           dist=st.sampled_from(["zipf", "uniform"]),
           sigma=st.integers(1, 6), tau=st.integers(1, 3),
           seed=st.integers(0, 2**16))
    def test_merge_parity_hypothesis(vocab, dist, sigma, tau, seed):
        sa, sb = job_pair(vocab, dist, sigma, tau, seed, n=1500)
        check_merge_parity(sa, sb, vocab)


# --------------------------------------------------------------------------- #
# generational index
# --------------------------------------------------------------------------- #

def drive_generational(compress: bool):
    """>=3 ingests with at least one compaction; parity vs from-scratch."""
    vocab, sigma, tau = 40, 4, 1
    cfg = NGramConfig(sigma=sigma, tau=tau, vocab_size=vocab)
    slices = [make_corpus(n, vocab, "zipf", 10 + i)
              for i, n in enumerate((4000, 900, 900, 900))]
    all_stats = [run_job(t, cfg) for t in slices]
    gen = GenerationalIndex(sigma=sigma, vocab_size=vocab, compress=compress)
    merges = 0
    for s in all_stats:
        merges += gen.ingest(s)["merges"]
    assert merges >= 1                     # the policy actually compacted
    assert gen.n_segments >= 2             # ...but not down to one artifact
    union = stats_union(*all_stats)
    build = build_compressed_index if compress else build_index
    target = build(union, vocab_size=vocab)

    exp = union.to_dict()
    gram_tuples = sorted(exp)
    g = np.zeros((len(gram_tuples), sigma), np.int32)
    ln = np.zeros(len(gram_tuples), np.int32)
    for i, t in enumerate(gram_tuples):
        g[i, :len(t)] = t
        ln[i] = len(t)
    got = np.asarray(lookup(gen, g, ln))
    np.testing.assert_array_equal(got, np.asarray(lookup(target, g, ln)))
    np.testing.assert_array_equal(got, [exp[t] for t in gram_tuples])

    rng = np.random.default_rng(0)
    lm = rng.integers(1, sigma + 1, 1500).astype(np.int32)
    gm = rng.integers(1, vocab + 1, (1500, sigma)).astype(np.int32)
    gm *= np.arange(sigma)[None, :] < lm[:, None]
    np.testing.assert_array_equal(np.asarray(lookup(gen, gm, lm)),
                                  np.asarray(lookup(target, gm, lm)))

    pool = [t[:-1] for t in gram_tuples if len(t) >= 2]
    prefixes = [(), ()] + [pool[i] for i in rng.choice(len(pool), 25)] \
        + [(vocab + 2,)]
    pg = np.zeros((len(prefixes), sigma), np.int32)
    pl = np.zeros(len(prefixes), np.int32)
    for i, t in enumerate(prefixes):
        pg[i, :len(t)] = t
        pl[i] = len(t)
    for uk in (False, True):
        got_c = [np.asarray(x) for x in
                 continuations(gen, pg, pl, k=6, use_kernels=uk)]
        want_c = [np.asarray(x) for x in continuations(target, pg, pl, k=6)]
        for a, b in zip(got_c, want_c):
            np.testing.assert_array_equal(a, b)

    # compact_all collapses to one segment with the same (bit-exact) artifact
    gen.compact_all()
    assert gen.n_segments == 1
    assert_trees_equal(gen.segments[0], target)


def test_generational_flat():
    drive_generational(compress=False)


def test_generational_compressed():
    drive_generational(compress=True)


def test_generational_tier_policy_keeps_l0_flat():
    """Fresh ingests stay flat (hot L0); only merged rungs freeze compressed."""
    from repro.index.build import NGramIndex
    from repro.index.compress import CompressedNGramIndex
    vocab, sigma = 40, 4
    cfg = NGramConfig(sigma=sigma, tau=1, vocab_size=vocab)
    gen = GenerationalIndex(sigma=sigma, vocab_size=vocab, compress=True)
    merges = 0
    for i, n in enumerate((4000, 900, 900, 900)):
        merges += gen.ingest(run_job(make_corpus(n, vocab, "zipf", 10 + i),
                                     cfg))["merges"]
    assert merges >= 1
    kinds = [type(ix) for ix in gen.segments]
    assert kinds[0] is NGramIndex           # newest rung: hot, flat
    assert CompressedNGramIndex in kinds    # elder rung(s): frozen compressed
    # compressed segments + bytes at rest are what the gauges report
    n_c = sum(k is CompressedNGramIndex for k in kinds)
    at_rest = sum(getattr(ix, "nbytes_at_rest", None) or ix.nbytes
                  for ix in gen.segments)
    assert n_c >= 1 and 0 < at_rest < sum(ix.nbytes for ix in gen.segments)


def check_mixed_stack_parity(sa, sb, vocab, sigma, *, block=4):
    """A stack mixing flat and compressed rungs answers bit-identically to the
    all-flat stack -- the compressed-at-rest serving contract."""
    ia, ib = (build_index(s, vocab_size=vocab) for s in (sa, sb))
    ca = build_compressed_index(sa, vocab_size=vocab, block_size=block)
    flat = GenerationalIndex(sigma=sigma, vocab_size=vocab)
    flat.levels = [ib, ia]                  # newest first, elder flat
    mixed = GenerationalIndex(sigma=sigma, vocab_size=vocab)
    mixed.levels = [ib, ca]                 # same rows, elder frozen

    exp = stats_union(sa, sb).to_dict()
    rng = np.random.default_rng(11)
    all_tuples = sorted(exp)
    gram_tuples = [all_tuples[i] for i in sorted(
        rng.choice(len(all_tuples), min(len(all_tuples), 500), replace=False))]
    miss_g = rng.integers(1, vocab + 1, (150, sigma)).astype(np.int32)
    miss_l = rng.integers(1, sigma + 1, 150).astype(np.int32)
    miss_g *= np.arange(sigma)[None, :] < miss_l[:, None]
    g = np.zeros((len(gram_tuples) + 150, sigma), np.int32)
    ln = np.zeros(len(gram_tuples) + 150, np.int32)
    for i, t in enumerate(gram_tuples):
        g[i, :len(t)] = t
        ln[i] = len(t)
    g[len(gram_tuples):] = miss_g
    ln[len(gram_tuples):] = miss_l
    got = np.asarray(lookup(mixed, g, ln))
    np.testing.assert_array_equal(got, np.asarray(lookup(flat, g, ln)))
    np.testing.assert_array_equal(
        got[:len(gram_tuples)], [exp[t] for t in gram_tuples])

    pool = [t[:-1] for t in all_tuples if len(t) >= 2] or [()]
    prefixes = [(), (vocab + 2,)] + [pool[i]
                                     for i in rng.choice(len(pool), 10)]
    pg = np.zeros((len(prefixes), sigma), np.int32)
    pl = np.zeros(len(prefixes), np.int32)
    for i, t in enumerate(prefixes):
        pg[i, :len(t)] = t
        pl[i] = len(t)
    got_c = [np.asarray(x) for x in continuations(mixed, pg, pl, k=5)]
    want_c = [np.asarray(x) for x in continuations(flat, pg, pl, k=5)]
    for a, b in zip(got_c, want_c):
        np.testing.assert_array_equal(a, b)


# two draws, not all of MERGE_DRAWS: every (vocab, sigma) pair recompiles the
# whole compressed query stack, and the hypothesis tier below varies them too
@pytest.mark.parametrize("vocab,dist,sigma,tau,seed",
                         [MERGE_DRAWS[1], MERGE_DRAWS[3]])
def test_mixed_stack_parity_generated_corpora(vocab, dist, sigma, tau, seed):
    sa, sb = job_pair(vocab, dist, sigma, tau, seed, n=1500)
    check_mixed_stack_parity(sa, sb, vocab, sigma)


if HAS_HYPOTHESIS:
    @pytest.mark.slow
    @settings(max_examples=4, deadline=None)
    @given(vocab=st.integers(2, 5000),
           dist=st.sampled_from(["zipf", "uniform"]),
           sigma=st.integers(1, 6), tau=st.integers(1, 3),
           seed=st.integers(0, 2**16))
    def test_mixed_stack_parity_hypothesis(vocab, dist, sigma, tau, seed):
        sa, sb = job_pair(vocab, dist, sigma, tau, seed, n=1200)
        check_mixed_stack_parity(sa, sb, vocab, sigma)


def test_generational_bootstrap_and_empty():
    empty = GenerationalIndex(sigma=3, vocab_size=9)
    assert np.asarray(lookup(empty, np.zeros((2, 3), np.int32),
                             np.ones(2, np.int32))).tolist() == [0, 0]
    nd, tot, terms, cfs = continuations(empty, np.zeros((2, 3), np.int32),
                                        np.zeros(2, np.int32), k=4)
    assert np.asarray(nd).tolist() == [0, 0]
    s = NGramStats(np.array([[5, 0, 0]], np.int32), np.array([1], np.int32),
                   np.array([7], np.int64))
    gen = generational_from_stats(s, vocab_size=9)
    assert gen.n_segments == 1 and gen.generation == 1
    with pytest.raises(ValueError):        # sigma mismatch on ingest
        gen.ingest(NGramStats(np.zeros((0, 4), np.int32),
                              np.zeros(0, np.int32), np.zeros(0, np.int64)))


# --------------------------------------------------------------------------- #
# streaming serving pieces (LRU cache, double buffering)
# --------------------------------------------------------------------------- #

def test_lru_cache_eviction_and_invalidation():
    from repro.launch.serve_ngrams import LRUQueryCache
    c = LRUQueryCache(capacity=2)
    c.put("a", 1, 10)
    c.put("b", 1, 20)
    assert c.get("a", 1) == 10             # refreshes "a"
    c.put("x", 1, 30)                      # evicts LRU "b"
    assert c.get("b", 1) is None
    assert c.get("a", 1) == 10 and c.get("x", 1) == 30
    assert c.get("a", 2) is None           # generation swap drops everything
    assert len(c) == 0
    c.put("a", 2, 11)
    assert c.get("a", 2) == 11
    assert 0.0 < c.hit_rate < 1.0
    # a stale (pre-swap) writer must neither install nor roll the cache back
    c.put("old", 1, 99)
    assert c.generation == 2 and c.get("a", 2) == 11
    assert c.get("old", 2) is None
    assert c.get("a", 1) is None           # stale reader: miss, no clear
    assert c.get("a", 2) == 11


def test_streaming_service_matches_oracle_and_caches():
    from repro.launch.serve_ngrams import StreamingNGramService
    vocab, sigma = 30, 3
    cfg = NGramConfig(sigma=sigma, tau=1, vocab_size=vocab)
    svc = StreamingNGramService(cfg, cache_capacity=4096)
    slices = [make_corpus(700, vocab, "zipf", 30 + i) for i in range(3)]
    for t in slices:
        svc.ingest(t)
    exp = stats_union(*[run_job(t, cfg) for t in slices]).to_dict()
    gram_tuples = sorted(exp)
    g = np.zeros((len(gram_tuples), sigma), np.int32)
    ln = np.zeros(len(gram_tuples), np.int32)
    for i, t in enumerate(gram_tuples):
        g[i, :len(t)] = t
        ln[i] = len(t)
    got = svc.lookup(g, ln)
    np.testing.assert_array_equal(got, [exp[t] for t in gram_tuples])
    # a repeat is pure cache: hits grow by the batch, misses don't
    h0, m0 = svc.cache.hits, svc.cache.misses
    again = svc.lookup(g, ln)
    np.testing.assert_array_equal(again, got)
    assert svc.cache.hits == h0 + len(gram_tuples)
    assert svc.cache.misses == m0
    # pipelined (double-buffered) drive returns the same answers in order
    batches = [(g[i:i + 64], ln[i:i + 64]) for i in range(0, len(gram_tuples), 64)]
    outs = svc.lookup_pipelined(batches)
    np.testing.assert_array_equal(np.concatenate(outs), got)
    # ingest bumps the generation -> stale entries never served
    svc.ingest(make_corpus(700, vocab, "zipf", 77))
    fresh = svc.lookup(g, ln)
    exp2 = stats_union(*[run_job(t, cfg) for t in slices +
                         [make_corpus(700, vocab, "zipf", 77)]]).to_dict()
    np.testing.assert_array_equal(fresh,
                                  [exp2[t] for t in gram_tuples])
    # top-k through the service agrees with the generational query path
    pool = [t[:-1] for t in gram_tuples if len(t) >= 2][:10]
    pg = np.zeros((len(pool), sigma), np.int32)
    pl = np.zeros(len(pool), np.int32)
    for i, t in enumerate(pool):
        pg[i, :len(t)] = t
        pl[i] = len(t)
    rows = svc.continuations(pg, pl, k=4)
    nd, tot, terms, cfs = [np.asarray(x)
                           for x in continuations(svc.gen, pg, pl, k=4)]
    np.testing.assert_array_equal(rows[:, 0], nd)
    np.testing.assert_array_equal(rows[:, 2:6], terms)
    np.testing.assert_array_equal(rows[:, 6:], cfs)


def test_double_buffered_driver_orders_results():
    from repro.launch.serve_ngrams import DoubleBufferedDriver
    calls = []
    drv = DoubleBufferedDriver(lambda x: (calls.append(x), x * 2)[1])
    outs = []
    for i in range(4):
        res, tag = drv.submit(np.asarray([i]), tag=i)
        if res is not None:
            outs.append((int(res[0]), tag))
    res, tag = drv.drain()
    outs.append((int(res[0]), tag))
    assert outs == [(0, 0), (2, 1), (4, 2), (6, 3)]
    assert drv.drain() == (None, None)
