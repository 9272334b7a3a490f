"""Incremental index maintenance: k-way segment merge + generational (LSM) index.

The job side emits one frozen artifact per run; before this module, refreshing
the served index under a growing corpus meant re-sorting *everything*.  The
sorted immutable :class:`~repro.index.build.IndexSegment` is the unit of
composition (Pibiri & Venturini's layout observation), so freshness becomes the
classic log-structured-merge discipline instead:

  * :func:`merge_segments` -- k-way merge of sorted segments into one new
    segment with duplicate grams' counts *summed*.  Three routes produce the
    sorted run: ``"kway"`` (the default fold of the wave engine) exploits the
    inputs' sortedness on the host -- a stable sort of the concatenated
    big-endian row bytes is a galloping k-way merge (timsort detects the k
    presorted runs), an order of magnitude cheaper than re-sorting blind --
    and folds duplicate counts exactly in int64 via ``np.add.reduceat``;
    ``"merge"`` runs the jitted pairwise merge-path (``kernels/merge_path.py``
    Pallas kernel, or its jnp ref) over a balanced pairing tree;
    ``"device"`` is the same merge-path tree with an automatic host-kway
    fallback above ``DEVICE_MERGE_MAX_ROWS`` total rows (oversized tau=1
    gram sets would thrash device memory); ``"sort"`` re-sorts the
    concatenation through ``mapreduce.sort``.  On the device routes, run
    boundaries come
    from ``mapreduce.segment``'s lcp primitive and the dedup-summed count
    fold runs through the reducer's segmented-sum path in two uint32 limbs
    (exact below ``_MAX_DEVICE_RUN`` duplicates per gram; longer runs replay
    on the host in int64).  Every route refuses loudly if a merged cf
    overflows the uint32 device lanes (mirroring the continuation-mass guard
    in ``build.py``), and all three produce bit-identical segments: the
    output order is ascending (length | packed lanes), a pure function of
    the row set.
  * :func:`merge_indexes` -- segments in, finished artifact out:
    ``index_from_segment`` rebuilds fanout/continuation/cumsum structures from
    the merged rows *without re-running the job*, and re-compresses when the
    inputs were compressed.  Because the structure build is shared with
    ``build_index`` and the continuation order is a pure function of the row
    set, ``merge(build(A), build(B))`` is bit-identical to ``build(A ∪ B)``.
  * :class:`GenerationalIndex` -- L0..Ln immutable segments under a size-ratio
    compaction policy: each ingest freezes a new L0 from a (small) job delta,
    and merges cascade only when a newer run grows to within ``size_ratio`` of
    its elder, so a 10% corpus delta costs a 10% job + occasional merges rather
    than a full rebuild.  Point lookups sum cf across live segments; top-k
    completion fetches per-segment candidates and merges them exactly
    (``query.py``/``serve.py`` route both layouts, single-device and sharded).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stats import NGramStats
from repro.mapreduce import pack as packing
from repro.mapreduce import segment as mr_segment
from repro.mapreduce import sort as mr_sort
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from ._layout import SENTINEL, pad_rows, round_capacity, row_bytes_view
from .build import IndexSegment, NGramIndex, build_index, index_from_segment
from .compress import CompressedNGramIndex, compress_index, decode_segment

DEFAULT_SIZE_RATIO = 4
_U32_MAX = np.iinfo(np.uint32).max

AnyIndex = "NGramIndex | CompressedNGramIndex"


def _merged_run(segs: list[IndexSegment], *, route: str,
                use_kernels: bool) -> tuple[jax.Array, jax.Array]:
    """One sorted run (duplicates kept, sentinels at the tail) over all rows."""
    if route == "sort":
        # fallback: re-sort the concatenation (mapreduce.sort, the job's own
        # multi-key lexicographic sort; sentinel rows sort to the tail)
        keys = jnp.concatenate([s.keys for s in segs], axis=0)
        counts = jnp.concatenate([s.counts for s in segs], axis=0)
        keys, (counts,) = mr_sort.sort_with_payload(keys, [counts])
    elif route in ("merge", "device"):
        if use_kernels:
            from repro.kernels import ops as kops
            merge2 = kops.merge_path
        else:
            from repro.kernels import ref as kref
            merge2 = kref.merge_path_ref
        # balanced pairing tree in segment-index order: every row rides
        # O(log k) pairwise merges instead of the linear chain's O(k), and
        # adjacent pairing + the merge-path's A-first tie rule keep global
        # duplicate order (moot anyway: the dedup fold sums duplicates, and
        # output order is a pure function of the row set)
        # wave-fold segments arrive host-resident; the merge tree's traced
        # binary search needs device operands, so lift once up front
        runs = [(jnp.asarray(s.keys, jnp.uint32),
                 jnp.asarray(s.counts, jnp.uint32)) for s in segs]
        while len(runs) > 1:
            paired = [merge2(runs[i][0], runs[i + 1][0],
                             runs[i][1], runs[i + 1][1])
                      for i in range(0, len(runs) - 1, 2)]
            if len(runs) % 2:
                paired.append(runs[-1])
            runs = paired
        keys, counts = runs[0]
    else:
        raise ValueError(f"unknown merge route {route!r}")
    return jnp.asarray(keys, jnp.uint32), jnp.asarray(counts, jnp.uint32)


# Two-limb uint32 segment sums stay exact while every run is shorter than
# this; a merge of k segments with distinct rows each has runs of length <= k,
# so the device fold covers everything but adversarial duplicate floods.
_MAX_DEVICE_RUN = 1 << 16

# the "device" route's size ceiling: above this many total input rows
# (sentinel pads included -- that is what the merge tree actually moves) the
# fold falls back to the host k-way path, which streams in numpy instead of
# holding every intermediate merge run in device memory.  Oversized tau=1
# gram sets (huge corpora at tiny tau) are exactly the shape that trips this.
DEVICE_MERGE_MAX_ROWS = 1 << 22


def _run_starts(sorted_bytes: np.ndarray) -> np.ndarray:
    """Start offsets of the duplicate runs of a sorted byte-row column."""
    n = sorted_bytes.shape[0]
    new_run = np.empty((n,), bool)
    if n:
        new_run[0] = True
        new_run[1:] = sorted_bytes[1:] != sorted_bytes[:-1]
    return np.flatnonzero(new_run)


def _check_u32(totals: np.ndarray) -> np.ndarray:
    """uint32 view of int64 merged counts, refusing loudly on overflow."""
    if totals.size and int(totals.max()) > _U32_MAX:
        bad = int(np.argmax(totals))
        raise ValueError(
            f"merged count {int(totals[bad])} of gram row {bad} overflows the "
            "uint32 device count lane; raise tau or shard the corpus before "
            "merging")
    return totals.astype(np.uint32)


def _sorted_unique(segs: list[IndexSegment]):
    """Merge + dedup-fold segments' real rows -> sorted (keys, totals int64).

    Sentinel tails are stripped up front (``n_rows``), so only real rows ride
    the sort.  Viewing each row as its big-endian bytes makes byte order
    equal numeric lexicographic order, so a *stable* sort of the
    concatenation is a galloping k-way merge (numpy's timsort detects the k
    presorted runs) -- measured ~5-8x cheaper than a blind lexsort at the
    wave engine's row counts.  Duplicate counts fold exactly in int64 via
    ``reduceat``.  Spans: ``merge.kway.concat``, ``merge.kway.sort``,
    ``merge.kway.fold``.
    """
    with obs_trace.span("merge.kway.concat"):
        keys = np.concatenate(
            [np.asarray(s.keys, np.uint32)[:s.n_rows] for s in segs], axis=0)
        counts = np.concatenate(
            [np.asarray(s.counts, np.uint32)[:s.n_rows] for s in segs],
            axis=0)
    with obs_trace.span("merge.kway.sort") as sp:
        if sp:
            sp.set(rows=int(keys.shape[0]))
        row_bytes = row_bytes_view(keys)
        order = np.argsort(row_bytes, kind="stable")
    with obs_trace.span("merge.kway.fold"):
        starts = _run_starts(row_bytes[order])
        if not starts.size:
            return (np.zeros((0, keys.shape[1]), np.uint32),
                    np.zeros((0,), np.int64), np.zeros((0,), row_bytes.dtype))
        picked = order[starts]
        totals = np.add.reduceat(counts[order].astype(np.int64), starts)
        return keys[picked], totals, row_bytes[picked]


def _kway_fold_host(segs: list[IndexSegment], *,
                    sigma: int) -> tuple[np.ndarray, np.ndarray]:
    """Host k-way dedup fold that exploits the inputs' sortedness.

    Balanced inputs take one galloping merge-by-stable-sort over every real
    row (see :func:`_sorted_unique`).  *Skewed* inputs -- one segment at
    least as large as all others combined, the shape of every LSM compaction
    (a fresh delta folding into a grown elder run) -- skip sorting the large
    segment entirely: only the small side is merged and deduped, then spliced
    into the base by binary search (``searchsorted``), so a compaction costs
    O(delta log delta + delta log base + total move) instead of
    O(total log total).  Both paths produce the identical sorted unique row
    set with exact int64 count folds and the uint32 overflow guard.
    """
    sizes = [s.n_rows for s in segs]
    b = int(np.argmax(sizes))
    nb, nd = sizes[b], sum(sizes) - sizes[b]
    if nd == 0:
        # one live input (plus empties): its rows are already sorted+unique
        base = segs[b]
        return (np.asarray(base.keys, np.uint32)[:nb],
                np.asarray(base.counts, np.uint32)[:nb])
    if nb < nd:
        keys, totals, _ = _sorted_unique(segs)
        with obs_trace.span("merge.kway.fold"):
            return keys, _check_u32(totals)

    # skewed fast path: sort/dedup only the small side ...
    d_keys, d_tot, d_bytes = _sorted_unique(segs[:b] + segs[b + 1:])
    with obs_trace.span("merge.kway.splice"):
        return _splice(segs[b], nb, d_keys, d_tot, d_bytes)


def _splice(base: IndexSegment, nb: int, d_keys, d_tot, d_bytes):
    """Fold the sorted unique delta rows into the ``nb`` base rows."""
    b_keys = np.asarray(base.keys, np.uint32)[:nb]
    b_tot = np.asarray(base.counts, np.uint32)[:nb].astype(np.int64)
    b_bytes = row_bytes_view(b_keys)
    # splice: delta rows already in the base fold their counts in
    # place (unique x unique -- no index collides), the rest interleave at
    # their searchsorted insertion points via one shift-and-scatter
    pos = np.searchsorted(b_bytes, d_bytes, side="left")
    dup = np.zeros(d_bytes.shape[0], bool)
    in_range = pos < nb
    dup[in_range] = b_bytes[pos[in_range]] == d_bytes[in_range]
    b_tot[pos[dup]] += d_tot[dup]
    ins = pos[~dup]                      # sorted: delta is
    n_new = int(ins.shape[0])
    out_keys = np.empty((nb + n_new, b_keys.shape[1]), np.uint32)
    out_tot = np.empty((nb + n_new,), np.int64)
    new_at = ins + np.arange(n_new)
    base_at = np.arange(nb) + np.cumsum(
        np.bincount(ins, minlength=nb + 1))[:nb]
    out_keys[base_at] = b_keys
    out_tot[base_at] = b_tot
    out_keys[new_at] = d_keys[~dup]
    out_tot[new_at] = d_tot[~dup]
    return out_keys, _check_u32(out_tot)


@partial(jax.jit, static_argnames=("sigma",))
def _fold_runs_device(keys: jax.Array, counts: jax.Array, *, sigma: int):
    """Dedup-fold a sorted run on device: the reducer's segmented-sum path.

    Device count lanes are uint32 and x64 may be off, so the fold runs in two
    uint32 limbs (lo/hi 16 bits of each count, segment-summed separately and
    recombined) -- exact while runs stay under ``_MAX_DEVICE_RUN`` rows, with
    the recombine carry doubling as the loud cf-overflow guard.  Run starts
    are compacted to the front with a stable argsort (order preserved), the
    tail refilled with sentinels.  Returns
    (keys [N, C], totals [N], n_runs, overflow?, max_run_len).
    """
    n, n_cols = keys.shape
    lcp = mr_segment.lcp_lengths(keys.astype(jnp.int32))
    new_run = lcp < n_cols                     # row 0 has lcp 0 -> always True
    seg = jnp.maximum(jnp.cumsum(new_run.astype(jnp.int32)) - 1, 0)
    run_len = jax.ops.segment_sum(jnp.ones((n,), jnp.uint32), seg,
                                  num_segments=n)
    slo = jax.ops.segment_sum(counts & jnp.uint32(0xFFFF), seg, num_segments=n)
    shi = jax.ops.segment_sum(counts >> 16, seg, num_segments=n)
    hi = shi + (slo >> 16)                     # carry; > 0xFFFF == cf overflow
    totals = (hi << 16) | (slo & jnp.uint32(0xFFFF))
    real = new_run & (keys[:, 0] <= jnp.uint32(sigma))  # sentinels sort last
    order = jnp.argsort(~real, stable=True)    # real run starts first, in order
    n_runs = jnp.sum(real.astype(jnp.int32))
    in_range = jnp.arange(n) < n_runs
    out_keys = jnp.where(in_range[:, None], keys[order], SENTINEL)
    out_counts = jnp.where(in_range, totals[seg][order], 0)
    overflow = jnp.any(in_range & ((hi[seg][order] >> 16) != 0))
    return out_keys, out_counts, n_runs, overflow, jnp.max(run_len)


def _fold_runs_host(keys: np.ndarray, counts: np.ndarray, *,
                    sigma: int) -> tuple[np.ndarray, np.ndarray]:
    """Host int64 fold -- fallback for runs too long for the two-limb device
    path, and the bearer of the detailed overflow diagnostic."""
    lcp = np.asarray(mr_segment.lcp_lengths(
        jnp.asarray(keys).astype(jnp.int32)))
    new_run = lcp < keys.shape[1]
    starts = np.flatnonzero(new_run)
    cs = np.concatenate([[0], np.cumsum(counts, dtype=np.int64)])
    ends = np.append(starts[1:], keys.shape[0])
    totals = cs[ends] - cs[starts]                      # int64: exact fold
    run_keys = keys[starts]
    real = run_keys[:, 0] <= np.uint32(sigma)           # sentinel length sorts last
    r_keys = run_keys[real]
    r_tot = totals[real]
    # mirror of build.py's continuation-mass guard: a silently wrapped cf would
    # serve plausible-looking garbage, so refuse loudly instead (raise tau, or
    # shard the corpus so per-shard counts stay in range)
    if r_tot.size and int(r_tot.max()) > _U32_MAX:
        bad = int(np.argmax(r_tot))
        raise ValueError(
            f"merged count {int(r_tot[bad])} of gram row {bad} overflows the "
            "uint32 device count lane; raise tau or shard the corpus before "
            "merging")
    return r_keys, r_tot.astype(np.uint32)


def merge_segments(segments, *, route: str = "merge", use_kernels: bool = False,
                   pad_to: int | None = None,
                   n_compressed: int | None = None) -> IndexSegment:
    """Merge sorted segments into one, summing counts of duplicate grams.

    ``route="kway"`` folds on the host exploiting the inputs' sortedness
    (stable sort of concatenated big-endian row bytes == galloping k-way
    merge; int64 ``reduceat`` count fold); ``route="merge"`` runs the jitted
    pairwise merge-path (Pallas kernel when ``use_kernels``, jnp ref
    otherwise) over a balanced pairing tree; ``route="device"`` is the
    merge-path tree as the wave fold's on-device k-way sort, falling back to
    the host kway fold when the inputs exceed ``DEVICE_MERGE_MAX_ROWS``
    total rows; ``route="sort"`` re-sorts the concatenation (the
    ``mapreduce.sort`` fallback).  All routes are bit-identical.  Raises
    ``ValueError`` if any merged count overflows the uint32 device lanes.

    ``n_compressed`` is purely observational: callers that decoded some
    inputs from the compressed layout record the flat/compressed mix on the
    ``merge.segments`` span.
    """
    segs = list(segments)
    if not segs:
        raise ValueError("cannot merge zero segments")
    sigma, vocab = segs[0].sigma, segs[0].vocab_size
    for s in segs[1:]:
        if (s.sigma, s.vocab_size) != (sigma, vocab):
            raise ValueError(
                f"segment meta mismatch: ({s.sigma}, {s.vocab_size}) vs "
                f"({sigma}, {vocab})")
    sp = obs_trace.span("merge.segments")
    if sp:
        sp.set(n_segments=len(segs),
               rows_in=sum(int(s.keys.shape[0]) for s in segs))
        if n_compressed is not None:
            sp.set(n_compressed=n_compressed,
                   n_flat=len(segs) - n_compressed)
    sp.__enter__()
    try:
        return _merge_segments_body(segs, sigma, vocab, route=route,
                                    use_kernels=use_kernels, pad_to=pad_to)
    finally:
        sp.__exit__(None, None, None)


def _merge_segments_body(segs, sigma, vocab, *, route, use_kernels, pad_to):
    host = route == "kway"
    if route == "device" and sum(
            int(s.keys.shape[0]) for s in segs) > DEVICE_MERGE_MAX_ROWS:
        # oversized tau=1 gram set: the device tree would hold O(total) rows
        # per merge level -- take the streaming host fold instead
        host = True
    if host:
        r_keys, r_tot = _kway_fold_host(segs, sigma=sigma)
    else:
        keys, counts = _merged_run(segs, route=route, use_kernels=use_kernels)

        # run boundaries (a row starts a run iff it differs from its
        # predecessor, via mapreduce.segment's lcp primitive) and the
        # dedup-summed totals all fold on device through the reducer's
        # segmented-sum path; the host only learns (n_runs, overflow?,
        # max_run) to size and validate the result
        out_keys, out_counts, n_runs, overflow, max_run = _fold_runs_device(
            keys, counts, sigma=sigma)
        n_runs, overflow, max_run = int(n_runs), bool(overflow), int(max_run)
        if overflow or max_run >= _MAX_DEVICE_RUN:
            # rare: replay on host for the int64 fold / detailed diagnostic
            r_keys, r_tot = _fold_runs_host(np.asarray(keys, np.uint32),
                                            np.asarray(counts, np.uint32),
                                            sigma=sigma)
        else:
            r_keys = np.asarray(out_keys[:n_runs], np.uint32)
            r_tot = np.asarray(out_counts[:n_runs], np.uint32)
    r = int(r_keys.shape[0])
    size = pad_to if pad_to is not None else round_capacity(r)
    if size < r + 1:
        raise ValueError(f"pad_to={size} < n_rows+1={r + 1}")
    with obs_trace.span("merge.pad"):
        keys_p = pad_rows(r_keys, size, SENTINEL)
        cnts_p = pad_rows(r_tot, size, 0)
    if not host:
        # device routes hand device arrays back; the host folds stay
        # host-resident end to end -- an LSM cascade of kway merges would
        # otherwise pay an h2d/d2h round trip per compaction for data the
        # next merge reads right back on the host
        keys_p, cnts_p = jnp.asarray(keys_p), jnp.asarray(cnts_p)
    return IndexSegment(keys=keys_p, counts=cnts_p, sigma=sigma,
                        vocab_size=vocab)


def _merge_input_segment(entry, *, route: str) -> IndexSegment:
    """Segment view of one merge input, compressed-native when needed.

    Flat entries pass through (``to_segment`` on an :class:`NGramIndex` is a
    field read); compressed entries stream-decode block chunks through
    :func:`~repro.index.compress.decode_segment` -- O(chunk) peak decoded
    working set, never a whole decoded table.  The host ``"kway"`` route (the
    LSM default) takes the unpadded host segment straight in; device routes
    get the capacity-padded device form their search kernels expect.
    """
    if isinstance(entry, CompressedNGramIndex):
        return decode_segment(entry) if route == "kway" else entry.to_segment()
    return entry if isinstance(entry, IndexSegment) else entry.to_segment()


def merge_indexes(indexes, *, route: str = "merge", use_kernels: bool = False,
                  pad_to: int | None = None):
    """Merge finished indexes into one of the same layout, job-free.

    All inputs must share (sigma, vocab_size) and layout; compressed inputs must
    agree on ``block_size`` and yield a compressed result.  Compressed inputs
    merge natively: their rows stream through the chunked block decode rather
    than a full-table ``to_segment`` round trip.
    """
    ixs = list(indexes)
    if not ixs:
        raise ValueError("cannot merge zero indexes")
    compressed = isinstance(ixs[0], CompressedNGramIndex)
    for ix in ixs[1:]:
        if isinstance(ix, CompressedNGramIndex) != compressed:
            raise ValueError("cannot merge mixed flat/compressed layouts")
    seg = merge_segments([_merge_input_segment(ix, route=route) for ix in ixs],
                         route=route, use_kernels=use_kernels,
                         n_compressed=sum(
                             isinstance(ix, CompressedNGramIndex)
                             for ix in ixs))
    idx = index_from_segment(seg, pad_to=pad_to)
    if compressed:
        bs = {ix.block_size for ix in ixs}
        if len(bs) != 1:
            raise ValueError(f"mixed block_size across inputs: {sorted(bs)}")
        return compress_index(idx, block_size=bs.pop())
    return idx


def segment_to_stats(seg: IndexSegment, *,
                     min_count: int | None = None) -> NGramStats:
    """Host-side ``NGramStats`` view of a segment (sharded rebuilds, tests).

    ``min_count`` filters rows *before* the term unpack -- the wave
    finalizer's global tau, applied while the row set is still packed, so
    only surviving rows (the monolithic-sized output) pay the unpack.
    Filtering commutes with unpacking, so the result equals filtering the
    full view after the fact.
    """
    r = seg.n_rows
    with obs_trace.span("stats.filter"):
        keys = np.asarray(seg.keys)[:r]
        counts = np.asarray(seg.counts)[:r].astype(np.int64)
        if min_count is not None and min_count > 1:
            keep = counts >= min_count
            keys = keys[keep]
            counts = counts[keep]
    with obs_trace.span("stats.unpack") as sp:
        if sp:
            sp.set(rows=int(keys.shape[0]))
        lengths = keys[:, 0].astype(np.int32)
        grams = packing.unpack_terms_np(keys[:, 1:],
                                        vocab_size=seg.vocab_size,
                                        sigma=seg.sigma)
        return NGramStats(grams.astype(np.int32), lengths, counts)


def stats_union(*stats: NGramStats) -> NGramStats:
    """Dedup-summed union of job outputs -- the from-scratch merge oracle."""
    acc: dict[tuple[int, ...], int] = {}
    sigma = max((int(s.grams.shape[1]) for s in stats), default=0)
    for s in stats:
        for g, v in s.to_dict().items():
            acc[g] = acc.get(g, 0) + v
    grams = np.zeros((len(acc), sigma), np.int32)
    lengths = np.zeros((len(acc),), np.int32)
    counts = np.zeros((len(acc),), np.int64)
    for i, (g, v) in enumerate(acc.items()):
        grams[i, :len(g)] = g
        lengths[i] = len(g)
        counts[i] = v
    return NGramStats(grams, lengths, counts)


def merge_continuation_results(per_seg, *, k: int):
    """Exact cross-segment fold of per-segment continuation answers.

    per_seg: list of (n_distinct [Q], total [Q], terms [Q, m], counts [Q, m])
    numpy-compatible tuples, each holding a segment's *complete* continuation
    set (certified upstream: every n_distinct <= m).  Returns the standard
    (nd [Q], total [Q], terms [Q, k], counts [Q, k]) with per-term counts
    summed across segments, ranked (cf desc, term asc) -- the same tie order
    the continuation view stores, so the fold is bit-compatible with a
    from-scratch merged index.
    """
    nd0, tot0, t0, c0 = [np.asarray(x) for x in per_seg[0]]
    q = nd0.shape[0]
    total = np.zeros((q,), np.int64)
    terms_all, counts_all, qid_all = [], [], []
    for nd_i, tot_i, t_i, c_i in per_seg:
        total += np.asarray(tot_i, np.int64)
        t_i = np.asarray(t_i)
        c_i = np.asarray(c_i, np.int64)
        live = c_i > 0
        qid = np.broadcast_to(np.arange(q)[:, None], t_i.shape)
        terms_all.append(t_i[live].astype(np.int64))
        counts_all.append(c_i[live])
        qid_all.append(qid[live])
    terms = np.concatenate(terms_all) if terms_all else np.zeros(0, np.int64)
    cfs = np.concatenate(counts_all) if counts_all else np.zeros(0, np.int64)
    qid = np.concatenate(qid_all) if qid_all else np.zeros(0, np.int64)
    span = int(terms.max()) + 2 if terms.size else 2
    key = qid * span + terms
    uniq, inv = np.unique(key, return_inverse=True)
    sums = np.bincount(inv, weights=cfs.astype(np.float64)).astype(np.int64)
    # query-time mirror of the merge fold's guard: summed-across-segment
    # masses/counts must fit the uint32 result lanes or refuse loudly
    worst = max(int(sums.max()) if sums.size else 0,
                int(total.max()) if total.size else 0)
    if worst > _U32_MAX:
        raise ValueError(
            f"summed continuation mass {worst} across live segments overflows "
            "uint32; compact the index or raise tau")
    u_q = (uniq // span).astype(np.int64)
    u_t = (uniq % span).astype(np.int64)
    nd = np.bincount(u_q, minlength=q).astype(np.uint32)
    # rank within each query: cf desc, term asc (the continuation tie order)
    order = np.lexsort((u_t, -sums, u_q))
    rank = np.arange(order.size) - np.concatenate(
        [[0], np.cumsum(np.bincount(u_q, minlength=q))])[u_q[order]]
    topk_t = np.zeros((q, k), np.uint32)
    topk_c = np.zeros((q, k), np.uint32)
    keep = rank < k
    topk_t[u_q[order][keep], rank[keep]] = u_t[order][keep]
    topk_c[u_q[order][keep], rank[keep]] = sums[order][keep]
    return nd, total.astype(np.uint32), topk_t, topk_c


class TieredSegmentAccumulator:
    """Size-tiered fold of a stream of sorted segments (the wave accumulator).

    The wave engine's naive fold -- ``acc = merge_segments([acc, seg])`` per
    wave -- re-merges the whole running segment every wave: O(waves x total)
    rows through the merge path.  This accumulator applies the same LSM
    discipline as :class:`GenerationalIndex` to raw segments: ``push`` stacks
    the new segment as the newest rung and merges only while the newest rung
    has grown to within ``size_ratio`` of its elder, so equal-sized waves
    amortize to O(total log waves) merge rows; ``result`` folds the surviving
    rungs once.  Because dedup-summed segment merges are associative and the
    output order is a pure function of the row set, the final segment is
    bit-identical to the pairwise fold's.

    ``fold_rows`` counts every input row fed through :func:`merge_segments`
    -- the measured merge work the benchmarks compare across strategies.
    """

    def __init__(self, *, size_ratio: int = DEFAULT_SIZE_RATIO,
                 route: str = "sort", use_kernels: bool = False):
        if size_ratio < 1:
            raise ValueError("size_ratio must be >= 1")
        self.size_ratio = size_ratio
        self.route = route
        self.use_kernels = use_kernels
        self.rungs: list[tuple[IndexSegment, int]] = []   # newest first
        self.fold_rows = 0

    def _merge_front(self, n: int) -> None:
        segs = [s for s, _ in reversed(self.rungs[:n])]   # elder first
        self.fold_rows += sum(r for _, r in self.rungs[:n])
        merged = merge_segments(segs, route=self.route,
                                use_kernels=self.use_kernels)
        self.rungs[:n] = [(merged, merged.n_rows)]

    def push(self, seg: IndexSegment, *, n_rows: int | None = None) -> None:
        """Stack one segment, then compact rungs under the size-ratio policy.

        ``n_rows`` (when the caller already knows it, e.g. from the stats the
        segment was frozen from) skips the segment's own host-side row count.
        """
        self.rungs.insert(0, (seg, seg.n_rows if n_rows is None else n_rows))
        while (len(self.rungs) >= 2 and
               self.rungs[0][1] * self.size_ratio >= self.rungs[1][1]):
            self._merge_front(2)

    def result(self) -> IndexSegment:
        """Fold the remaining rungs into the one final sorted segment."""
        if not self.rungs:
            raise ValueError("no segments accumulated")
        if len(self.rungs) > 1:
            self._merge_front(len(self.rungs))
        return self.rungs[0][0]


class DeferredSegmentAccumulator:
    """Stack every wave segment; fold once, k-way, at :meth:`result`.

    The wave engine's default fold.  Incremental compaction (tiered or
    pairwise) re-merges rows it has merged before -- O(total log waves) and
    O(waves x total) rows respectively -- but a :meth:`run` fold does not
    need intermediate merged state at all: only ``result`` is ever read.
    Deferring makes the total fold work exactly *one* k-way merge over the
    raw wave partials (O(total) rows through :func:`merge_segments`, which
    the ``"kway"`` route turns into a single galloping host merge).

    Memory: all wave partials stay live until ``result`` -- O(total tau=1
    rows), the same order as the merged segment every accumulator must
    produce anyway.  When waves must release their partials eagerly (truly
    bounded-memory streaming), use :class:`TieredSegmentAccumulator`
    (log-many live rungs) or :class:`PairwiseSegmentAccumulator` (one).
    Same interface, bit-identical result: dedup-summed merges are
    associative and the output order is a pure function of the row set.
    """

    def __init__(self, *, route: str = "kway", use_kernels: bool = False,
                 **_ignored):
        self.route = route
        self.use_kernels = use_kernels
        self.segs: list[IndexSegment] = []
        self._rows: list[int] = []
        self.fold_rows = 0

    def push(self, seg: IndexSegment, *, n_rows: int | None = None) -> None:
        self.segs.append(seg)
        self._rows.append(seg.n_rows if n_rows is None else n_rows)

    def result(self) -> IndexSegment:
        if not self.segs:
            raise ValueError("no segments accumulated")
        if len(self.segs) == 1:
            return self.segs[0]
        self.fold_rows += sum(self._rows)
        merged = merge_segments(self.segs, route=self.route,
                                use_kernels=self.use_kernels)
        self.segs = [merged]
        self._rows = [merged.n_rows]
        return merged


class PairwiseSegmentAccumulator:
    """The legacy fold-every-wave-into-one-segment baseline (O(waves x total)).

    Same interface and bit-identical result as
    :class:`TieredSegmentAccumulator`; kept for the benchmark comparison and
    as the degenerate-memory option (exactly one live segment at all times).
    """

    def __init__(self, *, route: str = "sort", use_kernels: bool = False,
                 **_ignored):
        self.route = route
        self.use_kernels = use_kernels
        self._seg: IndexSegment | None = None
        self._rows = 0
        self.fold_rows = 0

    def push(self, seg: IndexSegment, *, n_rows: int | None = None) -> None:
        rows = seg.n_rows if n_rows is None else n_rows
        if self._seg is None:
            self._seg, self._rows = seg, rows
            return
        self.fold_rows += self._rows + rows
        self._seg = merge_segments([self._seg, seg], route=self.route,
                                   use_kernels=self.use_kernels)
        self._rows = self._seg.n_rows

    def result(self) -> IndexSegment:
        if self._seg is None:
            raise ValueError("no segments accumulated")
        return self._seg


class GenerationalIndex:
    """L0..Ln immutable sorted segments + size-ratio compaction (an LSM tree).

    ``ingest`` freezes a job delta into a new L0 (newest-first list) and then
    compacts: while the newest run has grown to within ``size_ratio`` of its
    elder (``rows(L0) * size_ratio >= rows(L1)``), the two merge -- so equal
    ingests amortize into log-many segments and a small delta over a big base
    costs no merge at all.

    Writes are segment-first: a level lives as a bare :class:`IndexSegment`
    until a reader touches it, at which point :attr:`segments` materializes
    the full :class:`NGramIndex` / :class:`CompressedNGramIndex` artifact in
    place (cached until the level is compacted away).  Ingest therefore
    costs one sorted-segment freeze plus the galloping segment merge --
    the acceleration structures are built once per *surviving* level
    instead of once per wave, the classic write-optimized LSM trade.
    Because ``build_index == index_from_segment . segment_from_stats``, a
    lazily materialized level is bit-identical to an eagerly frozen one.
    Queries go through ``query.py`` / ``serve.py``, which sum point counts
    and exactly fold top-k candidates across live segments.  ``generation``
    bumps on every mutation -- the serving cache's invalidation key.

    **Compressed-at-rest tier policy** (``compress=True``): hot L0 deltas
    materialize *flat* -- they are small, short-lived, and merge away soon --
    while any rung produced by a compaction merge freezes to the
    :class:`CompressedNGramIndex` at-rest layout.  Provenance, not position,
    decides: a rung that has been through a merge is the cold, grown run.
    Mixed flat/compressed stacks answer bit-identically (the compressed
    layout's parity contract), and compaction decodes compressed inputs
    chunk-by-chunk via :func:`~repro.index.compress.decode_segment` -- never
    a whole decoded table.
    """

    def __init__(self, *, sigma: int, vocab_size: int, compress: bool = False,
                 block_size: int = 4, size_ratio: int = DEFAULT_SIZE_RATIO,
                 route: str = "kway", use_kernels: bool = False):
        if size_ratio < 1:
            raise ValueError("size_ratio must be >= 1")
        self.sigma = sigma
        self.vocab_size = vocab_size
        self.compress = compress
        self.block_size = block_size
        self.size_ratio = size_ratio
        self.route = route
        self.use_kernels = use_kernels
        self._next_id = 0
        # newest (L0) first; an entry is a bare IndexSegment until a reader
        # materializes it (in place) into a built index artifact
        self.levels = []
        self.generation = 0
        # lifetime compaction accounting, surfaced through the metrics
        # registry on every mutation (see _publish_metrics)
        self.compaction_stats = {"ingests": 0, "merges": 0, "rows_merged": 0}

    # --- structure ----------------------------------------------------------- #

    @property
    def levels(self) -> list:
        """Live level entries, newest first.  Assign a full list to replace
        the stack (tests/benchmarks bootstrap with pre-built artifacts);
        in-place mutation is reserved for the index itself, which keeps the
        per-level provenance and identity books in sync."""
        return self._levels

    @levels.setter
    def levels(self, entries) -> None:
        # externally handed entries carry no merge provenance: bare segments
        # among them materialize flat, matching a fresh-ingest L0
        self._levels = list(entries)
        self._from_merge = [False] * len(self._levels)
        self._level_ids = [self._take_id() for _ in self._levels]

    @property
    def level_ids(self) -> tuple:
        """Stable per-level identity tokens (newest first): a level keeps its
        id as long as its content is untouched, and every ingest/merge mints
        a fresh id -- the incremental re-shard reuse key (``serve.py``)."""
        return tuple(self._level_ids)

    def _take_id(self) -> int:
        self._next_id += 1
        return self._next_id

    def _materialize(self, i: int):
        """Build (and cache, replacing in place) level ``i``'s query artifact."""
        entry = self._levels[i]
        if isinstance(entry, IndexSegment):
            with obs_trace.span("gen.materialize") as sp:
                idx = index_from_segment(entry)
                # tier policy: only merged (cold, grown) rungs freeze to the
                # compressed at-rest layout; fresh L0 deltas stay flat
                compressed = self.compress and self._from_merge[i]
                if compressed:
                    idx = compress_index(idx, block_size=self.block_size)
                if sp:
                    sp.set(level=i, rows=idx.n_rows,
                           compressed=int(compressed))
            self._levels[i] = entry = idx
        return entry

    @property
    def segments(self) -> tuple:
        return tuple(self._materialize(i) for i in range(len(self._levels)))

    @property
    def n_segments(self) -> int:
        return len(self.levels)

    @property
    def n_rows(self) -> int:
        return sum(ix.n_rows for ix in self.levels)

    @property
    def nbytes(self) -> int:
        return sum(ix.nbytes for ix in self.levels)

    def __repr__(self) -> str:
        rows = "+".join(str(ix.n_rows) for ix in self.levels) or "0"
        return (f"GenerationalIndex(gen={self.generation}, "
                f"segments={self.n_segments}, rows={rows})")

    # --- mutation ------------------------------------------------------------ #

    def _freeze(self, stats: NGramStats) -> IndexSegment:
        # segment only -- the query artifact (and compression) materializes
        # lazily on first read, so ingest stays O(delta sort)
        from .build import segment_from_stats
        return segment_from_stats(stats, vocab_size=self.vocab_size)

    def ingest(self, stats: NGramStats) -> dict:
        """Freeze a job delta into L0, then compact.  Returns a report dict
        (rows ingested, merges performed, live segment row counts)."""
        if int(stats.grams.shape[1]) != self.sigma:
            raise ValueError(
                f"delta sigma {int(stats.grams.shape[1])} != index sigma "
                f"{self.sigma}")
        with obs_trace.span("gen.ingest") as sp:
            seg = None
            if len(stats):
                with obs_trace.span("gen.freeze"):
                    seg = self._freeze(stats)
            return self._ingest_body(seg, len(stats), sp)

    def ingest_segment(self, seg: IndexSegment | None, *,
                       n_rows: int | None = None) -> dict:
        """Ingest an already-frozen sorted segment as the new L0, then compact.

        The wave engine's streaming entry: the fold thread freezes each
        wave's partial on the host (``build.segment_from_wave_stats``) and
        hands the bare segment straight in -- no per-wave index build; the
        query artifact materializes lazily on first read.
        """
        if seg is not None and (seg.sigma, seg.vocab_size) != (
                self.sigma, self.vocab_size):
            raise ValueError(
                f"segment meta ({seg.sigma}, {seg.vocab_size}) != index "
                f"({self.sigma}, {self.vocab_size})")
        with obs_trace.span("gen.ingest") as sp:
            rows = 0 if seg is None else \
                (seg.n_rows if n_rows is None else n_rows)
            return self._ingest_body(seg, rows, sp)

    def _ingest_body(self, seg, rows: int, sp) -> dict:
        """Shared L0 insert + compaction + accounting of both ingest entries.

        An *empty* delta (e.g. an all-PAD wave of the streaming ingest path)
        bumps the generation -- readers must still observe the swap -- but
        inserts no segment: an all-sentinel L0 would cost every future query
        a full per-segment dispatch for nothing.
        """
        merges = 0
        if rows:
            self._levels.insert(0, seg)
            self._from_merge.insert(0, False)       # fresh delta: hot, flat
            self._level_ids.insert(0, self._take_id())
            merges = self._compact()
        self.generation += 1
        self.compaction_stats["ingests"] += 1
        self._publish_metrics()
        if sp:
            sp.set(rows=rows, merges=merges, segments=len(self.levels))
        return {"ingested_rows": rows, "merges": merges,
                "segment_rows": [ix.n_rows for ix in self.levels]}

    def _merge_front(self, n: int) -> None:
        # elder segments first: merge-path ties keep generation order stable;
        # compaction works on segment views (any cached artifact of a merged
        # level dies with it -- the merged level rebuilds lazily if read);
        # compressed rungs stream-decode chunk by chunk, never a full table
        with obs_trace.span("gen.compact") as sp:
            rows_in = sum(ix.n_rows for ix in self._levels[:n])
            merged = merge_segments(
                [_merge_input_segment(e, route=self.route)
                 for e in reversed(self._levels[:n])],
                route=self.route, use_kernels=self.use_kernels,
                n_compressed=sum(isinstance(e, CompressedNGramIndex)
                                 for e in self._levels[:n]))
            self._levels[:n] = [merged]
            self._from_merge[:n] = [True]           # merged: cold at rest
            self._level_ids[:n] = [self._take_id()]
            self.compaction_stats["merges"] += 1
            self.compaction_stats["rows_merged"] += rows_in
            if sp:
                sp.set(rows_in=rows_in, rows_out=merged.n_rows)

    def _compact(self) -> int:
        merges = 0
        while (len(self.levels) >= 2 and
               self.levels[0].n_rows * self.size_ratio >= self.levels[1].n_rows):
            self._merge_front(2)
            merges += 1
        return merges

    def _publish_metrics(self) -> None:
        """Push live structure + lifetime compaction stats to the registry.

        A no-op (shared null singleton) when metrics are disabled; gauges
        carry the current shape (rung sizes newest-first), counters mirror
        the monotonic ``compaction_stats``.
        """
        reg = obs_metrics.get_registry()
        if not reg:
            return
        reg.gauge("gen.generation").set(self.generation)
        reg.gauge("gen.segments").set(self.n_segments)
        reg.gauge("gen.rows").set(self.n_rows)
        # rung sizes newest-first; bounded set of gauges (log-many rungs).
        # bytes_at_rest reads the entry as-is: a bare (not yet materialized)
        # rung reports its flat segment bytes and shrinks at the first
        # publish after its lazy compression; compressed rungs report their
        # persisted stream bytes (nbytes_at_rest), not the resident total
        # with decoded query caches
        n_comp, total_bytes = 0, 0
        for i, ix in enumerate(self._levels):
            reg.gauge(f"gen.rung{i}_rows").set(ix.n_rows)
            b = getattr(ix, "nbytes_at_rest", None) or ix.nbytes
            total_bytes += b
            reg.gauge(f"gen.rung{i}_bytes_at_rest").set(b)
            n_comp += isinstance(ix, CompressedNGramIndex)
        reg.gauge("gen.bytes_at_rest").set(total_bytes)
        reg.gauge("gen.compressed_segments").set(n_comp)
        for k, v in self.compaction_stats.items():
            c = reg.counter(f"gen.{k}")
            c.add(v - c.value)          # counters mirror the lifetime totals

    def compact_all(self) -> None:
        """Force-merge every live segment into one (maintenance/benchmarks)."""
        if len(self.levels) >= 2:
            self._merge_front(len(self.levels))
            self.generation += 1
            self._publish_metrics()


def generational_from_stats(stats: NGramStats, *, vocab_size: int,
                            compress: bool = False,
                            **kw) -> GenerationalIndex:
    """Bootstrap a generational index from one finished job's output."""
    gen = GenerationalIndex(sigma=int(stats.grams.shape[1]),
                            vocab_size=vocab_size, compress=compress, **kw)
    gen.ingest(stats)
    return gen
