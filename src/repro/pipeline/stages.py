"""Shared stage implementations of the map/combine/shuffle/sort/reduce pipeline.

Before this module each method in ``repro.core`` carried its own copy of the
post-map plumbing: SUFFIX-sigma had a sort-based combiner and an LCP reducer,
the whole-gram methods (NAIVE, APRIORI-*) had their own fused sort+count, and
each hashed partition keys its own way.  The method-specific part of an
algorithm is its *map emit* (and how rounds chain); everything after the emit
is the same MapReduce machinery, so it lives here once:

  combine -- map-side pre-aggregation (the Hadoop combiner).  Two routes:
             ``"sort"`` (sort + run-merge, exact within the buffer) and
             ``"hash"`` (the sort-free hash-slot pass of
             ``kernels/hash_combine.py`` -- Lemire & Kaser's one-pass hashing;
             best-effort per block, exact in total weight).
  shuffle -- partition-key computation (``mapreduce.shuffle.record_key``).
  sort    -- multi-key lexicographic sort of the packed lanes.
  reduce  -- ``reduce_suffix`` (LCP runs: every prefix of every suffix --
             Algorithm 4) or ``reduce_exact`` (whole-gram runs with optional
             position payloads -- Algorithms 1-3).

All functions take and return static-shape arrays, so a jitted composition
(one wave of :class:`~repro.pipeline.executor.WaveExecutor`, or a whole
single-device job) compiles once per record shape.

Reserved-id-0 convention: the validity masks here (``valid = terms != 0`` in
the reducers, weight-lane zeroing in the combiners) all read token id 0 as
"no token" -- the PAD / document-separator convention
:class:`~repro.core.stats.NGramConfig` documents and
``NGramConfig.validate_tokens`` range-checks.  Wave tail masking does NOT
lean on it: the executor passes each wave's true live count, so the
zero-padded tail past a partial final wave is excluded by position, and the
zero checks only ever encode real document boundaries.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.mapreduce import pack as packing
from repro.mapreduce import segment, shuffle, sort


# ------------------------------------------------------------------- combine
def combine_sort(records: jax.Array, n_lanes: int, has_bucket: bool) -> jax.Array:
    """Sort-based map-side combiner: merge records with identical keys.

    Keys = packed lanes (+ bucket lane if present, so series buckets stay
    separate).  Non-first rows of each run get weight 0 (dropped by the
    shuffle's validity mask); shapes stay static.
    """
    n_keys = n_lanes + (1 if has_bucket else 0)
    if has_bucket:  # move bucket next to lanes for sorting, weight last
        rec = jnp.concatenate(
            [records[:, :n_lanes], records[:, n_lanes + 1:],
             records[:, n_lanes:n_lanes + 1]], axis=1)
    else:
        rec = records
    rec = sort.sort_records(rec, n_keys=n_keys)
    keys = rec[:, :n_keys]
    first = jnp.any(keys != jnp.roll(keys, 1, axis=0), axis=1).at[0].set(True)
    seg = jnp.maximum(jnp.cumsum(first.astype(jnp.int32)) - 1, 0)
    wsum = jax.ops.segment_sum(rec[:, -1], seg, num_segments=rec.shape[0])
    new_w = jnp.where(first, wsum[seg], 0)
    rec = rec.at[:, -1].set(new_w)
    if has_bucket:  # restore layout lanes | weight | bucket
        rec = jnp.concatenate(
            [rec[:, :n_lanes], rec[:, -1:], rec[:, n_lanes:-1]], axis=1)
    return rec


def combine_hash(records: jax.Array, n_lanes: int, has_bucket: bool, *,
                 use_kernels: bool = False, block: int = 256) -> jax.Array:
    """Sort-free hash-slot combiner: collapse duplicate keys without a sort.

    Per block of ``block`` records, rows hash into slots; all rows whose key
    equals their slot winner's key donate their weight to the winner.  Rows
    that lose a slot to a different key keep their own weight (a Hadoop
    combiner is best-effort -- the reducer re-aggregates exactly), so the
    (key -> total weight) map is preserved and row order never changes.
    """
    n_keys = n_lanes + (1 if has_bucket else 0)
    if has_bucket:
        keys = jnp.concatenate(
            [records[:, :n_lanes], records[:, n_lanes + 1:n_lanes + 2]], axis=1)
    else:
        keys = records[:, :n_lanes]
    weights = records[:, n_lanes]
    if use_kernels:
        from repro.kernels import ops as kops
        new_w = kops.hash_combine(keys[:, :n_keys], weights, block=block)
    else:
        from repro.kernels import ref as kref
        new_w = kref.hash_combine_ref(keys[:, :n_keys], weights, block=block)
    return records.at[:, n_lanes].set(new_w)


def combine(records: jax.Array, n_lanes: int, has_bucket: bool, *,
            route: str = "sort", use_kernels: bool = False) -> jax.Array:
    if route == "sort":
        return combine_sort(records, n_lanes, has_bucket)
    if route == "hash":
        return combine_hash(records, n_lanes, has_bucket,
                            use_kernels=use_kernels)
    raise ValueError(f"unknown combine route {route!r}")


# ------------------------------------------------------------------- shuffle
def partition_keys(records: jax.Array, n_lanes: int, *, kind: str,
                   vocab_size: int) -> jax.Array:
    """Per-record shuffle key (uint32) from the packed gram lanes."""
    return shuffle.record_key(records[:, :n_lanes], kind=kind,
                              vocab_size=vocab_size)


# -------------------------------------------------------------- sort + reduce
def sort_stage(records: jax.Array, *, n_keys: int) -> jax.Array:
    """The MapReduce sort phase: lexicographic on the first ``n_keys`` lanes."""
    return sort.sort_records(records, n_keys=n_keys)


def reduce_suffix(rec: jax.Array, *, sigma: int, vocab_size: int,
                  n_buckets: int = 0, use_kernels: bool = False):
    """LCP-run reducer over a *sorted* record block (SUFFIX-sigma).

    rec: [N, W] sorted = lanes | weight | (bucket).  Returns
    (terms [N, sigma], flags [N, sigma], counts [N, sigma] or [N, sigma, B]).
    """
    n_l = packing.n_lanes(sigma, vocab_size)
    terms = packing.unpack_terms(rec[:, :n_l], vocab_size=vocab_size, sigma=sigma)
    weight = rec[:, n_l].astype(jnp.int32)
    if use_kernels:
        from repro.kernels import ops as kops
        lcp, flags = kops.lcp_boundary(terms)
    else:
        lcp = segment.lcp_lengths(terms)
        flags = segment.boundary_flags(terms, lcp)
    valid = terms != 0
    if n_buckets:
        bucket = rec[:, n_l + 1].astype(jnp.int32)
        wmat = jax.nn.one_hot(bucket, n_buckets, dtype=jnp.int32) * weight[:, None]
        counts = segment.run_counts_matrix(flags, valid, wmat,
                                           max_segments=rec.shape[0])
    else:
        counts = segment.run_counts(flags, valid, weight,
                                    max_segments=rec.shape[0])
    return terms, flags, counts


def reduce_exact(rec: jax.Array, *, sigma: int, vocab_size: int,
                 with_positions: bool = False):
    """Whole-gram reducer over a *sorted* record block (NAIVE / APRIORI-*).

    rec: [N, W] sorted = lanes | weight | (pos).  Returns (terms, flags,
    counts) shaped like :func:`reduce_suffix` so ``NGramStats.from_dense``
    applies; flags mark the first row of each run at the row's own gram
    length.  If ``with_positions``, also returns per-original-position run
    totals [N] (scattered back through the sort permutation) for the
    APRIORI-INDEX posting-list join.
    """
    n = rec.shape[0]
    n_l = packing.n_lanes(sigma, vocab_size)
    lanes = rec[:, :n_l]
    weight = rec[:, n_l].astype(jnp.int32)
    terms = packing.unpack_terms(lanes, vocab_size=vocab_size, sigma=sigma)

    first = jnp.any(lanes != jnp.roll(lanes, 1, axis=0), axis=1).at[0].set(True)
    seg = jnp.maximum(jnp.cumsum(first.astype(jnp.int32)) - 1, 0)
    totals = jax.ops.segment_sum(weight, seg, num_segments=n)[seg]

    length = jnp.sum(terms != 0, axis=1)                       # gram length per row
    valid_row = (length > 0) & (weight >= 0)
    pos_in_row = jnp.maximum(length - 1, 0)
    row_flags = first & valid_row & (totals > 0)
    flags = (jax.nn.one_hot(pos_in_row, sigma, dtype=jnp.int32)
             * row_flags[:, None].astype(jnp.int32)).astype(bool)
    counts = flags * totals[:, None]

    if not with_positions:
        return terms, flags, counts
    orig_pos = rec[:, n_l + 1].astype(jnp.int32)
    totals_at_pos = jnp.zeros((n,), jnp.int32).at[orig_pos].set(totals, mode="drop")
    return terms, flags, counts, totals_at_pos


# ------------------------------------------------- device-side segment collect
def segment_cells(flags: jax.Array, counts: jax.Array, lanes: jax.Array,
                  masks: jax.Array, *, sigma: int, reduce_kind: str):
    """The cells of a reducer's dense output as segment rows.

    A kept cell of length ``l`` has segment key ``(l | lanes & masks[l])``
    (zeroing a term slot's bit field == packing PAD there -- see
    ``mapreduce.pack.prefix_lane_masks``) and its run's count; a cell is
    kept where it is flagged with a count of at least 1 (``from_dense`` at
    the wave regime's tau = 1).  ``"suffix"`` reducers may keep several
    lengths per row, so their cells are the [N, sigma] grid (cell (r, l) is
    the length-(l+1) prefix run of row r); ``"exact"`` reducers keep at most
    one, the row's own gram length, so their cells are the [N] rows.
    Returns (keep, length uint32, key lanes [..., n_l], count), each with
    the cells' shape; a row that keeps nothing has length 0 and key lanes 0.
    """
    n = lanes.shape[0]
    keep = (flags != 0) & (counts >= 1)
    if reduce_kind == "suffix":
        length = jnp.broadcast_to(jnp.arange(1, sigma + 1, dtype=jnp.uint32),
                                  keep.shape)
        pref = jnp.stack([lanes & masks[l] for l in range(1, sigma + 1)],
                         axis=1)                              # [N, sigma, n_l]
        return keep, length, pref, counts
    len_idx = jnp.argmax(keep, axis=1)                        # 0 when dead
    keep_row = jnp.any(keep, axis=1)
    length = jnp.where(keep_row, (len_idx + 1).astype(jnp.uint32),
                       jnp.uint32(0))
    return keep_row, length, lanes & masks[length], \
        counts[jnp.arange(n), len_idx]


def segment_candidates(flags: jax.Array, counts: jax.Array, lanes: jax.Array,
                       masks: jax.Array, *, sigma: int, reduce_kind: str):
    """Packed segment-candidate rows straight off a reducer's dense output.

    The mesh wave program's collect: the candidate table is a pure
    elementwise function of (flags, counts, sorted key lanes)
    (:func:`segment_cells`) and folds into the fused program -- the host
    never sees dense reducer output, only flat ``(length | prefix lanes,
    count)`` rows with dead rows zeroed (length 0, count 0).  Shapes are
    static: [N * sigma] rows for ``"suffix"`` reducers, [N] for ``"exact"``.

    Candidate *order* is deliberately unspecified: within one wave every kept
    gram key is unique across rounds (rounds emit disjoint lengths; a sorted
    reducer block flags each run once), so the collector's closing stable
    byte-view sort is a pure function of the row set.
    """
    keep, length, pref, cnt = segment_cells(flags, counts, lanes, masks,
                                            sigma=sigma,
                                            reduce_kind=reduce_kind)
    keys = jnp.concatenate(
        [jnp.where(keep, length, jnp.uint32(0))[..., None],
         jnp.where(keep[..., None], pref, jnp.uint32(0))], axis=-1)
    cnts = jnp.where(keep, cnt, 0).astype(jnp.uint32)
    n_cols = keys.shape[-1]
    return keys.reshape(-1, n_cols), cnts.reshape(-1)


def segment_table(flags: jax.Array, counts: jax.Array, lanes: jax.Array,
                  masks: jax.Array, *, sigma: int, reduce_kind: str,
                  chunk: int):
    """A reducer's kept cells as finished segment rows, compacted on the
    device in segment order: the single-device wave program's collect.

    Cells (:func:`segment_cells`) are ranked by (length, row rank): for a
    ``"suffix"`` reducer the column-major walk of its grid, for an
    ``"exact"`` one its rows grouped by gram length.  The reducer walked a
    sorted record block, so within one length the kept keys ascend: that
    rank order is segment order.  One sort keyed by the rank (dead cells
    last) compacts them.  The table is lane-major, ``n_l`` key-lane columns
    then the count column, and comes as a tuple of flat chunks of ``chunk``
    rows each (the last may be shorter): chunk ``c`` holds rows ``[c *
    chunk, c * chunk + chunk)``, column ``j`` at ``[j * rows, (j + 1) *
    rows)``.  The capacity is every cell, so nothing can overflow; a caller
    copies only the chunks that hold kept rows.  The length column is left
    off: a caller rebuilds it from ``per_len`` [sigma] int32, the kept cells
    of each length.
    """
    keep, length, pref, cnt = segment_cells(flags, counts, lanes, masks,
                                            sigma=sigma,
                                            reduce_kind=reduce_kind)
    n, n_l = lanes.shape
    if reduce_kind == "suffix":
        keep_c = keep.T.reshape(-1)
        cols = [pref[..., j].T.reshape(-1) for j in range(n_l)] \
            + [cnt.T.reshape(-1)]
        rank = jnp.arange(n * sigma, dtype=jnp.uint32)
        per_len = jnp.sum(keep, axis=0, dtype=jnp.int32)
    else:
        keep_c = keep
        cols = [pref[:, j] for j in range(n_l)] + [cnt]
        rank = (length - 1) * jnp.uint32(n) + jnp.arange(n, dtype=jnp.uint32)
        per_len = jnp.sum(keep[:, None] & (length[:, None] == jnp.arange(
            1, sigma + 1, dtype=jnp.uint32)), axis=0, dtype=jnp.int32)
    if n * sigma >= 0xFFFFFFFF:
        raise ValueError(f"{n} rows x sigma {sigma} cells overflow the "
                         "uint32 rank of the segment table")
    # a kept cell's rank is below n * sigma: the dead cells sort after it
    key = jnp.where(keep_c, rank, jnp.uint32(0xFFFFFFFF))
    cols = sort.sort_columns([key] + [c.astype(jnp.uint32) for c in cols],
                             num_keys=1)[1:]
    cap = cols[0].shape[0]
    chunks = tuple(
        jnp.concatenate([c[start:start + chunk] for c in cols])
        for start in range(0, cap, chunk))
    return per_len, chunks


# ----------------------------------------------------------- canonical output
def canonical_stats(stats):
    """Canonical row order + dedup of a job output: sort by (length, terms
    lexicographic) and sum counts of identical grams -- exactly the order an
    :class:`~repro.index.build.IndexSegment` stores (length | packed lanes
    ascending), so a wave run folded through the segment-merge path and a
    monolithic run land on bit-identical arrays.  Host-side int64, so no
    uint32 round trip; series ([R, B]) counts are carried whole.
    """
    from repro.core.stats import NGramStats
    grams = np.asarray(stats.grams, np.int32)
    lengths = np.asarray(stats.lengths, np.int32)
    counts = np.asarray(stats.counts)
    r, sigma = grams.shape
    if r == 0:
        return NGramStats(grams, lengths,
                          counts.astype(np.int64), dict(stats.counters))
    # np.lexsort: last key is primary -> (length, g[:,0], ..., g[:,sigma-1])
    order = np.lexsort(tuple(grams[:, i] for i in range(sigma - 1, -1, -1))
                       + (lengths,))
    g_s, l_s, c_s = grams[order], lengths[order], counts[order]
    prev_diff = np.any(g_s != np.roll(g_s, 1, axis=0), axis=1) | \
        (l_s != np.roll(l_s, 1))
    prev_diff[0] = True
    starts = np.flatnonzero(prev_diff)
    summed = np.add.reduceat(c_s.astype(np.int64), starts, axis=0)
    return NGramStats(g_s[starts], l_s[starts], summed, dict(stats.counters))
