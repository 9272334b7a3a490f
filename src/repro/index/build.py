"""Freeze a finished SUFFIX-sigma job into a device-resident, queryable index.

The job leaves an ``NGramStats`` blob -- (gram, cf) rows in arbitrary order -- whose
only lookup path is a Python dict.  Following Pibiri & Venturini's observation that
the post-job win is a *sorted, compressed, immutable* layout, the build is split in
two along the line the generational (LSM-style) index composes over:

  * :func:`segment_from_stats` packs the rows into the shuffle/sort phases' own
    packed-lane record format (``mapreduce.pack``) and sorts them in the same
    lexicographic order into an :class:`IndexSegment` -- the sorted immutable
    run of (length | lanes, cf) rows that is the unit of merge
    (``index/merge.py``).  Builds pack and sort on the host: their row counts
    differ from build to build, and a device sort compiles anew for each;
  * :func:`index_from_segment` derives the acceleration structures from any
    sorted segment, whether it came from a job or from a k-way merge of older
    segments:

      - **per-length sections** -- ``section_start[l]`` delimits the length-(l+1)
        section, so a point query binary-searches only rows of its own length;
      - **first-term fanout table** -- within each section, rows of equal lead
        term are contiguous, so ``fanout[l-1, b] .. fanout[l-1, b+1]`` brackets
        the rows whose lead-term bucket is ``b`` (Lemire & Kaser's "one hash
        narrows the hot path", as a monotone table instead of a filter);
      - the **continuation view** -- the same rows re-ordered by (|gram|, packed
        *prefix* lanes, cf desc, next term asc), plus the running-mass
        ``cont_cumsum``.  The final next-term key makes the order a pure
        function of the row *set* (not of input order), which is what lets a
        merged segment rebuild bit-identical structures to a from-scratch build.

``build_index`` is their composition.  Everything is a flat jnp array
(registered dataclass pytrees), so artifacts can be ``device_put`` whole,
stacked along a leading shard axis (``serve.py``), and closed over by jitted
query functions.  Counts are stored as uint32 on device (cf <= total tokens;
the int64 path stays on the host-side ``NGramStats``).
"""
from __future__ import annotations

import bisect
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bsearch import search_steps  # re-export: queries need it
from repro.mapreduce import pack as packing
from repro.core.stats import NGramStats
from ._layout import (MAX_FANOUT, SENTINEL, fanout_layout, pad_rows,
                      round_capacity, row_bytes_view, row_offsets)

_SENTINEL = SENTINEL   # backwards-compat alias (pre-_layout name)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class IndexSegment:
    """One sorted immutable run of n-gram rows -- the unit of merge.

    Rows are sorted by (length | packed lanes); rows 0..n_rows-1 are real, the
    tail is all-ones sentinels that sort after every real row.  Both
    :class:`NGramIndex` (which stores a segment verbatim plus derived
    structures) and :class:`~repro.index.compress.CompressedNGramIndex` (which
    re-encodes one, and decodes back via ``to_segment``) wrap this abstraction;
    ``index/merge.py`` consumes and produces it.
    """

    keys: jax.Array    # [size, 1+L] uint32: (row length | packed gram lanes)
    counts: jax.Array  # [size] uint32 collection frequencies (0 on sentinels)
    sigma: int = dataclasses.field(metadata=dict(static=True))
    vocab_size: int = dataclasses.field(metadata=dict(static=True))

    @property
    def size(self) -> int:
        return int(self.keys.shape[-2])

    @property
    def n_lanes(self) -> int:
        return int(self.keys.shape[-1]) - 1

    @property
    def lanes(self) -> jax.Array:
        """Packed gram lanes [..., size, L] (the length column stripped)."""
        return self.keys[..., 1:]

    @property
    def n_rows(self) -> int:
        """Real (non-sentinel) rows; the length column is the primary sort key,
        so one host-side binary search recovers the boundary -- on a host
        segment straight on the strided column view, O(log n) row reads and
        no copy of the column.  Cached on first read (segments are
        immutable; compaction polls row counts per ingest, which would
        otherwise re-sync the device per poll)."""
        cached = self.__dict__.get("_n_rows")
        if cached is None:
            lens = self.keys[..., 0]
            if not isinstance(lens, np.ndarray):
                lens = np.asarray(lens)
            cached = bisect.bisect_right(lens, self.sigma)
            object.__setattr__(self, "_n_rows", cached)
        return cached

    @property
    def nbytes(self) -> int:
        return sum(int(np.asarray(f).nbytes) for f in (self.keys, self.counts))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class NGramIndex:
    """Immutable device-resident n-gram index (see module docstring).

    Wraps the point-lookup :class:`IndexSegment` (rows sorted by (length, lex
    packed lanes); sentinel tail) plus the derived acceleration structures.
    """

    # --- point-lookup view: the sorted segment itself ----------------------------
    segment: IndexSegment
    section_start: jax.Array  # [sigma+1] int32: section l+1 = rows [s[l], s[l+1])
    fanout: jax.Array         # [sigma, n_fanout+1] int32 lead-term bucket offsets
    # --- continuation view: rows sorted by (length, prefix lanes, cf desc) -------
    cont_prefix: jax.Array    # [size, L] uint32 packed lanes of the length-1 prefix
    cont_last: jax.Array      # [size]    uint32 final term of each gram
    cont_counts: jax.Array    # [size]    uint32 cf, descending within prefix group
    cont_fanout: jax.Array    # [sigma, n_fanout+1] int32 prefix-lead bucket offsets
    cont_cumsum: jax.Array    # [size+1]  uint32 running sum of cont_counts
    # --- static meta (part of the treedef; identical across shards) --------------
    sigma: int = dataclasses.field(metadata=dict(static=True))
    vocab_size: int = dataclasses.field(metadata=dict(static=True))
    size: int = dataclasses.field(metadata=dict(static=True))
    fanout_shift: int = dataclasses.field(metadata=dict(static=True))
    n_fanout: int = dataclasses.field(metadata=dict(static=True))

    @property
    def lanes(self) -> jax.Array:
        """[..., size, L] uint32 packed gram lanes (the segment's, sans length)."""
        return self.segment.lanes

    @property
    def counts(self) -> jax.Array:
        """[..., size] uint32 collection frequencies."""
        return self.segment.counts

    @property
    def n_lanes(self) -> int:
        # last axis, so the property also holds for a [P, size, L] sharded stack
        return self.segment.n_lanes

    @property
    def n_rows(self) -> int:
        """Real (non-sentinel) rows; the last section end."""
        return int(self.section_start[-1])

    @property
    def nbytes(self) -> int:
        return self.segment.nbytes + sum(int(np.asarray(f).nbytes) for f in (
            self.section_start, self.fanout,
            self.cont_prefix, self.cont_last, self.cont_counts,
            self.cont_fanout, self.cont_cumsum))

    def to_segment(self) -> IndexSegment:
        """The point-view segment (shared arrays, no copy)."""
        return self.segment


def segment_from_stats(stats: NGramStats, *, vocab_size: int,
                       pad_to: int | None = None) -> IndexSegment:
    """Sort a finished job's rows into an :class:`IndexSegment`.

    Bucketed (time-series) counts are marginalized -- segments carry cf.
    ``pad_to`` fixes the padded capacity (default rounds R+1 up to 128).
    """
    grams = np.asarray(stats.grams, np.int32)
    lengths = np.asarray(stats.lengths, np.int32)
    counts = np.asarray(stats.counts)
    if counts.ndim == 2:
        counts = counts.sum(axis=1)
    counts = counts.astype(np.uint32)
    r, sigma = grams.shape
    size = pad_to if pad_to is not None else round_capacity(r)
    if size < r + 1:
        raise ValueError(f"pad_to={size} < n_rows+1={r + 1}")

    # host pack + sort: a device sort would compile anew per row count
    lanes = packing.pack_terms_np(grams, vocab_size=vocab_size)
    keys = np.concatenate([lengths.astype(np.uint32)[:, None], lanes], axis=1)
    order = np.argsort(row_bytes_view(keys), kind="stable")
    return IndexSegment(
        keys=jnp.asarray(pad_rows(keys[order], size, SENTINEL)),
        counts=jnp.asarray(pad_rows(counts[order], size, 0)),
        sigma=sigma, vocab_size=vocab_size)


def segment_from_wave_stats(stats: NGramStats, *,
                            vocab_size: int) -> IndexSegment:
    """Freeze one wave's partial into a sorted segment without a device trip.

    The single-device wave collector emits rows in reducer order: for every
    gram length, ascending packed lanes (the reducer walks the sorted record
    block).  A *stable* argsort on the length column alone -- a sigma-way
    counting sort, not a general sort -- therefore recovers full
    (length | packed lanes) segment order, and the final stable byte-view
    argsort degenerates to a linear verification pass (timsort on sorted
    input).  Rows from collectors without the ordering guarantee (e.g.
    hash-partitioned mesh partials) are genuinely sorted by that same pass.
    Everything runs in numpy (``pack_terms_np``), so the per-wave freeze
    costs ~a millisecond instead of an eager device pack+sort+transfer
    chain.

    The result is host-resident and unpadded (no sentinel tail) -- exactly
    what the k-way fold consumes; ``IndexSegment.n_rows`` still answers
    correctly, and any route of :func:`~repro.index.merge.merge_segments`
    accepts it.
    """
    grams = np.asarray(stats.grams, np.int32)
    lengths = np.asarray(stats.lengths, np.uint32)
    counts = np.asarray(stats.counts)
    if counts.ndim == 2:
        counts = counts.sum(axis=1)
    counts = counts.astype(np.uint32)
    sigma = int(grams.shape[1])
    lanes = packing.pack_terms_np(grams, vocab_size=vocab_size)
    keys = np.concatenate([lengths[:, None], lanes], axis=1).astype(np.uint32)
    order = np.argsort(keys[:, 0], kind="stable")
    keys = keys[order]
    counts = counts[order]
    full = np.argsort(row_bytes_view(keys), kind="stable")
    return IndexSegment(keys=keys[full], counts=counts[full], sigma=sigma,
                        vocab_size=vocab_size)


def index_from_segment(seg: IndexSegment, *,
                       pad_to: int | None = None) -> NGramIndex:
    """Derive the acceleration structures of a sorted segment -- the shared back
    half of ``build_index`` and of every incremental merge (``index/merge.py``),
    which is what makes merged and from-scratch indexes bit-identical.
    """
    sigma, vocab_size = seg.sigma, seg.vocab_size
    r = seg.n_rows
    keys = np.asarray(seg.keys)[:r]
    counts_s = np.asarray(seg.counts)[:r]
    len_s = keys[:, 0].astype(np.int64)
    lanes_s = keys[:, 1:]
    shift, n_fanout = fanout_layout(vocab_size)
    size = pad_to if pad_to is not None else round_capacity(r)
    if size < r + 1:
        raise ValueError(f"pad_to={size} < n_rows+1={r + 1}")

    grams = packing.unpack_terms_np(lanes_s, vocab_size=vocab_size,
                                    sigma=sigma)
    lead_s = grams[:, 0].astype(np.uint32)
    # combined (length, bucket) key is monotone: length is the primary sort key
    # and the lead term sits in lane 0's most-significant bits
    combined = len_s * n_fanout + (lead_s.astype(np.int64) >> shift)
    section_start = row_offsets(len_s, np.arange(1, sigma + 2))
    grid = (np.arange(1, sigma + 1)[:, None] * n_fanout
            + np.arange(n_fanout + 1)[None, :])
    fanout = np.minimum(row_offsets(combined, grid.reshape(-1)).reshape(
        sigma, n_fanout + 1), section_start[1:][:, None]).astype(np.int32)

    # ---- continuation view: (length | prefix lanes | cf desc | next term) -------
    # the trailing next-term key breaks (prefix, cf) ties deterministically, so
    # the view depends only on the row *set* -- merge parity leans on this
    lengths = len_s.astype(np.int32)
    prefix = grams * (np.arange(sigma)[None, :] < (lengths - 1)[:, None])
    p_lanes = packing.pack_terms_np(prefix, vocab_size=vocab_size)
    last = grams[np.arange(r), np.maximum(lengths - 1, 0)].astype(np.uint32) \
        if r else np.zeros((0,), np.uint32)
    p_lead = prefix[:, 0].astype(np.uint32)
    ckeys = np.concatenate([lengths.astype(np.uint32)[:, None], p_lanes,
                            (~counts_s.astype(np.uint32))[:, None],
                            last[:, None]], axis=1)
    n_l = seg.n_lanes
    corder = np.argsort(row_bytes_view(ckeys), kind="stable")
    ckeys_s, c_counts_s, c_lead_s = ckeys[corder], counts_s[corder], \
        p_lead[corder]
    cp_lanes_s = ckeys_s[:, 1:1 + n_l]
    c_last_s = ckeys_s[:, 2 + n_l]
    c_combined = (ckeys_s[:, 0].astype(np.int64) * n_fanout
                  + (np.asarray(c_lead_s, np.int64) >> shift))
    cont_fanout = np.minimum(row_offsets(c_combined, grid.reshape(-1)).reshape(
        sigma, n_fanout + 1), section_start[1:][:, None]).astype(np.int32)
    # running mass in int64 first: the total over all rows is ~sigma x corpus
    # tokens and can exceed uint32 even when every individual cf fits.  A wrap
    # would silently corrupt continuation totals, so refuse loudly instead --
    # sharding the index (serve.py) divides the mass per shard.
    mass = np.cumsum(np.asarray(c_counts_s, np.int64))
    if r and mass[-1] > np.iinfo(np.uint32).max:
        raise ValueError(
            f"total continuation mass {int(mass[-1])} overflows the uint32 "
            "device cumsum; build the index sharded (build_sharded_index) or "
            "raise tau")
    cont_cumsum = np.zeros((size + 1,), np.uint32)
    cont_cumsum[1:r + 1] = mass.astype(np.uint32)
    if r:
        cont_cumsum[r + 1:] = cont_cumsum[r]

    return NGramIndex(
        segment=IndexSegment(
            keys=jnp.asarray(pad_rows(keys.astype(np.uint32), size, SENTINEL)),
            counts=jnp.asarray(pad_rows(counts_s.astype(np.uint32), size, 0)),
            sigma=sigma, vocab_size=vocab_size),
        section_start=jnp.asarray(section_start),
        fanout=jnp.asarray(fanout),
        cont_prefix=jnp.asarray(pad_rows(cp_lanes_s.astype(np.uint32), size,
                                         SENTINEL)),
        cont_last=jnp.asarray(pad_rows(c_last_s.astype(np.uint32), size, 0)),
        cont_counts=jnp.asarray(pad_rows(np.asarray(c_counts_s, np.uint32),
                                         size, 0)),
        cont_fanout=jnp.asarray(cont_fanout),
        cont_cumsum=jnp.asarray(cont_cumsum),
        sigma=sigma, vocab_size=vocab_size, size=size,
        fanout_shift=shift, n_fanout=n_fanout,
    )


def build_index(stats: NGramStats, *, vocab_size: int,
                pad_to: int | None = None) -> NGramIndex:
    """Freeze ``stats`` (a finished job's output) into an :class:`NGramIndex`.

    ``pad_to`` fixes the padded row capacity (sharded builds pass a common
    capacity so shards stack into one array).
    """
    return index_from_segment(
        segment_from_stats(stats, vocab_size=vocab_size, pad_to=pad_to),
        pad_to=pad_to)
