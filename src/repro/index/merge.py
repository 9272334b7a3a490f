"""Incremental index maintenance: k-way segment merge + generational (LSM) index.

The job side emits one frozen artifact per run; before this module, refreshing
the served index under a growing corpus meant re-sorting *everything*.  The
sorted immutable :class:`~repro.index.build.IndexSegment` is the unit of
composition (Pibiri & Venturini's layout observation), so freshness becomes the
classic log-structured-merge discipline instead:

  * :func:`merge_segments` -- k-way merge of sorted segments into one new
    segment with duplicate grams' counts *summed*, optionally dropping rows
    whose summed count is under ``min_count``.  Two routes, both
    host-resident end to end and bit-identical (the output order is
    ascending (length | packed lanes), a pure function of the row set):
    ``"kway"`` exploits the inputs' sortedness on the host -- a stable sort
    of the concatenated big-endian row bytes is a galloping k-way merge
    (timsort detects the k presorted runs), an order of magnitude cheaper
    than re-sorting blind -- and folds duplicate counts exactly in int64 via
    ``np.add.reduceat``; it is the CPU backend's fold and the LSM index's.
    ``"device"`` is the blocked fold on the chip, the wave job's fold off
    the CPU: the host cuts the sorted inputs into key-range blocks of at
    most :func:`device_block_rows` rows, fewer the wider the keys (a gram
    never straddles two; on an accelerator every block of one key width is
    padded to that one shape, whose programs :func:`load_block_programs`
    readies up front), and one jitted program of fixed shape sorts each
    block, sums its duplicate counts exactly in 8-bit limbs, drops rows
    under ``min_count`` and compacts the survivors to the front, so only
    they come back to the host; blocks are dispatched asynchronously, the
    next one assembled while the device folds the last, and there is no
    size cap.  The device fold is exact below ``_MAX_DEVICE_RUN``
    duplicates per gram; a block with a longer run replays on the host in
    int64.  Both routes refuse loudly if a merged cf overflows the uint32
    device lanes (mirroring the continuation-mass guard in ``build.py``).
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

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from functools import partial, reduce

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from repro.core.stats import NGramStats
from repro.mapreduce import pack as packing
from repro.mapreduce import sort as mr_sort
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from ._layout import SENTINEL, pad_rows, round_capacity, row_bytes_view
from .build import IndexSegment, index_from_segment
from .compress import CompressedNGramIndex, compress_index, decode_segment

DEFAULT_SIZE_RATIO = 4
_U32_MAX = np.iinfo(np.uint32).max

# The device fold's limbed count sums are trusted only while every run is
# shorter than this; a merge of k segments with distinct rows each has runs
# of length <= k, so the device fold covers everything but adversarial
# duplicate floods.
_MAX_DEVICE_RUN = 1 << 16

# Most rows in a block of the "device" route's blocked fold: at most 2**24,
# so the 8-bit count limbs' prefix sums over a block stay below 2**32.
DEVICE_BLOCK_ROWS = 1 << 24
# Key bytes in a block on an accelerator, whatever the input's size, so every
# fold of one key width runs one program shape: 2**24 rows of six columns
# (the sigma-5 job's keys), whose two blocks in flight hold about 2 GB of a
# v5e's HBM.  Wider keys get fewer rows (:func:`device_block_rows`).
DEVICE_BLOCK_BYTES = 4 * 6 << 24
# survivors come back in chunks of this many rows through one slice program
_SURVIVOR_CHUNK = 1 << 20


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


@jax.jit
def _merge_block(flat_keys: jax.Array, counts: jax.Array,
                 min_count: jax.Array):
    """Fold one key-range block of the ``"device"`` route on the chip.

    ``flat_keys`` [B * C] (the row-major key rows, flat, so the host-to-device
    copy needs no relayout on the host) and ``counts`` [B] hold the block's
    rows from every input segment, sentinel-padded with count 0.  The lanes
    are split out on the device by strided slices of 128-row groups, which
    never pads a [B, C] array out to the chip's 128-wide tiles.  Sorts the
    rows in segment order,
    sums each gram's duplicate counts exactly, drops sentinels and grams
    whose sum is under ``min_count``, and compacts the survivors, in
    order, to the front.  The sums run in four 8-bit limbs, whose prefix
    sums stay below 2**32 over B <= 2**24 rows: a run's limb sum is its
    end's prefix less the prefix before its start, carried forward by a
    running max, so no gather or scatter is needed.  Returns
    (keys [B, C], totals [B], n_keep, overflow?, max_run_len); the limbs are
    exact while runs stay under ``_MAX_DEVICE_RUN`` rows.  The module is
    named ``jit__merge_block`` in device traces.
    """
    n = counts.shape[0]
    n_cols = flat_keys.shape[0] // n
    group = min(128, n)
    rows = flat_keys.reshape(n // group, group * n_cols)
    lanes = [rows[:, j::n_cols].reshape(n) for j in range(n_cols)]
    *lanes, counts = mr_sort.sort_columns(lanes + [counts], num_keys=n_cols)
    differs = reduce(jnp.logical_or, [l[1:] != l[:-1] for l in lanes])
    first = jnp.ones((1,), bool)
    new_run = jnp.concatenate([first, differs])
    run_end = jnp.concatenate([differs, first])
    idx = jnp.arange(n, dtype=jnp.int32)
    run_len = idx + 1 - lax.cummax(jnp.where(new_run, idx, 0))
    limbs = []
    for b in range(4):
        x = (counts >> (8 * b)) & jnp.uint32(0xFF)
        c = jnp.cumsum(x, dtype=jnp.uint32)
        limbs.append(c - lax.cummax(jnp.where(new_run, c - x, 0)))
    lo = limbs[0] + (limbs[1] << 8)
    hi = limbs[2] + (limbs[3] << 8) + (lo >> 16)
    totals = (hi << 16) | (lo & jnp.uint32(0xFFFF))
    real = run_end & (lanes[0] != SENTINEL)
    keep = real & (totals >= min_count)
    out = mr_sort.sort_columns([jnp.where(keep, idx, n)] + lanes + [totals],
                               num_keys=1)
    return (jnp.stack(out[1:-1], axis=1), out[-1],
            jnp.sum(keep, dtype=jnp.int32), jnp.any(real & (hi > 0xFFFF)),
            jnp.max(jnp.where(real, run_len, 0)))


@partial(jax.jit, static_argnames=("size",))
def _survivor_chunk(keys: jax.Array, totals: jax.Array, start: jax.Array, *,
                    size: int):
    """``size`` rows of a folded block from ``start``: one program for
    every chunk, whatever a block keeps."""
    return (lax.dynamic_slice_in_dim(keys, start, size),
            lax.dynamic_slice_in_dim(totals, start, size))


def _lower_bound(keys: np.ndarray, lo: int, hi: int, key: tuple) -> int:
    """First row of sorted ``keys[lo:hi]`` not below ``key``: a binary
    search of O(log n) row comparisons, with no byte view of the rows."""
    while lo < hi:
        mid = (lo + hi) // 2
        if tuple(keys[mid].tolist()) < key:
            lo = mid + 1
        else:
            hi = mid
    return lo


def _block_cuts(views: list[np.ndarray], rows: int) -> list[list[tuple]]:
    """Cut sorted unique key arrays into key-range blocks of <= ``rows`` rows.

    Splitters are rows of the largest input at evenly spaced ranks; every
    input is cut at each splitter's lower bound, so all copies of a gram
    land in one block (``rows`` >= the number of inputs then guarantees
    progress).  A block still over ``rows`` is cut again at the middle row
    of its largest piece.  Returns, per block, one (lo, hi) row range per
    input, in key order.
    """
    total = sum(len(v) for v in views)
    big = views[int(np.argmax([len(v) for v in views]))]
    n_blocks = -(-total // (rows - rows // 16))     # aim 15/16 full
    cuts = [[0] * len(views)]
    for j in range(1, n_blocks):
        key = tuple(big[j * len(big) // n_blocks].tolist())
        cuts.append([_lower_bound(v, c, len(v), key)
                     for v, c in zip(views, cuts[-1])])
    cuts.append([len(v) for v in views])
    todo = [list(zip(a, b)) for a, b in zip(cuts, cuts[1:])][::-1]
    blocks = []
    while todo:
        blk = todo.pop()
        n = sum(hi - lo for lo, hi in blk)
        if n <= rows:
            if n:
                blocks.append(blk)
            continue
        p = int(np.argmax([hi - lo for lo, hi in blk]))
        key = tuple(views[p][(blk[p][0] + blk[p][1]) // 2].tolist())
        mid = [_lower_bound(v, lo, hi, key) for v, (lo, hi) in zip(views, blk)]
        todo.append([(m, hi) for m, (_, hi) in zip(mid, blk)])
        todo.append([(lo, m) for m, (lo, _) in zip(mid, blk)])
    return blocks


def device_block_rows(n_cols: int) -> int:
    """Rows of a block of keys ``n_cols`` columns wide on an accelerator:
    the largest power of two whose key rows fit ``DEVICE_BLOCK_BYTES``, at
    most ``DEVICE_BLOCK_ROWS`` (2**24 rows at six columns, 2**19 at 101)."""
    rows = DEVICE_BLOCK_BYTES // (4 * n_cols)
    return min(DEVICE_BLOCK_ROWS, 1 << (rows.bit_length() - 1))


def _block_rows(total: int, k: int, n_cols: int) -> int:
    """Rows of every block of a fold of ``total`` rows from ``k`` inputs.

    On an accelerator always :func:`device_block_rows` of the key width: one
    compiled program serves every fold of that width, which
    :func:`load_block_programs` readies up front.  The CPU backend sizes the
    block to the input instead (the next power of two >= ``total`` and
    >= ``k``, at most that), so small folds stay small there.
    """
    cap = device_block_rows(n_cols)
    if jax.default_backend() != "cpu":
        return cap
    return max(min(cap, 1 << (total - 1).bit_length()),
               1 << (k - 1).bit_length())


def _compile_block_programs(rows: int, n_cols: int) -> None:
    u32 = partial(jax.ShapeDtypeStruct, dtype=jnp.uint32)
    _merge_block.lower(u32((rows * n_cols,)), u32((rows,)), u32(())).compile()
    _survivor_chunk.lower(u32((rows, n_cols)), u32((rows,)),
                          jax.ShapeDtypeStruct((), jnp.int32),
                          size=min(_SURVIVOR_CHUNK, rows)).compile()


_block_loads: dict[tuple[int, int], Future] = {}
_block_loads_lock = threading.Lock()
_block_loader = ThreadPoolExecutor(1, thread_name_prefix="block-programs")


def load_block_programs(n_cols: int) -> Future | None:
    """Compile, or load from the compile cache, the accelerator's block
    programs for keys of ``n_cols`` columns, once a process, on a side
    thread: no data moves and nothing runs on the device.  Returns the
    load's future (its ``result()`` raises what the compile raised), or
    ``None`` on the CPU backend, whose block shape follows each input.
    Later folds of that width reuse the compiled programs.
    """
    if jax.default_backend() == "cpu":
        return None
    key = (device_block_rows(n_cols), n_cols)
    with _block_loads_lock:
        if key not in _block_loads:
            _block_loads[key] = _block_loader.submit(
                _compile_block_programs, *key)
        return _block_loads[key]


def _fold_blocks_device(segs: list[IndexSegment], *, min_count: int | None):
    """The ``"device"`` route: blocked fold on the chip, survivors only back.

    Returns host (keys, uint32 totals, blocks run).  Each block's pieces are
    copied into one of two reused host buffers, sentinel-filled past the
    rows; block i + 1 is assembled and copied to the device while block i
    folds, and block i's survivors are sliced before block i + 1 is queued
    behind it.  A buffer is refilled only after the block that last used it
    has finished.  Span ``merge.device.block`` (rows, kept) runs from the
    block's dispatch until its survivors are on the host.
    """
    sigma, vocab = segs[0].sigma, segs[0].vocab_size
    views = [np.asarray(s.keys, np.uint32)[:s.n_rows] for s in segs]
    cnts = [np.asarray(s.counts, np.uint32)[:s.n_rows] for s in segs]
    n_cols = views[0].shape[1]
    total = sum(len(v) for v in views)
    if not total:
        return np.zeros((0, n_cols), np.uint32), np.zeros((0,), np.uint32), 0
    rows = _block_rows(total, len(segs), n_cols)
    if rows > 1 << 24:
        raise ValueError(f"device blocks of {rows} rows overflow the 8-bit "
                         "count limbs' prefix sums (at most 2**24 rows)")
    blocks = _block_cuts(views, rows)
    bufs = [(np.empty((rows, n_cols), np.uint32), np.empty((rows,), np.uint32))
            for _ in range(min(2, len(blocks)))]
    thr = np.uint32(min(max(min_count or 0, 0), _U32_MAX))
    chunk = min(_SURVIVOR_CHUNK, rows)

    def stage(i):
        k_buf, c_buf = bufs[i % 2]
        off = 0
        for (lo, hi), v, c in zip(blocks[i], views, cnts):
            k_buf[off:off + hi - lo] = v[lo:hi]
            c_buf[off:off + hi - lo] = c[lo:hi]
            off += hi - lo
        k_buf[off:] = SENTINEL
        c_buf[off:] = 0
        return jnp.asarray(k_buf.reshape(-1)), jnp.asarray(c_buf)

    out_keys, out_tot = [], []
    running = _merge_block(*stage(0), thr)
    for i, blk in enumerate(blocks):
        with obs_trace.span("merge.device.block") as sp:
            staged = stage(i + 1) if i + 1 < len(blocks) else None
            b_keys, b_tot, n_keep, overflow, max_run = running
            n_keep = int(n_keep)
            bad = bool(overflow) or int(max_run) >= _MAX_DEVICE_RUN
            if not bad:
                parts = [_survivor_chunk(b_keys, b_tot, np.int32(s), size=chunk)
                         for s in range(0, n_keep, chunk)]
            running = _merge_block(*staged, thr) if staged else None
            if bad:
                # a run past the limbs or a total past uint32: replay the
                # block on the host in int64 (its overflow guard raises)
                k, t = _kway_fold_host(
                    [IndexSegment(keys=v[lo:hi], counts=c[lo:hi], sigma=sigma,
                                  vocab_size=vocab)
                     for (lo, hi), v, c in zip(blk, views, cnts)], sigma=sigma)
                keep = t >= thr
                k, t = k[keep], t[keep]
            else:
                k = np.concatenate([np.zeros((0, n_cols), np.uint32)] + [
                    np.asarray(pk) for pk, _ in parts])[:n_keep]
                t = np.concatenate([np.zeros((0,), np.uint32)] + [
                    np.asarray(pt) for _, pt in parts])[:n_keep]
            out_keys.append(k)
            out_tot.append(t)
            if sp:
                sp.set(rows=sum(hi - lo for lo, hi in blk), kept=len(t))
    return np.concatenate(out_keys), np.concatenate(out_tot), len(blocks)


def merge_segments(segments, *, route: str = "kway",
                   pad_to: int | None = None, min_count: int | None = None,
                   n_compressed: int | None = None) -> IndexSegment:
    """Merge sorted segments into one, summing counts of duplicate grams.

    ``route="kway"`` folds on the host exploiting the inputs' sortedness
    (stable sort of concatenated big-endian row bytes == galloping k-way
    merge; int64 ``reduceat`` count fold); ``route="device"`` is the
    blocked fold on the chip (key-range blocks of a fixed byte budget on
    an accelerator, survivors only back to the host, no size cap).  Both
    routes are bit-identical and return a host-resident segment.  Raises
    ``ValueError`` on any other route, or if any merged count overflows the
    uint32 device lanes.

    ``min_count`` drops merged rows whose summed count is below it before
    the result is padded: on the ``"device"`` route before they leave the
    chip.  ``None`` keeps every row.

    ``n_compressed`` is purely observational: callers that decoded some
    inputs from the compressed layout record the flat/compressed mix on the
    ``merge.segments`` span.
    """
    return _merge(segments, route=route, pad_to=pad_to, min_count=min_count,
                  n_compressed=n_compressed)[0]


def _merge(segments, *, route: str, pad_to: int | None = None,
           min_count: int | None = None,
           n_compressed: int | None = None) -> tuple[IndexSegment, int]:
    """:func:`merge_segments`, and the number of device blocks it ran."""
    if route not in ("kway", "device"):
        raise ValueError(f"unknown merge route {route!r}")
    segs = list(segments)
    if not segs:
        raise ValueError("cannot merge zero segments")
    sigma, vocab = segs[0].sigma, segs[0].vocab_size
    for s in segs[1:]:
        if (s.sigma, s.vocab_size) != (sigma, vocab):
            raise ValueError(
                f"segment meta mismatch: ({s.sigma}, {s.vocab_size}) vs "
                f"({sigma}, {vocab})")
    with obs_trace.span("merge.segments") as sp:
        if sp:
            sp.set(n_segments=len(segs),
                   rows_in=sum(int(s.keys.shape[0]) for s in segs))
            if n_compressed is not None:
                sp.set(n_compressed=n_compressed,
                       n_flat=len(segs) - n_compressed)
        return _merge_segments_body(segs, sigma, vocab, route=route,
                                    pad_to=pad_to, min_count=min_count)


def _merge_segments_body(segs, sigma, vocab, *, route, pad_to, min_count):
    blocks = 0
    if route == "device":
        r_keys, r_tot, blocks = _fold_blocks_device(segs, min_count=min_count)
    else:
        r_keys, r_tot = _kway_fold_host(segs, sigma=sigma)
        if min_count is not None:
            with obs_trace.span("merge.filter"):
                keep = r_tot >= min_count
                r_keys, r_tot = r_keys[keep], r_tot[keep]
    r = int(r_keys.shape[0])
    size = pad_to if pad_to is not None else round_capacity(r)
    if size < r + 1:
        raise ValueError(f"pad_to={size} < n_rows+1={r + 1}")
    with obs_trace.span("merge.pad"):
        keys_p = pad_rows(r_keys, size, SENTINEL)
        cnts_p = pad_rows(r_tot, size, 0)
    return IndexSegment(keys=keys_p, counts=cnts_p, sigma=sigma,
                        vocab_size=vocab), blocks


def _merge_input_segment(entry) -> IndexSegment:
    """Host segment view of one merge input, compressed-native when needed.

    Flat entries pass through (``to_segment`` on an :class:`NGramIndex` is a
    field read); compressed entries stream-decode block chunks through
    :func:`~repro.index.compress.decode_segment` -- O(chunk) peak decoded
    working set, never a whole decoded table.  Both routes take the
    unpadded host segment straight in.
    """
    if isinstance(entry, CompressedNGramIndex):
        return decode_segment(entry)
    return entry if isinstance(entry, IndexSegment) else entry.to_segment()


def merge_indexes(indexes, *, route: str = "kway", pad_to: int | None = None):
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
    seg = merge_segments([_merge_input_segment(ix) for ix in ixs],
                         route=route, n_compressed=sum(
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


class DeferredSegmentAccumulator:
    """Stack every wave segment; fold once, k-way, at :meth:`result`.

    The wave engine's fold.  A :meth:`run` fold never reads intermediate
    merged state -- only ``result`` -- so the total fold work is exactly
    *one* k-way merge over the raw wave partials (O(total) rows through
    :func:`merge_segments`, which the ``"kway"`` route turns into a single
    galloping host merge and the ``"device"`` route into fixed-shape blocks
    folded on the chip).  Memory: all wave partials stay live until
    ``result`` -- O(total tau=1 rows), the same order as the merged segment.

    ``fold_rows`` counts every input row fed through the fold, and
    ``finalize_blocks`` the blocks the ``"device"`` route folded.
    """

    def __init__(self, *, route: str = "kway"):
        self.route = route
        self.segs: list[IndexSegment] = []
        self._rows: list[int] = []
        self.fold_rows = 0
        self.finalize_blocks = 0

    def push(self, seg: IndexSegment, *, n_rows: int | None = None) -> None:
        """Stack one segment; ``n_rows`` (when the caller already knows it)
        skips the segment's own host-side row count."""
        self.segs.append(seg)
        self._rows.append(seg.n_rows if n_rows is None else n_rows)

    def result(self, *, min_count: int | None = None) -> IndexSegment:
        """The one k-way fold of every pushed segment.

        ``min_count`` drops merged rows under it inside the merge (on the
        ``"device"`` route, before they leave the chip); such a filtered
        result is not kept as the accumulator's state.  A lone segment comes
        back as it was pushed, unfiltered.
        """
        if not self.segs:
            raise ValueError("no segments accumulated")
        if len(self.segs) == 1:
            return self.segs[0]
        self.fold_rows += sum(self._rows)
        merged, blocks = _merge(self.segs, route=self.route,
                                min_count=min_count)
        self.finalize_blocks += blocks
        if min_count is None:
            self.segs = [merged]
            self._rows = [merged.n_rows]
        return merged


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
                 block_size: int = 4, size_ratio: int = DEFAULT_SIZE_RATIO):
        if size_ratio < 1:
            raise ValueError("size_ratio must be >= 1")
        self.sigma = sigma
        self.vocab_size = vocab_size
        self.compress = compress
        self.block_size = block_size
        self.size_ratio = size_ratio
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
        the stack (tests bootstrap with pre-built artifacts);
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
        # elder segments first (the merged order is a pure function of the
        # row set, so any input order gives the same segment); compaction
        # works on segment views (any cached artifact of a merged level
        # dies with it -- the merged level rebuilds lazily if read);
        # compressed rungs stream-decode chunk by chunk, never a full table
        with obs_trace.span("gen.compact") as sp:
            rows_in = sum(ix.n_rows for ix in self._levels[:n])
            merged = merge_segments(
                [_merge_input_segment(e) for e in reversed(self._levels[:n])],
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
        """Force-merge every live segment into one (maintenance)."""
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
