"""Wave-based job execution: out-of-core n-gram jobs over a shared pipeline.

The monolithic single-device jobs in ``repro.core`` hold the whole token
array (and every intermediate record buffer) on the device at once, so corpus
size is capped by HBM.  Hadoop never has that cap: it streams splits through
map -> combine -> shuffle -> sort -> reduce *across machines*.
:class:`WaveExecutor` restores the streaming shape:

  * the corpus stays host-resident; fixed-size token *waves* (plus a
    ``sigma - 1`` token halo from the next wave, exactly the ppermute halo of
    the distributed jobs) move to the device one at a time, so the device
    working set is O(wave * sigma), independent of corpus size;
  * each wave runs the method's :class:`~repro.pipeline.plan.JobPlan` as
    **one fused jitted program** (``_wave_core``): every round's map emit,
    the combine -> shuffle-key -> sort -> reduce stage chain, and the tau=1
    carry updates feeding the next round all trace into a single donated XLA
    program, compiled once per plan and reused by every wave -- a wave is a
    single dispatch, not a per-stage (or per-round) chain of them;
  * where the caller collects each wave into a segment (the fold paths of
    :meth:`WaveExecutor.run` and :meth:`WaveExecutor.run_streaming`), the
    program's **segment form** also builds the wave's segment rows on the
    device, compacted in segment order (``stages.segment_table``), so the
    host copies only kept rows and sorts nothing;
  * the wave loop is **device-resident with an overlapped fold**
    (``_for_each_wave``): the main thread only slices host token slabs and
    dispatches fused wave programs, while a background fold thread
    materializes each wave and folds it (segment stack / generational
    ingest) -- so host-side fold work overlaps the next waves' device work
    instead of serializing with it, with a bounded in-flight queue keeping
    the memory model.  No per-wave host syncs ride the feeder's hot path --
    counters stay device scalars until collect time;
  * per-wave partials are produced at ``tau = 1`` -- a gram below tau in every
    wave can still be frequent globally, so nothing may be dropped early --
    and folded through the *segment merge* path (``index/merge.py``).  The
    fold **defers**: wave segments stack and merge once, k-way, at the end
    (:class:`~repro.index.merge.DeferredSegmentAccumulator`), with the
    global tau filter inside that one merge -- on the chip the blocked
    device fold, on the CPU backend one stable host sort over O(total)
    rows.  Both routes yield the same sorted segment, so the final output
    stays bit-identical to the monolithic job (canonical order);
  * with a ``mesh``, every wave is **distributed and just as fused**: the
    wave's extended window shards contiguously over the mesh axis and the
    *entire round chain* -- one ppermute sigma-1 halo pull, then every
    round's emit -> combine -> hash-partitioned ``all_to_all`` shuffle ->
    sort -> reduce, with APRIORI carries kept shard-local and
    device-resident between rounds -- traces into ONE jitted ``shard_map``
    program per wave (``_build_mesh_wave_program``), cached per
    ``(n_local, capacity scale, skew?)``.  Reduced lanes fold **on device**
    into packed segment-candidate rows (``stages.segment_candidates``, the
    key formula of the single-device segment form), so the host never
    rebuilds dense ``NGramStats`` per round/shard; shuffle overflow
    accumulates as a device scalar and is checked ONCE per wave at collect
    (the rare trip reruns the whole wave at doubled capacity), which is what
    lets mesh waves ride the same double-buffered dispatch + overlapped fold
    thread as the single-device path.  Bit-identical to the monolithic job.

A single-device job whose records are wider than ``SPLIT_HEAD_LANES``
lanes (sigma 100 at a web vocabulary) runs as a **head/tail split**
(``WaveExecutor._run_split``): an ordinary wave pass at the head width, a
hash table of its frequent full-length heads on the device, and a second
pass whose one program per wave (``_build_tail_program``) builds
sigma-wide records only at the positions that start a frequent head.

``run_streaming`` closes the loop with serving: each wave's partial goes
straight into :class:`~repro.index.merge.GenerationalIndex` ingest, so a
corpus that never fits on the device streams end to end into a queryable,
compacting index.

``run_plan`` is the one-wave degenerate case the ``repro.core`` methods now
delegate their single-device path to: whole corpus, legacy tau-per-round
semantics (APRIORI pruning at full strength), same counters as the old
monolithic code -- just one shared implementation of the stage plumbing.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.mapreduce import pack as packing
from repro.mapreduce import shuffle as mr_shuffle
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.pipeline import stages
from repro.pipeline.plan import JobPlan, plan_for

_SKEW_BUCKETS = 64   # nominal reducer count for the shuffle-skew counter

# jitted stage programs keyed by backend: buffer donation is decided per
# backend (a no-op with a warning on CPU), and the backend can change between
# calls (tests flip platforms, a driver may move from CPU warmup to TPU), so
# the decision must never be frozen at first call
_STAGE_CORE: dict[str, object] = {}

# fused whole-wave programs keyed by (backend, plan, cfg): every round's
# emit -> combine -> shuffle-key -> sort -> reduce plus the tau=1 carry
# updates traced into ONE jitted program, so a wave is a single dispatch.
# Both plan (frozen JobPlan of function refs) and cfg (frozen NGramConfig)
# hash by value, so distinct WaveExecutor instances over the same job share
# the compiled program (e.g. a fresh executor per job).
_WAVE_PROGRAMS: dict[tuple, object] = {}

# in-flight single-device waves beyond the one being folded: bounds the
# device/host footprint of the overlapped fold at O(wave * sigma) times a
# small constant while still keeping the device fed during host-side folds
_WAVES_IN_FLIGHT = 2

# A single-device job whose records are wider than this many lanes runs as a
# head/tail split (``WaveExecutor._run_split``): the head pass counts every
# gram of at most this many lanes' worth of terms, the tail pass the longer
# grams, at the positions whose head is frequent over the whole job.
SPLIT_HEAD_LANES = 8
# the tail program's survivor buffer holds a wave's positions over this; a
# wave with more survivors reruns with the buffer doubled
_TAIL_SHARE = 8
# the frequent-head hash table is padded to a power of two at least this
# long, so jobs of one size run one compiled tail program
_HEAD_TABLE_MIN = 1 << 16
# rows of one chunk of the segment form's table (``stages.segment_table``):
# a collect copies only the chunks that hold kept rows
SEGMENT_CHUNK_ROWS = 1 << 22
# lays out a wave's segment rows a chunk a task: numpy's copies release the
# interpreter lock, and the first touch of the fresh rows faults in parallel
_ROW_LAYOUT = ThreadPoolExecutor(min(8, os.cpu_count() or 1),
                                 thread_name_prefix="segment-rows")


def reset_stage_cache() -> None:
    """Drop the jitted stage programs (tests / backend reconfiguration)."""
    _STAGE_CORE.clear()
    _WAVE_PROGRAMS.clear()


def _stage_core(records, valid, **kw):
    backend = jax.default_backend()
    fn = _STAGE_CORE.get(backend)
    if fn is None:
        # buffer donation is a no-op (with a warning) on CPU; donate only
        # where it helps
        donate = (0,) if backend != "cpu" else ()
        fn = partial(
            jax.jit, donate_argnums=donate,
            static_argnames=("n_lanes", "has_bucket", "combine_route",
                             "use_kernels", "sigma", "lane_vocab",
                             "shuffle_key", "reduce_kind", "with_positions",
                             "n_buckets"))(_stage_core_impl)
        _STAGE_CORE[backend] = fn
    return fn(records, valid, **kw)


def _stage_core_impl(records, valid, *, n_lanes: int, has_bucket: bool,
                     combine_route: str | None, use_kernels: bool, sigma: int,
                     lane_vocab: int, shuffle_key: str, reduce_kind: str,
                     with_positions: bool, n_buckets: int):
    """combine -> shuffle-key -> sort -> reduce over one wave's records.

    The single jitted program every wave reuses; ``records`` is donated, so
    the map buffer's memory is recycled for the sort.  ``valid`` is the map
    emit's live mask: its sum (the ``map_records`` counter) rides the program
    as a device scalar so callers never host-sync before dispatch.  Returns
    (dense reducer outputs, map-record count, post-combine live-record count,
    partition histogram over ``_SKEW_BUCKETS`` nominal reducers -- the
    realized shuffle skew, and the sorted records' packed key lanes -- the
    direct-segment collector's raw material); all five stay device-resident
    until the caller's materialize sync.
    """
    map_rec = jnp.sum(valid)
    if combine_route is not None:
        with jax.named_scope("combine"):
            records = stages.combine(records, n_lanes, has_bucket,
                                     route=combine_route,
                                     use_kernels=use_kernels)
    live = records[:, n_lanes] > 0
    shuffled = jnp.sum(live)
    with jax.named_scope("partition"):
        key = stages.partition_keys(records, n_lanes, kind=shuffle_key,
                                    vocab_size=lane_vocab)
        # the real partitioner's bucketing (hash_u32 % P, invalid -> P), so
        # the skew counter measures realized reducer load, not raw-key spread
        bucket = mr_shuffle.partition_ids(key, live, _SKEW_BUCKETS)
        hist = jnp.bincount(bucket, length=_SKEW_BUCKETS + 1)[:_SKEW_BUCKETS]
    with jax.named_scope("sort"):
        rec = stages.sort_stage(records, n_keys=n_lanes)
    with jax.named_scope("reduce"):
        if reduce_kind == "suffix":
            dense = stages.reduce_suffix(rec, sigma=sigma,
                                         vocab_size=lane_vocab,
                                         n_buckets=n_buckets,
                                         use_kernels=use_kernels)
        else:
            dense = stages.reduce_exact(rec, sigma=sigma,
                                        vocab_size=lane_vocab,
                                        with_positions=with_positions)
    return dense, map_rec, shuffled, hist, rec[:, :n_lanes]


def _build_wave_program(cfg, plan: JobPlan, segment: bool = False):
    """Trace one wave's FULL round chain into a single jitted program.

    Every round's map emit, the fused stage core, and the tau=1 carry update
    feeding the next round (``plan.py``'s traceability contract: under the
    wave regime carries are pure jnp functions of the emit-side evidence)
    compile into one donated XLA program -- a wave is one dispatch, not a
    per-stage (or per-round) chain of them.  ``n_live`` is a traced scalar so
    the partial final wave reuses the same executable, and position payloads
    are skipped (``with_positions=False``): only tau>1 carries consume them,
    which the wave regime never takes.

    Each round returns ``(reduced, map-record count, post-combine
    live-record count, skew histogram)``.  The dense form's ``reduced`` is
    the reducer's (terms, flags, counts) and the sorted key lanes, for the
    stats collect; the ``segment`` form's is the round's finished segment
    rows, compacted on the device in segment order
    (``stages.segment_table``: per-length counts and the chunks of the
    lane-major table), for the collect of a wave into a segment.  That
    form needs the record lanes in the segment layout
    (``WaveExecutor._direct``).

    Each stage traces under a ``jax.named_scope`` (emit, combine, partition,
    sort, reduce, segment_table), so a profile's op metadata names the
    stage of every device op.  Both forms keep the function's name: the
    benchmark's readers find the program as the XLA module ``jit_wave_fn``.
    """
    lane_vocab = plan.effective_lane_vocab(cfg)
    n_l = packing.n_lanes(cfg.sigma, lane_vocab)
    combine_route = plan.combine.route if plan.combine is not None else None
    if segment:
        masks = jnp.asarray(packing.prefix_lane_masks(cfg.sigma, lane_vocab))
        chunk = SEGMENT_CHUNK_ROWS

    def wave_fn(tok_ext, n_live):
        carry = None
        rounds = []
        for k in range(1, plan.rounds + 1):
            with jax.named_scope("emit"):
                records, valid, emit_extras = plan.map.emit(
                    tok_ext, None, n_live, cfg, carry, k)
            dense, map_rec, shuffled, hist, lanes = _stage_core_impl(
                records, valid, n_lanes=n_l, has_bucket=False,
                combine_route=combine_route, use_kernels=cfg.use_kernels,
                sigma=cfg.sigma, lane_vocab=lane_vocab,
                shuffle_key=plan.shuffle.key, reduce_kind=plan.reduce.kind,
                with_positions=False, n_buckets=0)
            if segment:
                with jax.named_scope("segment_table"):
                    reduced = stages.segment_table(
                        dense[1], dense[2], lanes, masks, sigma=cfg.sigma,
                        reduce_kind=plan.reduce.kind, chunk=chunk)
            else:
                reduced = (dense[:3], lanes)
            rounds.append((reduced, map_rec, shuffled, hist))
            if k < plan.rounds and plan.update_carry is not None:
                carry = plan.update_carry(cfg, 1, k, tok_ext, None, {},
                                          emit_extras, carry)
        return tuple(rounds)

    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(wave_fn, donate_argnums=donate)


def _wave_core(cfg, plan: JobPlan, tok_ext, n_live: int,
               segment: bool = False):
    """Dispatch one wave through the cached fused program (one dispatch)."""
    key = (jax.default_backend(), plan, cfg, segment and SEGMENT_CHUNK_ROWS)
    fn = _WAVE_PROGRAMS.get(key)
    if fn is None:
        fn = _WAVE_PROGRAMS[key] = _build_wave_program(cfg, plan, segment)
    return fn(tok_ext, n_live)


def _build_tail_program(cfg, head: int):
    """Trace the tail pass of one split wave into a single jitted program.

    A live position of the window (``sigma - 1`` halo) survives when its
    first ``head`` terms are all real and their packed lanes hash into
    ``table``, the sorted hashes of the job's frequent heads
    (``core.common.membership_hashes``).  The survivors' positions are
    compacted into a buffer of ``capacity`` rows first; only for them are
    sigma-wide suffix records built, sorted and reduced.  Returns the
    counts of the lengths past ``head`` [capacity, sigma - head], the
    sorted key lanes [capacity, n_lanes] and the number of survivors, of
    which the buffer holds the first ``capacity``.

    A hash collision only lets through a position whose head is not
    frequent: every gram it starts is then infrequent, and the job's tau
    drops it.  The module is ``jit_tail_fn`` in device traces.
    """
    from repro.core.common import gram_hash, member
    from repro.core.suffix_sigma import suffix_windows
    sigma, vocab = cfg.sigma, cfg.vocab_size
    n_l = packing.n_lanes(sigma, vocab)

    def tail_fn(tok_ext, n_live, table, *, capacity):
        n = tok_ext.shape[0]
        with jax.named_scope("heads"):
            heads, _ = suffix_windows(tok_ext, head)
            ok = (heads[:, head - 1] != 0) & (jnp.arange(n) < n_live)
            ok = ok & member(table, gram_hash(
                packing.pack_terms(heads, vocab_size=vocab)))
            n_ok = jnp.sum(ok, dtype=jnp.int32)
        with jax.named_scope("compact"):
            pos = jnp.nonzero(ok, size=capacity, fill_value=n)[0]
        with jax.named_scope("emit"):
            # the fill position n reads only the zeros past the window
            padded = jnp.concatenate([tok_ext,
                                      jnp.zeros((sigma,), tok_ext.dtype)])
            win = jnp.stack([padded[pos + j] for j in range(sigma)], axis=1)
            win = win * jnp.cumprod((win != 0).astype(win.dtype), axis=1)
            records = jnp.concatenate(
                [packing.pack_terms(win, vocab_size=vocab),
                 (win[:, :1] != 0).astype(jnp.uint32)], axis=1)
        with jax.named_scope("sort"):
            rec = stages.sort_stage(records, n_keys=n_l)
        with jax.named_scope("reduce"):
            _, _, counts = stages.reduce_suffix(rec, sigma=sigma,
                                                vocab_size=vocab)
        return counts[:, head:], rec[:, :n_l], n_ok

    return jax.jit(tail_fn, static_argnames=("capacity",))


def _tail_core(cfg, head: int, tok_ext, n_live: int, table, capacity: int):
    """Dispatch one wave's tail pass through its cached program."""
    key = ("tail", jax.default_backend(), cfg, head)
    fn = _WAVE_PROGRAMS.get(key)
    if fn is None:
        fn = _WAVE_PROGRAMS[key] = _build_tail_program(cfg, head)
    return fn(tok_ext, n_live, table, capacity=capacity)


def _head_table(lanes: np.ndarray):
    """Sorted hashes of the frequent heads' packed lanes, padded to a
    power of two of at least ``_HEAD_TABLE_MIN`` rows (the padding hashes to
    the largest value, which at worst lets a few positions through)."""
    from repro.core.common import membership_hashes
    size = max(_HEAD_TABLE_MIN, 1 << max(len(lanes) - 1, 0).bit_length())
    padded = np.zeros((size, lanes.shape[1]), np.uint32)
    padded[:len(lanes)] = lanes
    return membership_hashes(jnp.asarray(padded),
                             jnp.arange(size) < len(lanes))


def _prefix_rows(keep, counts, lanes, masks, first_len: int = 1):
    """Segment rows (length | prefix lanes) and counts of the kept cells of a
    reducer's [rows, lengths] grid, whose column j holds length
    ``first_len + j``; a kept row of length l has key lanes ``lanes &
    masks[l]``.  Rows come out of ``nonzero(keep.T)`` in (length, lane rank)
    order, which is segment order."""
    lens0, rows = np.nonzero(keep.T)
    lengths = (lens0 + first_len).astype(np.uint32)
    pref = lanes[rows] & masks[lengths]
    return (np.concatenate([lengths[:, None], pref], axis=1).astype(np.uint32),
            counts[rows, lens0].astype(np.uint32))


def _table_chunks(chunks, n_rows: int, n_cols: int):
    """The chunks of a segment table (``stages.segment_table``, ``n_cols``
    columns) that hold its first ``n_rows`` rows."""
    rows = chunks[0].shape[0] // n_cols
    return chunks[:-(-n_rows // rows)]


def _table_rows(per_lens, tables, n_l: int):
    """Segment rows (length | key lanes) and counts of the kept rows of
    each round's segment table, the rounds one after another, and the row
    at which each round starts.  ``per_lens[k]`` counts round ``k``'s kept
    rows by length, which gives the length column; ``tables[k]`` holds the
    host copies of the chunks that hold them."""
    starts = np.cumsum([0] + [int(p.sum()) for p in per_lens])
    keys = np.empty((int(starts[-1]), 1 + n_l), np.uint32)
    cnts = np.empty((int(starts[-1]),), np.uint32)
    tasks = []
    for start, end, per_len, chunks in zip(starts, starts[1:], per_lens,
                                           tables):
        bounds = (start + np.cumsum([0] + per_len.tolist())).tolist()
        off = start
        for c in chunks:
            cols = c.reshape(n_l + 1, -1)
            tasks.append((cols, off, min(off + cols.shape[1], end), bounds))
            off += cols.shape[1]

    def lay_out(task):
        cols, lo, hi, bounds = task
        keys[lo:hi, 1:] = cols[:n_l, :hi - lo].T
        cnts[lo:hi] = cols[n_l, :hi - lo]
        for length, (a, b) in enumerate(zip(bounds, bounds[1:]), 1):
            if max(a, lo) < min(b, hi):
                keys[max(a, lo):min(b, hi), 0] = length

    for _ in _ROW_LAYOUT.map(lay_out, tasks):
        pass
    return keys, cnts, starts[:-1]


def _segment_order(keys: np.ndarray, cnts: np.ndarray, starts):
    """Rows made of runs that are each in segment order, in segment order:
    unchanged where each run's last key is below the next run's first, else
    stable-sorted on the byte view.  Returns (keys, counts, sorted?)."""
    from repro.index._layout import row_bytes_view
    bounds = [int(b) for b in starts[1:] if 0 < b < len(keys)]
    if all(tuple(keys[b - 1].tolist()) < tuple(keys[b].tolist())
           for b in bounds):
        return keys, cnts, False
    order = np.argsort(row_bytes_view(keys), kind="stable")
    return keys[order], cnts[order], True


def _run_rounds(tok_ext, aux_ext, n_live: int, cfg, plan: JobPlan,
                tau_eff: int, counters: dict):
    """All of a plan's rounds over one token window -> merged ``NGramStats``.

    The *synchronous* interpreter ``run_plan`` uses: per-round host
    materialization (tau-filtered carries, ``stop_on_empty``), legacy
    monolithic counter semantics.  The wave hot path uses the async
    ``WaveExecutor._submit_wave`` / ``_collect_wave`` pair instead.
    """
    from repro.core.stats import NGramStats, add_counters

    lane_vocab = plan.effective_lane_vocab(cfg)
    n_l = packing.n_lanes(cfg.sigma, lane_vocab)
    has_bucket = aux_ext is not None
    n_meta = plan.map.n_meta + (1 if has_bucket else 0)
    rec_bytes = packing.record_bytes(cfg.sigma, lane_vocab, n_meta=n_meta)
    combine_route = plan.combine.route if plan.combine is not None else None

    out = None
    carry = None
    for k in range(1, plan.rounds + 1):
        with obs_trace.span("round.emit") as sp:
            if sp:
                sp.set(round=k)
            records, valid, emit_extras = plan.map.emit(
                tok_ext, aux_ext, n_live, cfg, carry, k)
        # combine -> shuffle-key -> sort -> reduce fuse into one jitted
        # program, so the stage granularity under this span is the dispatch;
        # the device time lands in the materialize span's sync below.  The
        # map-record counter rides the program as a device scalar (read at
        # the materialize sync below) -- summing ``valid`` here would force
        # a host round trip *before* the stage dispatch.
        with obs_trace.span("round.stages") as sp:
            if sp:
                sp.set(round=k)
            dense, map_rec, shuffled, hist, _lanes = _stage_core(
                records, valid, n_lanes=n_l, has_bucket=has_bucket,
                combine_route=combine_route, use_kernels=cfg.use_kernels,
                sigma=cfg.sigma, lane_vocab=lane_vocab,
                shuffle_key=plan.shuffle.key, reduce_kind=plan.reduce.kind,
                with_positions=plan.reduce.with_positions,
                n_buckets=cfg.n_buckets)
        with obs_trace.span("round.materialize") as sp:
            if sp:
                sp.set(round=k)
            terms, flags, counts = (np.asarray(x) for x in dense[:3])
            stats_k = NGramStats.from_dense(terms, flags, counts, tau_eff)
        reduce_extras = ({"totals_pos": dense[3]}
                         if plan.reduce.with_positions else {})
        map_rec = int(map_rec)
        shuffled = int(shuffled)
        hist = np.asarray(hist)
        add_counters(counters, jobs=1, map_records=map_rec,
                     shuffle_records=shuffled,
                     shuffle_bytes=shuffled * rec_bytes)
        if shuffled:
            skew = float(hist.max() * _SKEW_BUCKETS / max(hist.sum(), 1))
            counters["shuffle_skew"] = max(counters.get("shuffle_skew", 0.0),
                                           skew)
        out = stats_k if out is None else out.merged_with(stats_k)
        if plan.stop_on_empty and len(stats_k) == 0:
            break
        if k < plan.rounds and plan.update_carry is not None:
            carry = plan.update_carry(cfg, tau_eff, k, tok_ext, stats_k,
                                      reduce_extras, emit_extras, carry)
    out.counters = counters
    return out


def run_plan(tokens, cfg, bucket_ids=None, plan: JobPlan | None = None):
    """One-wave (whole-corpus) plan execution -- the single-device job.

    Semantics and counters match the old per-method monolithic code (tau and
    APRIORI pruning apply per round); output rows are in canonical segment
    order (``stages.canonical_stats``), which is what the wave executor is
    bit-compared against.
    """
    plan = plan or plan_for(cfg)
    with obs_trace.span("plan.run") as sp:
        if sp:
            sp.set(method=cfg.method, rounds=plan.rounds)
        tokens = jnp.asarray(tokens, jnp.int32)
        aux = None if bucket_ids is None else jnp.asarray(bucket_ids,
                                                          jnp.uint32)
        # the full canonical counter set (obs.metrics.COUNTER_DOC), so the
        # monolithic and wave paths expose identical keys with stable types
        counters = dict.fromkeys(
            ("jobs", "map_records", "shuffle_records", "shuffle_bytes",
             "retries", "overflow"), 0)
        counters["shuffle_skew"] = 0.0
        out = _run_rounds(tokens, aux, int(tokens.shape[0]), cfg, plan,
                          cfg.tau, counters)
        out.counters = obs_metrics.normalize_counters(out.counters)
        return stages.canonical_stats(out)


class DoubleBufferedDriver:
    """Overlap host-side work with device execution.

    ``submit`` dispatches batch i+1 (``answer`` must return its result
    *unmaterialized* -- device arrays or a record holding them) and only then
    materializes batch i's via ``collect`` -- jax's async dispatch runs the new
    batch while the host reads the old one, with no ``jax.block_until_ready``
    anywhere on the hot path.  ``submit`` returns (previous batch's collected
    result, its submit-time payload); ``drain`` flushes the last in-flight
    batch.

    Shared by the serving loop (``launch/serve_ngrams.py``, where it overlaps
    query batching with device lookups) and the wave engine's ingest loop
    (where it overlaps wave i+1's h2d/compute with wave i's host-side fold).
    """

    def __init__(self, answer, collect=None):
        self._answer = answer
        self._collect = collect
        self._pending = None

    def _materialize(self, out):
        if self._collect is not None:
            return self._collect(out)
        return np.asarray(out)

    def submit(self, *args, tag=None):
        out = self._answer(*args)
        prev, self._pending = self._pending, (out, tag)
        if prev is None:
            return None, None
        return self._materialize(prev[0]), prev[1]

    def drain(self):
        if self._pending is None:
            return None, None
        (out, tag), self._pending = self._pending, None
        return self._materialize(out), tag


def _merge_wave_counters(dst: dict, src: dict) -> None:
    """Fold one wave's counters into the run totals.

    Delegates to the one shared policy (``repro.obs.metrics``): sums, except
    the documented max-merged ratio keys (``shuffle_skew``).  The canonical
    counter set and its semantics live in ``obs.metrics.COUNTER_DOC``.
    """
    obs_metrics.merge_counter_dicts(dst, src)


def _to_host(*xs):
    """``np.asarray`` of each device value, and the bytes they brought over."""
    host = tuple(np.asarray(x) for x in xs)
    return host, sum(h.nbytes for h in host)


def _add_round_counters(counters: dict, map_rec, shuffled, hist,
                        rec_bytes: int, d2h_bytes: int) -> None:
    """Fold one collected round's host scalars and skew histogram into a
    wave's counters."""
    from repro.core.stats import add_counters
    shuffled = int(shuffled)
    add_counters(counters, jobs=1, map_records=int(map_rec),
                 shuffle_records=shuffled, shuffle_bytes=shuffled * rec_bytes,
                 d2h_bytes=d2h_bytes)
    if shuffled:
        skew = float(hist.max() * _SKEW_BUCKETS / max(hist.sum(), 1))
        counters["shuffle_skew"] = max(counters.get("shuffle_skew", 0.0),
                                       skew)


def _wait_for_device(outs, wave: int) -> None:
    """Traced runs only: block on a wave's outputs inside a span of their
    own, so a collect's wait for the device is apart from its host work (the
    untraced collect blocks in its first ``np.asarray`` instead)."""
    with obs_trace.span("wave.collect.wait") as sp:
        sp.set(wave=wave)
        jax.block_until_ready(outs)


class WavePartial:
    """One collected wave: its host-frozen sorted segment + job counters.

    The unit the fold consumes (segment push in :meth:`WaveExecutor.run`,
    generational ingest in :meth:`WaveExecutor.run_streaming`): ``segment``
    is an unpadded host-resident :class:`~repro.index.build.IndexSegment`
    holding the wave's exact tau=1 rows in (length | packed lanes) order,
    ``n_rows`` its real row count, ``counters`` the wave's MapReduce-style
    counter dict.
    """

    __slots__ = ("segment", "n_rows", "counters")

    def __init__(self, segment, n_rows: int, counters: dict):
        self.segment = segment
        self.n_rows = n_rows
        self.counters = counters


class WaveExecutor:
    """Run a :class:`JobPlan` over fixed-size token waves (out-of-core).

    ``wave_tokens`` bounds the device-resident working set; ``None`` (or a
    wave at least the corpus size) degenerates to one wave.  Waves execute at
    ``tau = 1``; :meth:`run` stacks their segments and folds them once,
    k-way, at finalize (``index.merge.DeferredSegmentAccumulator``, O(total)
    merge rows), with the global tau pushed into that fold as its
    ``min_count``, so for any wave size and either route the output is
    bit-identical to the monolithic job.  ``merge_route``: ``"device"`` =
    the blocked fold on the chip: the sorted wave segments are cut into
    fixed-shape key-range blocks that one jitted program merges,
    dedup-folds and tau-filters, so only rows with cf >= tau come back to
    the host; ``"kway"`` = galloping host merge of the presorted segments.
    ``None`` (the default) picks ``"device"`` where the default backend is
    an accelerator and ``"kway"`` on the CPU backend.  On the ``"device"``
    route the first run readies the one block program
    (``index.merge.load_block_programs``) beside its waves and returns only
    when it is ready, so no later run compiles it, even if this one folded
    nothing.

    With a ``mesh`` (size > 1), each wave runs as ONE fused ``shard_map``
    dispatch over ``axis_name``: contiguous token slices per shard, the
    distributed jobs' own ppermute sigma-1 halo between neighbors (pulled
    once per wave), every round's hash-partitioned ``all_to_all`` shuffle
    with a single collect-time counted-overflow capacity retry, and the
    device-side segment-candidate collect.  Mesh waves ride the same
    double-buffered dispatch + overlapped fold thread as single-device
    waves and fold through the same segment path, so the distributed run
    stays bit-identical to the single-device one.

    A single-device job whose records are wider than ``SPLIT_HEAD_LANES``
    lanes runs as the head/tail split of :meth:`_run_split`, with the same
    output; with a mesh, every sigma keeps one full-width pass.

    Memory model: device footprint is O(wave * sigma) records per stage (per
    shard when distributed); the running segments live wherever
    ``index/merge.py`` keeps them and together hold the *exact* (tau=1) gram
    set seen so far -- the unavoidable state of any exact out-of-core
    counter.  Restrictions: bucketed time series (``n_buckets``) need
    cross-wave bucket columns the segment fold does not carry, so waves
    require ``n_buckets == 0``.
    """

    def __init__(self, cfg, *, wave_tokens: int | None = None,
                 plan: JobPlan | None = None, merge_route: str | None = None,
                 mesh=None, axis_name: str = "data", overlap: bool = True):
        if wave_tokens is not None and wave_tokens < 1:
            raise ValueError("wave_tokens must be >= 1")
        if cfg.n_buckets:
            raise ValueError("wave execution does not support n_buckets "
                             "(bucketed series need the bucket-carrying "
                             "single job -- run_job / run_plan)")
        if merge_route not in (None, "kway", "device"):
            raise ValueError(f"unknown merge route {merge_route!r} "
                             "(options: 'kway', 'device')")
        self.cfg = cfg
        self.wave_tokens = wave_tokens
        self.plan = plan or plan_for(cfg)
        # the chip folds the deferred merge in blocks on the device, which is
        # idle by then; the CPU backend's "device" would only be slower host
        # code
        self.merge_route = merge_route or (
            "device" if jax.default_backend() != "cpu" else "kway")
        self.mesh = mesh
        self.axis_name = axis_name
        # overlap: run the per-wave fold (collect + segment stack /
        # generational ingest) on a background thread so it overlaps the next
        # wave's device work; False serializes fold and dispatch on the main
        # thread (debugging / environments where threads are unwelcome)
        self.overlap = overlap
        self._mesh_programs: dict = {}   # (n_local, capacity scale, skew?)
        # overflow-retry capacity scale: doubles on the rare overflowed wave
        # and sticks, so later waves dispatch at the proven capacity
        self._mesh_scale = 1
        # XLA's host-device collective rendezvous is not ordered across
        # concurrently launched executions: two in-flight mesh-wave programs
        # can interleave their ppermute/all_to_all participants across device
        # threads and stall (observed as multi-second rendezvous hangs).
        # Every mesh program launch therefore waits for the previous launch
        # to finish executing, under this lock (the fold thread's retry
        # launches race the feeder's next-wave dispatch without it).  Host
        # fold work still overlaps the next wave's device execution.
        self._mesh_launch_lock = threading.Lock()
        self._mesh_last_launch = None
        self._emit_rows_cache: dict = {}
        # direct-segment collect is valid iff the record lanes' packed layout
        # is the segment layout -- i.e. the plan packs with cfg.vocab_size
        # (pack ablations / pack_vocab overrides take the stats route)
        self._direct = (self.plan.effective_lane_vocab(cfg) == cfg.vocab_size)
        self._masks = None               # prefix_lane_masks, built lazily
        # the head/tail split (:meth:`_run_split`), decided by the record
        # width that sigma and the vocabulary give; mesh waves keep one
        # full-width pass
        self._head_ex = None
        self._tail_scale = 1             # the tail buffer's sticky doubling
        if (self._direct and not self._use_mesh and packing.n_lanes(
                cfg.sigma, cfg.vocab_size) > SPLIT_HEAD_LANES):
            head = SPLIT_HEAD_LANES * packing.terms_per_lane(cfg.vocab_size)
            self._head_ex = WaveExecutor(
                dataclasses.replace(cfg, sigma=head), wave_tokens=wave_tokens,
                merge_route=self.merge_route, overlap=overlap)

    # --- wave iteration ------------------------------------------------------ #

    def _windows(self, tokens: np.ndarray, *, to_device: bool = True):
        """Yield (tok_ext [wave + sigma - 1], n_live) fixed-shape windows.

        ``n_live`` is the *true* number of corpus tokens in the wave -- the
        final wave of a corpus that is not a multiple of ``wave_tokens`` gets
        a partial count, so the emit's live mask (positions ``< n_live``)
        excludes the zero-padded tail outright instead of leaning on the
        reserved-PAD convention (``NGramConfig.validate_tokens``) to mask
        phantom tail grams.  ``to_device=False`` yields host slices (the
        mesh path re-pads to the shard layout before its own h2d).
        """
        n = int(tokens.shape[0])
        wave = self._wave_len(n)
        n_waves = max(1, -(-n // wave))
        halo = self.cfg.sigma - 1
        with obs_trace.span("wave.window.pad") as sp:
            if sp:
                sp.set(n_waves=n_waves, wave_tokens=wave)
            padded = np.zeros((n_waves * wave + halo,), np.int32)
            padded[:n] = np.asarray(tokens, np.int32)
        for w in range(n_waves):
            n_live = max(0, min(wave, n - w * wave))
            tok_ext = padded[w * wave: (w + 1) * wave + halo]
            if to_device:
                with obs_trace.span("wave.window.h2d") as sp:
                    if sp:
                        sp.set(wave=w)
                    tok_ext = jnp.asarray(tok_ext)
            yield tok_ext, n_live

    def _wave_len(self, n: int) -> int:
        """Tokens a wave of an ``n``-token job holds."""
        wave = self.wave_tokens if self.wave_tokens is not None else n
        return max(1, min(wave, n) if n else 1)

    @property
    def _use_mesh(self) -> bool:
        return self.mesh is not None and self.mesh.size > 1

    # --- single-device async wave dispatch ----------------------------------- #

    def _submit_wave(self, tok_ext, n_live: int, wave: int = 0, *,
                     segment: bool = False) -> dict:
        """Dispatch one wave as ONE fused program; nothing materializes here.

        The wave regime always runs at ``tau_eff = 1``, where carries are a
        pure traceable function of the emit-side evidence (the contract
        ``plan.py`` documents), so the *entire* round chain -- emits, stage
        pipelines, carry updates, counters -- traces into a single jitted
        donated program (``_wave_core``) and stays in flight until
        :meth:`_collect_wave`.  ``stop_on_empty`` is skipped: an exhausted
        round chain emits empty partials that fold to nothing.  With a mesh,
        the wave dispatches through the fused sharded program instead
        (:meth:`_submit_wave_mesh`) -- same async contract.  ``wave`` is the
        wave's index, carried to the collect's spans.  ``segment`` runs the
        program's segment form, for :meth:`_collect_wave_segment`.
        """
        if self._use_mesh:
            return self._submit_wave_mesh(tok_ext, n_live, wave)
        cfg, plan = self.cfg, self.plan
        with obs_trace.span("wave.submit") as sp:
            if sp:
                sp.set(wave=wave, n_live=n_live, rounds=plan.rounds)
            # one span == one dispatch: the fused-wave regression tests count
            # exactly one round.stages span per wave, any number of rounds
            with obs_trace.span("round.stages") as sp_s:
                if sp_s:
                    sp_s.set(fused_rounds=plan.rounds)
                rounds = _wave_core(cfg, plan, tok_ext, n_live, segment)
            rec_bytes = packing.record_bytes(
                cfg.sigma, plan.effective_lane_vocab(cfg),
                n_meta=plan.map.n_meta)
            return {"rounds": list(rounds), "rec_bytes": rec_bytes,
                    "wave": wave, "segment": segment}

    def _submit_segment_wave(self, tok_ext, n_live: int,
                             wave: int = 0) -> dict:
        """Dispatch one wave for :meth:`_collect_wave_segment`: the wave
        program's segment form where the record lanes are the segment
        layout (``self._direct``), else as :meth:`_submit_wave`."""
        return self._submit_wave(tok_ext, n_live, wave,
                                 segment=self._direct)

    def _collect_wave(self, pend: dict):
        """Materialize a submitted wave -> exact ``NGramStats`` partial.

        The ``np.asarray`` materializations here are the wave's one device
        sync (``wave.collect.d2h``); traced, the wait for the device comes
        first, in ``wave.collect.wait``, and the host work on the rows in
        ``wave.collect.rows``.
        """
        if pend.get("mesh"):
            return self._collect_wave_mesh(pend)
        from repro.core.stats import NGramStats

        w = pend["wave"]
        with obs_trace.span("wave.collect") as sp:
            if sp:
                sp.set(wave=w)
                _wait_for_device(pend["rounds"], w)
            counters: dict = {}
            out = None
            for (dense, _lanes), map_rec, shuffled, hist in pend["rounds"]:
                with obs_trace.span("wave.collect.d2h") as sp_d:
                    (terms, flags, counts, hist, map_rec, shuffled), nbytes = \
                        _to_host(*dense, hist, map_rec, shuffled)
                    if sp_d:
                        sp_d.set(wave=w, bytes=nbytes)
                with obs_trace.span("wave.collect.rows") as sp_r:
                    if sp_r:
                        sp_r.set(wave=w)
                    stats_k = NGramStats.from_dense(terms, flags, counts, 1)
                    _add_round_counters(counters, map_rec, shuffled, hist,
                                        pend["rec_bytes"], nbytes)
                    out = stats_k if out is None else out.merged_with(stats_k)
            out.counters = counters
            if sp:
                sp.set(rows=len(out), shuffle_records=counters.get(
                    "shuffle_records", 0))
            return out

    def _prefix_masks(self) -> np.ndarray:
        masks = self._masks
        if masks is None:
            masks = self._masks = packing.prefix_lane_masks(
                self.cfg.sigma, self.cfg.vocab_size)
        return masks

    def _partial_from_stats(self, wave_stats) -> WavePartial:
        """Freeze an ``NGramStats`` wave partial (mesh / stats-route waves)."""
        from repro.index.build import segment_from_wave_stats
        seg = segment_from_wave_stats(wave_stats,
                                      vocab_size=self.cfg.vocab_size)
        return WavePartial(seg, len(wave_stats), wave_stats.counters)

    def _collect_wave_segment(self, pend: dict) -> WavePartial:
        """Materialize a submitted wave straight into a sorted host segment.

        The wave program's segment form (:meth:`_submit_segment_wave`)
        already built each round's rows on the device, compacted in segment
        order (``stages.segment_table``).  The first sync reads each round's
        per-length counts; then only the table chunks that hold kept rows
        come over (``wave.collect.d2h``), and the host lays them out as
        (length | key lanes) rows, the length column rebuilt from the
        counts (``wave.collect.rows``).  Each round's rows are in segment
        order by construction, so ``wave.collect.sort`` only checks that
        each round's last key is below the next round's first, and sorts
        (stable, on the byte view) only where that fails: counter
        ``collect_host_sorts``.  Bit-identical to
        ``segment_from_wave_stats(_collect_wave(pend))``: both are the same
        (key, count) row set in the same canonical order.  A wave
        dispatched in the dense form (lanes not in the segment layout)
        takes exactly that stats route; mesh waves their sharded twin.
        """
        if pend.get("mesh"):
            return self._collect_wave_segment_mesh(pend)
        if not pend["segment"]:
            return self._partial_from_stats(self._collect_wave(pend))
        from repro.index.build import IndexSegment

        cfg = self.cfg
        w = pend["wave"]
        rounds = pend["rounds"]
        n_l = packing.n_lanes(cfg.sigma, cfg.vocab_size)
        with obs_trace.span("wave.collect") as sp:
            if sp:
                sp.set(wave=w)
                _wait_for_device(rounds, w)
            with obs_trace.span("wave.collect.d2h") as sp_d:
                small, nbytes = _to_host(*(
                    x for (per_len, _), map_rec, shuffled, hist in rounds
                    for x in (per_len, map_rec, shuffled, hist)))
                per_lens = small[0::4]
                need = [_table_chunks(chunks, int(p.sum()), n_l + 1)
                        for p, ((_, chunks), *_) in zip(per_lens, rounds)]
                for c in (c for cs in need for c in cs):
                    c.copy_to_host_async()
                tables = []
                for cs in need:
                    host, b = _to_host(*cs)
                    tables.append(host)
                    nbytes += b
                if sp_d:
                    sp_d.set(wave=w, bytes=nbytes)
            with obs_trace.span("wave.collect.rows") as sp_r:
                if sp_r:
                    sp_r.set(wave=w)
                counters: dict = {}
                for k in range(len(rounds)):
                    _add_round_counters(counters, *small[4 * k + 1:4 * k + 4],
                                        pend["rec_bytes"],
                                        nbytes if k == 0 else 0)
                keys, cnts, starts = _table_rows(per_lens, tables, n_l)
            with obs_trace.span("wave.collect.sort") as sp_s:
                if sp_s:
                    sp_s.set(wave=w, rows=int(keys.shape[0]))
                keys, cnts, sorted_ = _segment_order(keys, cnts, starts)
                counters["collect_host_sorts"] = int(sorted_)
                seg = IndexSegment(keys=keys, counts=cnts, sigma=cfg.sigma,
                                   vocab_size=cfg.vocab_size)
            if sp:
                sp.set(rows=int(keys.shape[0]), shuffle_records=counters.get(
                    "shuffle_records", 0))
            return WavePartial(seg, int(keys.shape[0]), counters)

    # --- distributed (mesh) wave dispatch ------------------------------------ #

    def _emit_rows(self, win_len: int, k: int) -> int:
        """Map-emit record rows for a ``win_len``-token window (shape probe)."""
        key = (win_len, k)
        rows = self._emit_rows_cache.get(key)
        if rows is None:
            shape = jax.eval_shape(
                lambda t: self.plan.map.emit(t, None, 0, self.cfg, None, k)[0],
                jax.ShapeDtypeStruct((win_len,), jnp.int32))
            rows = self._emit_rows_cache[key] = int(shape.shape[0])
        return rows

    def _mesh_wave_program(self, n_local: int, scale: int, with_skew: bool):
        key = (n_local, scale, with_skew)
        fn = self._mesh_programs.get(key)
        if fn is None:
            fn = self._mesh_programs[key] = self._build_mesh_wave_program(
                n_local, scale, with_skew)
        return fn

    def _build_mesh_wave_program(self, n_local: int, scale: int,
                                 with_skew: bool):
        """Trace one mesh wave's FULL round chain into ONE shard_map program.

        The distributed twin of ``_build_wave_program``: each shard owns a
        contiguous ``n_local``-token slice of the wave's extended window,
        pulls its sigma-1 halo from the right neighbor via ppermute ONCE per
        wave (the last shard's halo is zeros -- the window already ends in
        the wave-level halo, and nothing live reads past it), then every
        round's emit -> combine -> hash-partitioned ``all_to_all`` shuffle ->
        sort -> reduce -> segment-candidate collect, plus the tau=1 carry
        updates feeding the next round, trace into a single jitted
        ``shard_map`` dispatch.  Carries never cross the program boundary:
        at ``tau_eff = 1`` a carry is a pure function of the shard's own
        extended window (see ``plan.py``), so they stay shard-local,
        device-resident, and reset per wave.

        Per-round shuffle capacities are static (the emit-shape probe times
        ``capacity_factor``), multiplied by the wave-level ``scale`` the
        overflow retry doubles.  Overflow is NOT host-synced per round: each
        round's local overflow count accumulates and rides the one psum'd
        counter block ``cnt [rounds, 3] = (map_records, shuffle_records,
        overflow)``, checked once per wave at collect time.  The skew
        histogram (a second psum) is only traced when ``with_skew`` -- the
        fused program skips that collective + device work entirely when
        observability is off.

        Outputs stay sharded (leading mesh axis): per round either the flat
        packed ``(keys [P*C, 1+n_l], counts [P*C])`` candidate table
        (``self._direct`` -- the host's whole fold is concat + one stable
        byte-view sort) or the dense ``(terms, flags, counts)`` triple
        ``[P, ...]`` for the stats fallback route.
        """
        from jax.sharding import PartitionSpec as P

        cfg, plan = self.cfg, self.plan
        mesh, axis_name = self.mesh, self.axis_name
        n_parts = mesh.shape[axis_name]
        lane_vocab = plan.effective_lane_vocab(cfg)
        n_l = packing.n_lanes(cfg.sigma, lane_vocab)
        halo = cfg.sigma - 1
        direct = self._direct
        combine_route = plan.combine.route if plan.combine is not None else None
        caps = {k: scale * max(8, int(cfg.capacity_factor
                                      * self._emit_rows(n_local + halo, k)
                                      / n_parts) + 1)
                for k in range(1, plan.rounds + 1)}
        masks = jnp.asarray(self._prefix_masks()) if direct else None

        def job(tok, n_live):
            tok = tok[0]                                     # [n_local]
            if halo:
                perm = [(i, (i - 1) % n_parts) for i in range(n_parts)]
                h = jax.lax.ppermute(tok[:halo], axis_name, perm)
                is_last = jax.lax.axis_index(axis_name) == n_parts - 1
                h = jnp.where(is_last, jnp.zeros_like(h), h)
                tok_ext = jnp.concatenate([tok, h])
            else:
                tok_ext = tok
            shard = jax.lax.axis_index(axis_name)
            n_live_local = jnp.clip(n_live - shard * n_local, 0, n_local)
            carry = None
            rounds_out = []
            cnt_rows = []
            hists = []
            for k in range(1, plan.rounds + 1):
                with jax.named_scope("emit"):
                    records, valid, emit_extras = plan.map.emit(
                        tok_ext, None, n_live_local, cfg, carry, k)
                map_rec = jnp.sum(valid.astype(jnp.int32))
                if combine_route is not None:
                    with jax.named_scope("combine"):
                        records = stages.combine(records, n_l, False,
                                                 route=combine_route,
                                                 use_kernels=cfg.use_kernels)
                live = records[:, n_l] > 0
                with jax.named_scope("partition"):
                    key = stages.partition_keys(records, n_l,
                                                kind=plan.shuffle.key,
                                                vocab_size=lane_vocab)
                    if with_skew:
                        skew = mr_shuffle.partition_ids(key, live,
                                                        _SKEW_BUCKETS)
                        hists.append(jnp.bincount(
                            skew, length=_SKEW_BUCKETS + 1)[:_SKEW_BUCKETS])
                with jax.named_scope("shuffle"):
                    local, overflow = mr_shuffle.shuffle(
                        records, key, live, axis_name=axis_name,
                        n_parts=n_parts, capacity=caps[k],
                        reduce_overflow=False)
                shuf = jnp.sum(local[:, n_l] > 0)
                cnt_rows.append(jnp.stack([map_rec, shuf,
                                           overflow.astype(jnp.int32)]))
                with jax.named_scope("sort"):
                    rec = stages.sort_stage(local, n_keys=n_l)
                with jax.named_scope("reduce"):
                    if plan.reduce.kind == "suffix":
                        terms, flags, counts = stages.reduce_suffix(
                            rec, sigma=cfg.sigma, vocab_size=lane_vocab,
                            n_buckets=0, use_kernels=cfg.use_kernels)
                    else:
                        # position payloads are only consumed by tau>1
                        # carries, which the wave regime never takes --
                        # skip the scatter
                        terms, flags, counts = stages.reduce_exact(
                            rec, sigma=cfg.sigma, vocab_size=lane_vocab,
                            with_positions=False)
                if direct:
                    with jax.named_scope("segment_candidates"):
                        rounds_out.append(stages.segment_candidates(
                            flags, counts, rec[:, :n_l], masks,
                            sigma=cfg.sigma, reduce_kind=plan.reduce.kind))
                else:
                    rounds_out.append((terms[None], flags[None],
                                       counts[None]))
                if k < plan.rounds and plan.update_carry is not None:
                    carry = plan.update_carry(cfg, 1, k, tok_ext, None, {},
                                              emit_extras, carry)
            # ONE collective for every per-round counter (plus one for the
            # skew histogram when observability asks for it)
            cnt = jax.lax.psum(jnp.stack(cnt_rows), axis_name)  # [rounds, 3]
            outs = [tuple(rounds_out), cnt[None]]
            if with_skew:
                outs.append(jax.lax.psum(jnp.stack(hists), axis_name)[None])
            return tuple(outs)

        per_round = (P(axis_name), P(axis_name)) if direct \
            else (P(axis_name),) * 3
        out_specs = [tuple(per_round for _ in range(plan.rounds)),
                     P(axis_name)]
        if with_skew:
            out_specs.append(P(axis_name))
        return jax.jit(jax.shard_map(
            job, mesh=mesh, in_specs=(P(axis_name, None), P()),
            out_specs=tuple(out_specs), check_vma=False))

    def _submit_wave_mesh(self, tok_host: np.ndarray, n_live: int,
                          wave: int = 0) -> dict:
        """Dispatch one mesh wave as ONE sharded program; nothing syncs here.

        ``tok_host`` stays a host array until the padded [n_parts, n_local]
        shard layout is built (no d2h round trip through a device window).
        The retry state the collect side needs -- the padded tokens, the
        dispatch-time capacity scale, the skew flag -- rides the pend dict.
        """
        cfg, plan = self.cfg, self.plan
        n_parts = self.mesh.shape[self.axis_name]
        win_len = int(tok_host.shape[0])
        # the one-hop ppermute halo pulls sigma-1 tokens from the right
        # neighbor, so a shard's slice must be at least that long -- tiny
        # waves leave trailing shards all-pad (no live positions)
        n_local = max(-(-win_len // n_parts), cfg.sigma - 1, 1)
        tok_p = np.zeros((n_parts * n_local,), np.int32)
        tok_p[:win_len] = tok_host
        tok_p = tok_p.reshape(n_parts, n_local)
        with_skew = bool(obs_metrics.get_registry())
        scale = self._mesh_scale
        with obs_trace.span("wave.mesh.dispatch") as sp:
            if sp:
                sp.set(wave=wave, n_live=n_live, rounds=plan.rounds,
                       n_local=n_local, scale=scale)
            outs = self._launch_mesh_wave(n_local, scale, with_skew, tok_p,
                                          n_live)
        rec_bytes = packing.record_bytes(
            cfg.sigma, plan.effective_lane_vocab(cfg), n_meta=plan.map.n_meta)
        return {"mesh": True, "outs": outs, "tok_p": tok_p, "n_live": n_live,
                "n_local": n_local, "scale": scale, "with_skew": with_skew,
                "rec_bytes": rec_bytes, "wave": wave}

    def _launch_mesh_wave(self, n_local: int, scale: int, with_skew: bool,
                          tok_p: np.ndarray, n_live: int):
        """Launch one fused mesh-wave program, serialized against the last.

        Collective programs launched while another is still executing can
        interleave their rendezvous participants across device threads on the
        host backend and stall for seconds (two in-flight waves = two run
        ids racing the same ppermute).  Launches therefore wait for the
        previous program to finish first; the lock covers the feeder thread
        vs the fold thread's overflow-retry launches.  Only device *launch*
        is serialized -- the host-side fold still overlaps the next wave's
        execution, which is where the 1-core overlap win actually is.
        """
        with self._mesh_launch_lock:
            if self._mesh_last_launch is not None:
                jax.block_until_ready(self._mesh_last_launch)
            fn = self._mesh_wave_program(n_local, scale, with_skew)
            outs = fn(mr_shuffle.shard_rows(tok_p, self.mesh, self.axis_name),
                      jnp.int32(n_live))
            self._mesh_last_launch = outs[1]
            return outs

    def _collect_wave_mesh_outs(self, pend: dict):
        """The wave's ONE host sync: read counters, retry on overflow.

        Materializing the psum'd ``cnt [rounds, 3]`` block is the only
        per-wave device round trip.  If any round overflowed its shuffle
        capacity, the WHOLE wave reruns at doubled capacity scale -- correct
        because carries are internal to the program (a rerun re-derives them
        from the same tokens) and cheap because overflow is rare and sticky:
        the doubled scale persists in ``self._mesh_scale``, so subsequent
        waves dispatch at the proven capacity and never trip again.  An
        overflowed attempt's counters never land (a rerun re-emits the same
        records; folding both would double-count) -- only the successful
        attempt's ``cnt``/hist do, while reruns stay visible via ``retries``.
        """
        outs = pend["outs"]
        retries = 0
        while True:
            with obs_trace.span("wave.collect.d2h") as sp:
                cnt = np.asarray(outs[1])[0]                 # [rounds, 3]
                if sp:
                    sp.set(wave=pend["wave"], bytes=cnt.nbytes)
            if int(cnt[:, 2].sum()) == 0:
                return outs, cnt, retries
            if retries >= 5:
                raise RuntimeError(
                    "wave shuffle overflow persisted at capacity scale "
                    f"{pend['scale']}")
            retries += 1
            pend["scale"] *= 2
            self._mesh_scale = max(self._mesh_scale, pend["scale"])
            with obs_trace.span("wave.mesh.retry") as sp:
                if sp:
                    sp.set(retry=retries, scale=pend["scale"])
                outs = self._launch_mesh_wave(pend["n_local"], pend["scale"],
                                              pend["with_skew"],
                                              pend["tok_p"], pend["n_live"])

    def _mesh_counters(self, cnt: np.ndarray, hist, pend: dict,
                       retries: int) -> dict:
        """Wave counters from the successful attempt's psum'd ``cnt`` block
        and, when metrics are on, its skew histogram ``hist`` (host)."""
        from repro.core.stats import add_counters

        counters: dict = {}
        if retries:   # capacity-doubling reruns, visible like the jobs'
            add_counters(counters, retries=retries)
        for k in range(cnt.shape[0]):
            shuf = int(cnt[k, 1])
            add_counters(counters, jobs=1, map_records=int(cnt[k, 0]),
                         shuffle_records=shuf,
                         shuffle_bytes=shuf * pend["rec_bytes"])
            if hist is not None and shuf:
                skew = float(hist[k].max() * _SKEW_BUCKETS
                             / max(hist[k].sum(), 1))
                counters["shuffle_skew"] = max(
                    counters.get("shuffle_skew", 0.0), skew)
        return counters

    def _mesh_wave_stats(self, rounds_out, counters: dict):
        """Stats-route fallback fold (``pack_vocab`` overrides): from_dense
        per shard per round over the host ``rounds_out``, merged on host --
        only configs whose lane layout is not the segment layout pay this."""
        from repro.core.stats import NGramStats

        out = None
        for terms, flags, counts in rounds_out:
            for p in range(terms.shape[0]):
                part = NGramStats.from_dense(terms[p], flags[p], counts[p], 1)
                out = part if out is None else out.merged_with(part)
        out.counters = counters
        return out

    def _collect_wave_segment_mesh(self, pend: dict) -> WavePartial:
        """Materialize a mesh wave straight into a sorted host segment.

        The sharded twin of :meth:`_collect_wave_segment`: the fused program
        already collected packed segment-candidate rows on device
        (``stages.segment_candidates``), so the host fold is concat over
        (shard, round) tables + drop dead rows + ONE stable byte-view sort.
        Within a wave every kept gram key is unique across shards (the
        shuffle routes all evidence of a gram to one reducer shard) and
        across rounds (rounds emit disjoint lengths), so the sorted row set
        -- and with it the bit-identity contract -- is independent of
        shard/round concat order.
        """
        from repro.index._layout import row_bytes_view
        from repro.index.build import IndexSegment

        from repro.core.stats import add_counters

        w = pend["wave"]
        with obs_trace.span("wave.mesh.collect") as sp:
            if sp:
                sp.set(wave=w)
                _wait_for_device(pend["outs"], w)
            outs, cnt, retries = self._collect_wave_mesh_outs(pend)
            with obs_trace.span("wave.collect.d2h") as sp_d:
                hist = np.asarray(outs[2])[0] if pend["with_skew"] else None
                rounds, nbytes = zip(*(_to_host(*r) for r in outs[0]))
                nbytes = cnt.nbytes + sum(nbytes) + (
                    hist.nbytes if hist is not None else 0)
                if sp_d:
                    sp_d.set(wave=w, bytes=nbytes)
            counters = self._mesh_counters(cnt, hist, pend, retries)
            add_counters(counters, d2h_bytes=nbytes)
            if not self._direct:
                with obs_trace.span("wave.collect.rows") as sp_r:
                    if sp_r:
                        sp_r.set(wave=w)
                    stats = self._mesh_wave_stats(rounds, counters)
                return self._partial_from_stats(stats)
            with obs_trace.span("wave.collect.rows") as sp_r:
                if sp_r:
                    sp_r.set(wave=w)
                keys = np.concatenate([k for k, _ in rounds], axis=0)
                cnts = np.concatenate([c for _, c in rounds], axis=0)
                live = cnts > 0
                keys, cnts = keys[live], cnts[live]
            with obs_trace.span("wave.collect.sort") as sp_s:
                if sp_s:
                    sp_s.set(wave=w, rows=int(keys.shape[0]))
                order = np.argsort(row_bytes_view(keys), kind="stable")
                seg = IndexSegment(keys=keys[order], counts=cnts[order],
                                   sigma=self.cfg.sigma,
                                   vocab_size=self.cfg.vocab_size)
            if sp:
                sp.set(rows=int(keys.shape[0]), retries=retries,
                       shuffle_records=counters.get("shuffle_records", 0))
            return WavePartial(seg, int(keys.shape[0]), counters)

    def _collect_wave_mesh(self, pend: dict):
        """Mesh collect -> ``NGramStats`` (the ``iter_wave_stats`` shape)."""
        from repro.index.merge import segment_to_stats

        part = self._collect_wave_segment_mesh(pend)
        out = segment_to_stats(part.segment)
        out.counters = dict(part.counters)
        return out

    # --- public iteration ----------------------------------------------------- #

    def iter_wave_stats(self, tokens):
        """Per-wave exact partials (``tau = 1``) -- the streaming delta feed.

        Waves are double-buffered: wave ``i + 1`` is dispatched before wave
        ``i`` is materialized, so the consumer's host-side work (segment
        folds, generational ingest) overlaps device execution.  Mesh waves
        take the same path -- the fused sharded program defers its overflow
        check to collect time, so dispatch never waits on a host sync.
        """
        tokens = np.asarray(tokens, np.int32)
        self.cfg.validate_tokens(tokens)
        drv = DoubleBufferedDriver(self._submit_wave,
                                   collect=self._collect_wave)
        for w, (tok_ext, n_live) in enumerate(
                self._windows(tokens, to_device=not self._use_mesh)):
            res, _ = drv.submit(tok_ext, n_live, w)
            if res is not None:
                yield res
        res, _ = drv.drain()
        if res is not None:
            yield res

    def _for_each_wave(self, tokens, consume, *, collect=None,
                       submit=None) -> None:
        """Run ``consume(collected wave)`` for every wave, in wave order.

        ``collect`` maps a submitted wave to the object ``consume`` sees
        (default :meth:`_collect_wave` -> ``NGramStats``; the fold paths
        pass :meth:`_collect_wave_segment` -> :class:`WavePartial`); both
        route mesh waves to their sharded twins via the pend dict.
        ``submit`` dispatches a window the way ``collect`` reads it (default
        :meth:`_submit_wave`; the fold paths pass
        :meth:`_submit_segment_wave`, the split's tail pass
        :meth:`_submit_tail`).

        The wave-level parallel fold: the main thread stays a pure *feeder*
        -- it slices host token slabs and dispatches one fused program per
        wave (single-device or sharded) -- while a background fold thread
        materializes each wave and runs ``consume`` (the segment push
        of :meth:`run`, the generational ingest of :meth:`run_streaming`).
        Host-side fold work therefore overlaps the next waves' device work
        instead of serializing with it; a bounded queue
        (``_WAVES_IN_FLIGHT``) backpressures the feeder (the
        ``wave.feed.wait`` span) so at most a small
        constant number of waves is ever in flight, preserving the
        O(wave * sigma) memory model.  The single FIFO fold thread keeps
        wave order, so the fold sequence -- and with it the bit-identity
        contract -- is exactly the serial path's.  Mesh overflow reruns
        happen on the fold thread too (collect-time), so even a retried
        wave never stalls the feeder.  ``overlap=False`` serializes.
        """
        collect = collect or self._collect_wave
        submit = submit or self._submit_wave
        tokens = np.asarray(tokens, np.int32)
        self.cfg.validate_tokens(tokens)
        to_device = not self._use_mesh
        if not self.overlap:
            for w, (tok_ext, n_live) in enumerate(
                    self._windows(tokens, to_device=to_device)):
                consume(collect(submit(tok_ext, n_live, w)))
            return
        import queue
        import threading

        work: queue.Queue = queue.Queue(maxsize=_WAVES_IN_FLIGHT)
        failure: list[BaseException] = []

        def fold_loop():
            while True:
                pend = work.get()
                try:
                    if pend is None:
                        return
                    if not failure:
                        consume(collect(pend))
                except BaseException as e:      # propagate to the feeder
                    failure.append(e)
                finally:
                    work.task_done()

        folder = threading.Thread(target=fold_loop, name="wave-fold",
                                  daemon=True)
        # ``start`` returns once the OS has run the new thread: milliseconds
        # on a loaded host
        with obs_trace.span("wave.feed.start"):
            folder.start()
        try:
            for w, (tok_ext, n_live) in enumerate(
                    self._windows(tokens, to_device=to_device)):
                if failure:
                    break
                pend = submit(tok_ext, n_live, w)
                # waits only while the fold thread is behind
                with obs_trace.span("wave.feed.wait") as sp:
                    if sp:
                        sp.set(wave=w)
                    work.put(pend)
        finally:
            # the feeder waits for the fold thread to finish the last waves
            with obs_trace.span("wave.feed.drain"):
                work.put(None)
                folder.join()
        if failure:
            raise failure[0]

    # --- whole-job execution ------------------------------------------------- #

    def run(self, tokens):
        """Execute the job over waves -> ``NGramStats`` (canonical order),
        bit-identical to the monolithic single-job run.  ``fold_rows`` in the
        counters is the total segment rows fed through ``merge_segments`` --
        the fold's measured merge work.  A single-device job whose
        records are wider than ``SPLIT_HEAD_LANES`` lanes runs as a head/tail
        split (:meth:`_run_split`)."""
        from repro.core.stats import NGramStats
        from repro.index.merge import segment_to_stats

        with obs_trace.span("wave.run") as root:
            tokens = np.asarray(tokens, np.int32)
            if root:
                root.set(n_tokens=int(tokens.shape[0]),
                         method=self.cfg.method)
            # full canonical counter set (obs.metrics.COUNTER_DOC): identical
            # keys to the monolithic run_plan, plus the wave-only ones
            counters = dict.fromkeys(
                ("jobs", "map_records", "shuffle_records", "shuffle_bytes",
                 "retries", "overflow", "waves", "fold_rows",
                 "finalize_blocks", "d2h_bytes", "collect_host_sorts",
                 "head_dict_rows", "tail_positions", "tail_retries"), 0)
            counters["shuffle_skew"] = 0.0
            if self._head_ex is not None:
                out = self._run_split(tokens, counters)
            else:
                acc, loading = self._accumulator()
                self._fold_waves(tokens, acc, counters)
                with obs_trace.span("wave.finalize") as sp:
                    # tau filters inside the fold (on the "device"
                    # route before rows leave the chip) and again,
                    # idempotently, before the term unpack, so only the
                    # survivor set pays the unpack
                    out = segment_to_stats(
                        self._finalize(acc, loading, counters),
                        min_count=self.cfg.tau)
                    if sp:
                        sp.set(rows=len(out), fold_rows=acc.fold_rows)
            return NGramStats(out.grams, out.lengths, out.counts,
                              obs_metrics.normalize_counters(counters))

    def _accumulator(self):
        """A fresh accumulator for one pass's wave segments, and, on the
        ``"device"`` route, the load of its key width's block programs."""
        from repro.index.merge import (DeferredSegmentAccumulator,
                                       load_block_programs)
        acc = DeferredSegmentAccumulator(route=self.merge_route)
        loading = None
        if self.merge_route == "device":
            loading = load_block_programs(
                1 + packing.n_lanes(self.cfg.sigma, self.cfg.vocab_size))
        return acc, loading

    def _fold_waves(self, tokens, acc, counters: dict) -> None:
        """Every wave of ``tokens`` through this executor's wave program into
        ``acc``, the waves' counters into ``counters``."""
        def fold(part: WavePartial):
            # runs on the fold thread: overlaps the next wave's dispatch
            counters["waves"] += 1
            _merge_wave_counters(counters, part.counters)
            with obs_trace.span("wave.fold") as sp:
                if sp:
                    sp.set(wave=counters["waves"] - 1, rows=part.n_rows)
                acc.push(part.segment, n_rows=part.n_rows)

        self._for_each_wave(tokens, fold, collect=self._collect_wave_segment,
                            submit=self._submit_segment_wave)

    def _finalize(self, acc, loading, counters: dict):
        """The accumulator's fold at the job's tau -> one sorted segment (a
        lone pushed segment comes back unfiltered)."""
        if loading is not None:
            loading.result()
        merged = acc.result(min_count=self.cfg.tau)
        counters["fold_rows"] += acc.fold_rows
        counters["finalize_blocks"] += acc.finalize_blocks
        return merged

    # --- head/tail split ------------------------------------------------------ #

    def _run_split(self, tokens, counters: dict):
        """A job of wide records as two passes over the same waves.

        Pass A runs the method's ordinary waves at the head width (sigma =
        ``SPLIT_HEAD_LANES`` lanes' worth of terms) and finalizes them: every
        gram of at most ``head`` terms with cf >= tau.  A longer gram can
        reach tau only if its head does, and each of its occurrences starts
        at an occurrence of that head.  So the frequent full-length heads go
        to the device as a hash table (span ``wave.heads``, counter
        ``head_dict_rows``), and pass B runs every wave through the tail
        program (:func:`_build_tail_program`), which builds sigma-wide
        records only at the positions whose head is in the table (counter
        ``tail_positions``) and keeps the lengths past the head.  Its wave
        partials fold like pass A's.  The two passes' rows are disjoint by
        length, so pass A's rows, zero-padded to sigma, then pass B's are
        the job's output in canonical order.  Returns ``NGramStats``
        without counters (:meth:`run` adds them).
        """
        from repro.core.stats import NGramStats
        from repro.index.merge import segment_to_stats

        cfg, head_ex = self.cfg, self._head_ex
        head = head_ex.cfg.sigma
        acc_a, loading_a = head_ex._accumulator()
        acc_b, loading_b = self._accumulator()
        head_ex._fold_waves(tokens, acc_a, counters)
        with obs_trace.span("wave.finalize") as sp:
            seg_a = self._finalize(acc_a, loading_a, counters)
            if sp:
                sp.set(fold_rows=acc_a.fold_rows)
        with obs_trace.span("wave.heads") as sp:
            keys = np.asarray(seg_a.keys)[:seg_a.n_rows]
            frequent = ((keys[:, 0] == head)
                        & (np.asarray(seg_a.counts)[:seg_a.n_rows] >= cfg.tau))
            lanes = keys[frequent, 1:]
            counters["head_dict_rows"] = len(lanes)
            table = _head_table(lanes) if len(lanes) else None
            if sp:
                sp.set(rows=len(lanes))
        if table is not None:
            base = max(8, -(-self._wave_len(int(tokens.shape[0]))
                            // _TAIL_SHARE))
            n_tail = [0]

            def fold(part: WavePartial):
                _merge_wave_counters(counters, part.counters)
                with obs_trace.span("wave.fold") as sp:
                    if sp:
                        sp.set(wave=n_tail[0], rows=part.n_rows, tail=True)
                    n_tail[0] += 1
                    acc_b.push(part.segment, n_rows=part.n_rows)

            self._for_each_wave(tokens, fold, collect=self._collect_tail,
                                submit=partial(self._submit_tail,
                                               table=table, base=base))
        with obs_trace.span("wave.finalize") as sp:
            a = segment_to_stats(seg_a, min_count=cfg.tau)
            if table is not None:
                b = segment_to_stats(self._finalize(acc_b, loading_b,
                                                    counters),
                                     min_count=cfg.tau)
            else:
                # no tail, but later runs find the wide programs ready
                if loading_b is not None:
                    loading_b.result()
                b = NGramStats(np.zeros((0, cfg.sigma), np.int32),
                               np.zeros((0,), np.int32),
                               np.zeros((0,), np.int64))
            grams = np.zeros((len(a) + len(b), cfg.sigma), np.int32)
            grams[:len(a), :head] = a.grams
            grams[len(a):] = b.grams
            out = NGramStats(grams, np.concatenate([a.lengths, b.lengths]),
                             np.concatenate([a.counts, b.counts]))
            if sp:
                sp.set(rows=len(out), fold_rows=acc_b.fold_rows)
        return out

    def _submit_tail(self, tok_ext, n_live: int, wave: int = 0, *, table,
                     base: int) -> dict:
        """Dispatch one wave's tail pass; nothing materializes here.  The
        buffer holds ``base`` rows times the sticky doubling, and the pend
        dict keeps the window for a rerun."""
        cap = base * self._tail_scale
        with obs_trace.span("wave.submit") as sp:
            if sp:
                sp.set(wave=wave, n_live=n_live, tail=True, capacity=cap)
            outs = _tail_core(self.cfg, self._head_ex.cfg.sigma, tok_ext,
                              n_live, table, cap)
        return {"outs": outs, "tok_ext": tok_ext, "n_live": n_live,
                "table": table, "base": base, "capacity": cap, "wave": wave}

    def _collect_tail(self, pend: dict) -> WavePartial:
        """Materialize one wave's tail pass into a sorted host segment of its
        grams longer than the head (span ``wave.tail``: ``wave``,
        ``positions``, ``rows``).  Only the survivors' rows come back.  A
        wave with more survivors than its buffer reruns with the buffer
        doubled; the doubling sticks for later waves (``tail_retries``)."""
        from repro.index._layout import row_bytes_view
        from repro.index.build import IndexSegment

        cfg, w = self.cfg, pend["wave"]
        head = self._head_ex.cfg.sigma
        with obs_trace.span("wave.tail") as sp:
            if sp:
                sp.set(wave=w)
                _wait_for_device(pend["outs"], w)
            outs, cap, retries, d2h = pend["outs"], pend["capacity"], 0, 0
            while True:
                with obs_trace.span("wave.collect.d2h") as sp_d:
                    (counts, lanes, n_ok), nbytes = _to_host(*outs)
                    if sp_d:
                        sp_d.set(wave=w, bytes=nbytes)
                d2h += nbytes
                n_ok = int(n_ok)
                if n_ok <= cap:
                    break
                retries += 1
                while cap < n_ok:
                    cap *= 2
                self._tail_scale = max(self._tail_scale, cap // pend["base"])
                outs = _tail_core(cfg, head, pend["tok_ext"], pend["n_live"],
                                  pend["table"], cap)
            with obs_trace.span("wave.collect.rows") as sp_r:
                if sp_r:
                    sp_r.set(wave=w)
                keys, cnts = _prefix_rows(counts >= 1, counts, lanes,
                                          self._prefix_masks(),
                                          first_len=head + 1)
            with obs_trace.span("wave.collect.sort") as sp_s:
                if sp_s:
                    sp_s.set(wave=w, rows=int(keys.shape[0]))
                order = np.argsort(row_bytes_view(keys), kind="stable")
                seg = IndexSegment(keys=keys[order], counts=cnts[order],
                                   sigma=cfg.sigma, vocab_size=cfg.vocab_size)
            if sp:
                sp.set(positions=n_ok, rows=int(keys.shape[0]))
        return WavePartial(seg, int(keys.shape[0]),
                           {"tail_positions": n_ok, "tail_retries": retries,
                            "d2h_bytes": d2h})

    def run_streaming(self, tokens, *, gen=None, compress: bool = False,
                      block_size: int = 4, **gen_kw):
        """Stream waves straight into a :class:`GenerationalIndex`.

        Each wave's exact partial (``tau = 1``; nothing may be dropped early)
        is frozen and ingested as a fresh L0 segment -- point/top-k answers
        over the resulting index match a from-scratch build over the full
        corpus at ``tau = 1`` exactly, while the device only ever holds one
        wave of job state plus the serving artifacts.  The generational
        ingest (freeze + compaction) runs on the overlapped fold thread, so
        it proceeds while the device already works on the next waves.
        Returns ``(index, reports)`` with one ingest report per wave.
        """
        from repro.index.merge import GenerationalIndex
        if gen is None:
            gen = GenerationalIndex(sigma=self.cfg.sigma,
                                    vocab_size=self.cfg.vocab_size,
                                    compress=compress, block_size=block_size,
                                    **gen_kw)
        reports = []

        def ingest(part: WavePartial):
            # hand the bare collected segment to the LSM (an empty wave
            # ingests no segment); the query artifact materializes lazily
            # on first read
            reports.append(gen.ingest_segment(
                part.segment if part.n_rows else None, n_rows=part.n_rows))

        self._for_each_wave(tokens, ingest,
                            collect=self._collect_wave_segment,
                            submit=self._submit_segment_wave)
        return gen, reports
