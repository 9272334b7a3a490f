"""Compressed index layout: front-coded blocks + Elias-Fano monotone structures.

The frozen :class:`~repro.index.build.NGramIndex` stores every row's packed
lanes verbatim; past VMEM-resident shard sizes that is the dominant cost.
Following Pibiri & Venturini (*Handling Massive N-Gram Datasets Efficiently*),
the sorted immutable layout admits two classic compressors, both implemented
here in device-decodable form:

**Front-coded blocks.**  Rows are cut into fixed ``block_size`` blocks.  Each
block stores its first row verbatim (the *head*, kept bit-packed in lane form so
the existing lexicographic binary search runs on heads unchanged) and every
other row as ``(lcp, suffix terms)`` against its predecessor: ``lcp`` values ride
in a nibble/byte stream, suffix terms in a ``bits_for_vocab``-wide stream, and a
per-block base offset (cumulative suffix-term count) replaces per-row pointers
-- in-block offsets are a prefix sum of ``store_len - lcp``, which the decoder
recomputes on the fly.  Prefix sharing is measured at build time with the same
``lcp_boundary`` kernel the SUFFIX-sigma reducer uses.

**Elias-Fano.**  Every monotone structure the query plan reads (section
starts, the continuation fanout table, ``cont_cumsum``) is split into
unary-coded high bits (uint32 words plus a per-word rank directory) and packed
low bits; ``select`` is a branchless
fixed-trip-count search over the rank directory plus an in-word popcount scan,
so bracket lookups and continuation-mass queries stay jittable and batched.

Row order, sentinel padding, and tie-breaks are inherited *exactly* from the
uncompressed index -- ``compress_index`` is a pure re-encoding, which is what
makes bit-exact differential testing against :class:`NGramIndex` possible (see
``tests/test_compress.py``; a silently corrupted count would otherwise hide
behind plausible-looking output).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bitpack import extract_bits, pack_bits
from repro.mapreduce import pack as packing
from repro.core.stats import NGramStats
from repro.kernels.bsearch import search_steps
from ._layout import SENTINEL, pad_rows, row_lengths
from .build import IndexSegment, NGramIndex, build_index


# --------------------------------------------------------------------------- #
# Elias-Fano
# --------------------------------------------------------------------------- #

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EliasFano:
    """Monotone non-decreasing uint sequence in ~(2 + log2(U/n)) bits/value.

    ``high`` holds the unary upper parts (one i sits at bit ``i + (v_i >> l)``),
    ``word_rank`` the cumulative popcount per high word (the select directory),
    ``low`` the packed ``low_bits``-wide lower parts.
    """

    low: jax.Array        # [lw] uint32 packed low bits
    high: jax.Array       # [hw] uint32 unary high bits
    word_rank: jax.Array  # [hw+1] uint32 cumulative popcount of ``high``
    n: int = dataclasses.field(metadata=dict(static=True))
    low_bits: int = dataclasses.field(metadata=dict(static=True))
    universe: int = dataclasses.field(metadata=dict(static=True))

    @staticmethod
    def encode(values: np.ndarray, universe: int | None = None) -> "EliasFano":
        v = np.asarray(values, np.uint64)
        n = int(v.shape[0])
        if n == 0:
            raise ValueError("cannot Elias-Fano encode an empty sequence")
        if np.any(np.diff(v.astype(np.int64)) < 0):
            raise ValueError("sequence is not monotone non-decreasing")
        u = int(v.max()) if universe is None else int(universe)
        if u < int(v.max()):
            raise ValueError(f"universe {u} < max value {int(v.max())}")
        l = max(0, int(math.floor(math.log2(max(u, 1) / n))) if u > n else 0)
        l = min(l, 31)
        low = pack_bits((v & np.uint64((1 << l) - 1)).astype(np.uint32), l)
        ones = np.arange(n, dtype=np.uint64) + (v >> np.uint64(l))
        n_bits = n + (u >> l) + 1
        hw = max(1, -(-n_bits // 32))
        high = np.zeros((hw,), np.uint32)
        np.bitwise_or.at(high, (ones >> np.uint64(5)).astype(np.int64),
                         np.uint32(1) << (ones & np.uint64(31)).astype(np.uint32))
        pop = np.array([bin(int(w)).count("1") for w in high], np.uint32)
        word_rank = np.zeros((hw + 1,), np.uint32)
        word_rank[1:] = np.cumsum(pop, dtype=np.uint32)
        return EliasFano(jnp.asarray(low), jnp.asarray(high),
                         jnp.asarray(word_rank), n=n, low_bits=l, universe=u)

    def select(self, i: jax.Array) -> jax.Array:
        """Values [*i.shape] uint32 at positions ``i`` (0 <= i < n), jit-safe."""
        i = i.astype(jnp.uint32)
        # word holding the i-th one: last w with word_rank[w] <= i
        w = (jnp.searchsorted(self.word_rank, i, side="right") - 1).astype(jnp.int32)
        w = jnp.clip(w, 0, self.high.shape[0] - 1)
        rank_in = i - jnp.take(self.word_rank, w)
        word = jnp.take(self.high, w)
        bits = (word[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
        cum = jnp.cumsum(bits, axis=-1)
        bitpos = jnp.sum((cum <= rank_in[..., None]).astype(jnp.uint32), axis=-1)
        one_pos = w.astype(jnp.uint32) * 32 + bitpos
        high_val = one_pos - i
        low_val = extract_bits(self.low, i, self.low_bits)
        return (high_val << jnp.uint32(self.low_bits)) | low_val

    def decode_all(self) -> jax.Array:
        """All n values [n] uint32 in one pass over the high words.

        The batched-select fast path: a query batch issuing more selects than
        ~n/32 amortizes this whole-table decode (O(high words + n) work, and a
        *transient* buffer -- the resident layout stays compressed) and then
        reads answers with one plain gather each, instead of paying a
        rank-directory search per query.
        """
        hw = self.high.shape[0]
        j = jnp.arange(32, dtype=jnp.uint32)
        bits = (self.high[:, None] >> j[None, :]) & jnp.uint32(1)    # [hw, 32]
        pos = jnp.arange(hw, dtype=jnp.uint32)[:, None] * 32 + j
        # compact the one-positions by sorting (ones first, position order kept):
        # XLA lowers sort far better than the equivalent scatter on every
        # backend we serve from
        masked = jnp.where(bits > 0, pos, jnp.uint32(0xFFFFFFFF)).reshape(-1)
        one_pos = jax.lax.sort(masked)[:self.n]
        high_val = one_pos - jnp.arange(self.n, dtype=jnp.uint32)
        low_val = extract_bits(self.low, jnp.arange(self.n), self.low_bits)
        return (high_val << jnp.uint32(self.low_bits)) | low_val

    def select_many(self, i: jax.Array) -> jax.Array:
        """:meth:`select`, but batch-adaptive: whole-decode + gather when the
        (static) batch size amortizes it, per-query directory search when not.

        The crossover is deliberately tight (4 selects per value, was 64):
        ``decode_all``'s whole-table sort dominated batch-4096 lookup latency,
        and past a few selects per value the per-query directory search wins
        on every backend we measured.  Hot paths should prefer the decoded
        caches on :class:`CompressedNGramIndex` and never reach this.
        """
        if self.n <= 4 * int(np.prod(i.shape)):
            return jnp.take(self.decode_all(), jnp.clip(i, 0, self.n - 1))
        return self.select(i)

    @property
    def nbytes(self) -> int:
        return sum(int(np.asarray(a).nbytes)
                   for a in (self.low, self.high, self.word_rank))


# --------------------------------------------------------------------------- #
# Compressed index
# --------------------------------------------------------------------------- #

def lcp_width_for(sigma: int) -> int:
    """Nibble for sigma <= 14, byte beyond: lcp values never straddle a word."""
    if sigma <= 14:
        return 4
    if sigma <= 254:
        return 8
    raise ValueError(f"sigma {sigma} out of supported range")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CompressedNGramIndex:
    """Front-coded + Elias-Fano re-encoding of an :class:`NGramIndex`.

    Same logical rows in the same order (sentinels included); every query path
    must answer bit-identically to the uncompressed index.
    """

    # --- point-lookup view -------------------------------------------------- #
    heads: jax.Array         # [nb, HL] uint32 dense (row_len|terms) head keys
    lcps: jax.Array          # packed lcp stream, lcp_width bits/row
    payload: jax.Array       # packed suffix-term stream, term_bits bits/term
    block_base: jax.Array    # [nb+1] uint32 cumulative suffix terms per block
    counts_packed: jax.Array  # packed cf stream, count_width bits/row
    ef_section: EliasFano    # section_start  (sigma+1 values, universe=size)
    # (both views bracket their head bsearch through the decoded fanout
    # caches below; the point view's bracket rows never need EF encoding --
    # they are the flat fanout table's, divided by block_size)
    # --- continuation view -------------------------------------------------- #
    cont_heads: jax.Array        # [nb, HL] uint32 dense (gram len|prefix) keys
    cont_lcps: jax.Array
    cont_payload: jax.Array
    cont_block_base: jax.Array
    cont_last_packed: jax.Array   # packed next-term stream, term_bits bits/row
    cont_counts_packed: jax.Array  # packed cf stream, count_width bits/row
    ef_cont_fanout: EliasFano
    ef_cumsum: EliasFano          # cont_cumsum (size+1 values)
    # --- cached select directories ------------------------------------------ #
    # Deterministic decodes of the EF structures, precomputed once at build so
    # the query hot path gathers instead of paying per-batch EF select work.
    # The EFs above stay the at-rest format (``nbytes_at_rest``); these are
    # resident-only acceleration state, pure functions of the streams, so
    # merged-vs-built bit parity holds.  The fanout caches store the
    # head-search bracket *lo block* per (section, lead bucket) -- uint16 when
    # the block count allows -- which turns both views' head bsearch into the
    # fixed-``head_steps`` bracketed form.
    sec_cache: jax.Array       # [sigma+1] int32 decoded section starts
    cumsum_cache: jax.Array    # [size+1] uint32 decoded cont_cumsum
    fan_cache: jax.Array       # [sigma*(n_fanout+1)] point-view bracket blocks
    cont_fan_cache: jax.Array  # [sigma*(n_fanout+1)] cont-view bracket blocks
    # --- static meta -------------------------------------------------------- #
    sigma: int = dataclasses.field(metadata=dict(static=True))
    vocab_size: int = dataclasses.field(metadata=dict(static=True))
    size: int = dataclasses.field(metadata=dict(static=True))
    fanout_shift: int = dataclasses.field(metadata=dict(static=True))
    n_fanout: int = dataclasses.field(metadata=dict(static=True))
    block_size: int = dataclasses.field(metadata=dict(static=True))
    head_span: int = dataclasses.field(metadata=dict(static=True))
    head_steps: int = dataclasses.field(metadata=dict(static=True))
    term_bits: int = dataclasses.field(metadata=dict(static=True))
    count_width: int = dataclasses.field(metadata=dict(static=True))
    lcp_width: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_lanes(self) -> int:
        return packing.n_lanes(self.sigma, self.vocab_size)

    @property
    def n_blocks(self) -> int:
        return self.size // self.block_size

    @property
    def n_rows(self) -> int:
        """Real (non-sentinel) rows; the last section end."""
        return int(np.asarray(self.sec_cache[-1]))

    @property
    def nbytes(self) -> int:
        """Total resident bytes: the at-rest streams plus the decoded caches."""
        caches = (self.sec_cache, self.cumsum_cache, self.fan_cache,
                  self.cont_fan_cache)
        return (self.nbytes_at_rest
                + sum(int(np.asarray(a).nbytes) for a in caches))

    @property
    def nbytes_at_rest(self) -> int:
        """Bytes of the persisted compressed artifact: the front-coded /
        bit-packed streams plus the EF directories.  Excludes the decoded
        query caches, which are derived resident-only state rebuilt from the
        streams -- the number the compression-ratio contract and the
        generational ``bytes_at_rest`` gauges report."""
        arrays = (self.heads, self.lcps, self.payload, self.block_base,
                  self.counts_packed, self.cont_heads, self.cont_lcps,
                  self.cont_payload, self.cont_block_base,
                  self.cont_last_packed, self.cont_counts_packed)
        efs = (self.ef_section, self.ef_cont_fanout, self.ef_cumsum)
        return (sum(int(np.asarray(a).nbytes) for a in arrays)
                + sum(e.nbytes for e in efs))

    def section_starts(self) -> jax.Array:
        """Decoded [sigma+1] int32 section starts (the in-block length key)."""
        return self.sec_cache

    def to_segment(self) -> IndexSegment:
        """Decode the point view back into the sorted :class:`IndexSegment`.

        The inverse of ``compress_index`` restricted to the merge-relevant
        rows: :func:`decode_segment` streams the front-coded blocks back to
        the exact term matrix chunk by chunk, which re-packs to the exact
        lanes -- so segments extracted from the compressed layout merge
        bit-identically to ones from the flat layout.  (The merge path calls
        ``decode_segment`` directly and never pads back to capacity.)
        """
        seg = decode_segment(self)
        return IndexSegment(
            keys=jnp.asarray(pad_rows(np.asarray(seg.keys), self.size,
                                      SENTINEL)),
            counts=jnp.asarray(pad_rows(np.asarray(seg.counts), self.size,
                                        0)),
            sigma=self.sigma, vocab_size=self.vocab_size)


# shared with build/merge via index/_layout (satellite: constants dedupe)
_row_lengths = row_lengths

# rows decoded per chunk by decode_segment; module-level so tests can shrink
# it and assert the working-set bound
_DECODE_CHUNK_ROWS = 4096
# peak rows any single decode chunk materialized (test hook for the
# "compaction never decodes a full table" contract)
_DECODE_WATERMARK = {"rows": 0}


@partial(jax.jit, static_argnames=("term_bits", "lcp_width", "block_size",
                                   "vocab_size", "use_kernels"))
def _decode_chunk(lcps, payload, block_base, sec, ids, *, term_bits: int,
                  lcp_width: int, block_size: int, vocab_size: int,
                  use_kernels: bool):
    """Packed lanes [len(ids)*block_size, L] of the requested point blocks."""
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    sigma = sec.shape[0] - 1
    if use_kernels:
        terms = kops.block_expand(lcps, payload, block_base, sec, ids,
                                  sigma=sigma, term_bits=term_bits,
                                  lcp_width=lcp_width, block_size=block_size,
                                  len_off=0)
    else:
        terms = kref.block_expand_ref(lcps, payload, block_base, sec, ids,
                                      term_bits=term_bits, lcp_width=lcp_width,
                                      block_size=block_size, len_off=0)
    return packing.pack_terms(terms.reshape(-1, sigma), vocab_size=vocab_size)


def decode_segment(cidx: CompressedNGramIndex, *, chunk_rows: int | None = None,
                   use_kernels: bool = False) -> IndexSegment:
    """Stream the point view back into an **unpadded host** :class:`IndexSegment`.

    The compressed-native merge entry point: blocks decode ``chunk_rows`` rows
    at a time through one fixed-shape jitted program (the tail chunk clips
    block ids instead of recompiling), so the peak decoded working set is
    O(chunk), never the whole table.  Decode work is attributed to the metrics
    registry (``merge.blocks_decoded`` / ``compress.rows_decoded``) so any
    remaining full-table decode shows up in traces.
    """
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace

    b = cidx.block_size
    r = cidx.n_rows
    nb_used = -(-r // b)                       # blocks holding real rows
    cb = max(1, (chunk_rows if chunk_rows is not None
                 else _DECODE_CHUNK_ROWS) // b)
    # never wider than the table: an oversized chunk would pad ids out to the
    # requested width and decode the clamp-filler blocks over and over
    cb = min(cb, max(nb_used, 1))
    n_lanes = cidx.n_lanes
    keys = np.empty((r, 1 + n_lanes), np.uint32)
    keys[:, 0] = _row_lengths(np.asarray(cidx.sec_cache),
                              cidx.size)[:r].astype(np.uint32)
    sp = obs_trace.span("compress.decode")
    if sp:
        sp.set(rows=r, blocks=nb_used, chunk_blocks=cb)
    sp.__enter__()
    try:
        for c0 in range(0, nb_used, cb):
            ids = jnp.minimum(jnp.arange(c0, c0 + cb, dtype=jnp.int32),
                              max(cidx.n_blocks - 1, 0))
            lanes = np.asarray(_decode_chunk(
                cidx.lcps, cidx.payload, cidx.block_base, cidx.sec_cache, ids,
                term_bits=cidx.term_bits, lcp_width=cidx.lcp_width,
                block_size=b, vocab_size=cidx.vocab_size,
                use_kernels=use_kernels), np.uint32)
            lo, hi = c0 * b, min((c0 + cb) * b, r)
            keys[lo:hi, 1:] = lanes[:hi - lo]
            _DECODE_WATERMARK["rows"] = max(_DECODE_WATERMARK["rows"], cb * b)
        counts = np.asarray(extract_bits(cidx.counts_packed,
                                         jnp.arange(max(r, 1)),
                                         cidx.count_width), np.uint32)[:r]
    finally:
        sp.__exit__(None, None, None)
    reg = obs_metrics.get_registry()
    reg.counter("merge.blocks_decoded").add(nb_used)
    reg.counter("compress.rows_decoded").add(r)
    return IndexSegment(keys=keys, counts=counts, sigma=cidx.sigma,
                        vocab_size=cidx.vocab_size)


def head_key_layout(sigma: int, term_bits: int):
    """((offset, width) per field, n_lanes) of the dense head search key.

    Head rows are pure search accelerators (decode restarts from the payload
    at every block head), so they use a denser layout than the row lanes:
    (row_len, t0..t_{sigma-1}) concatenated MSB-first with no per-lane slack,
    split into uint32 lanes.  Lex order over the lanes equals lex order over
    (row_len, terms) -- the same total order the flat index sorts by -- while
    usually saving a lane per head vs the old (len | packed lanes) form:
    fewer gathers and compares per bsearch step on the hot path, and a
    smaller at-rest heads array.
    """
    len_bits = (sigma + 1).bit_length()     # row_len <= sigma+1 (sentinels)
    widths = [len_bits] + [term_bits] * sigma
    offs, o = [], 0
    for w in widths:
        offs.append(o)
        o += w
    return tuple(zip(offs, widths)), -(-o // 32)


def _pack_head_keys(row_len: np.ndarray, terms: np.ndarray,
                    *, term_bits: int) -> np.ndarray:
    """[n, HL] uint32 dense head keys (host build side of
    :func:`head_key_layout`; :func:`repro.index.query._dense_qkey` is the
    traced query side -- the two must pack bit-identically)."""
    n, sigma = terms.shape
    fields, hl = head_key_layout(sigma, term_bits)
    lanes = np.zeros((n, hl), np.uint32)
    cols = [row_len.astype(np.uint64)] + \
        [terms[:, j].astype(np.uint64) for j in range(sigma)]
    for (o, w), v in zip(fields, cols):
        v = v & np.uint64((1 << w) - 1)
        r = o + w
        j0 = o // 32
        e0 = 32 * (j0 + 1)
        if r <= e0:
            lanes[:, j0] |= (v << np.uint64(e0 - r)).astype(np.uint32)
        else:                       # field straddles a lane boundary
            lanes[:, j0] |= (v >> np.uint64(r - e0)).astype(np.uint32)
            e1 = 32 * ((r - 1) // 32 + 1)
            lanes[:, (r - 1) // 32] |= (
                (v << np.uint64(e1 - r)) & np.uint64(0xFFFFFFFF)
            ).astype(np.uint32)
    return lanes


def _lcp_host(terms: np.ndarray) -> np.ndarray:
    """lcp[i] = common prefix length of sorted rows i and i-1 (lcp[0] = 0)."""
    lcp = np.zeros(terms.shape[0], np.int32)
    if terms.shape[0] > 1:
        eq = (terms[1:] == terms[:-1]).astype(np.int32)
        lcp[1:] = np.cumprod(eq, axis=1).sum(axis=1)
    return lcp


def _front_code(terms: np.ndarray, row_len: np.ndarray,
                *, len_off: int, block_size: int, term_bits: int,
                lcp_width: int, payload_words: int | None):
    """(heads, lcps, payload, block_base) for one view.

    terms  : [size, S] int32 decoded term rows (view order, sentinels included)
    len_off: 0 for the point view, 1 for the continuation (prefix) view --
             stored terms per row = clip(row_len - len_off, 0, S); everything
             past that is PAD and reconstructed as 0.
    """
    size, sigma = terms.shape
    b = block_size
    if size % b:
        raise ValueError(f"size {size} not a multiple of block_size {b}")
    store_len = np.clip(row_len - len_off, 0, sigma).astype(np.int32)
    lcp = np.minimum(_lcp_host(terms), store_len)
    lcp[0::b] = 0                      # block heads restart the coding chain
    ns = store_len - lcp
    j = np.arange(sigma)[None, :]
    stored_mask = (j >= lcp[:, None]) & (j < store_len[:, None])
    suffix = terms[stored_mask].astype(np.uint32)   # C-order: row-major ✓
    cum = np.zeros(size + 1, np.int64)
    np.cumsum(ns, out=cum[1:])
    # size % b == 0, so the stride already ends on cum[size]: [nb+1] entries
    block_base = cum[0::b].astype(np.uint32)
    payload = pack_bits(suffix, term_bits, n_words=payload_words)
    lcps = pack_bits(lcp.astype(np.uint32), lcp_width)
    heads = _pack_head_keys(row_len[0::b], terms[0::b], term_bits=term_bits)
    return heads, lcps, payload, block_base


def _fan_lo_blocks(fan_rows: np.ndarray, block_size: int,
                   size: int) -> np.ndarray:
    """Per-(section, bucket) head-search bracket start, in *blocks*.

    The decoded fanout cache: one gather replaces the per-batch EF
    select/decode work that used to seed the head bsearch, and storing block
    ids (not rows) keeps it uint16 for every index under 64Ki blocks."""
    lo = fan_rows // block_size
    nb = size // block_size
    dt = np.uint16 if nb <= np.iinfo(np.uint16).max else np.int32
    return lo.astype(dt)


def compress_index(idx: NGramIndex, *, block_size: int = 4,
                   count_width: int | None = None,
                   payload_words: int | None = None,
                   cont_payload_words: int | None = None,
                   cumsum_universe: int | None = None,
                   head_span: int | None = None) -> CompressedNGramIndex:
    """Re-encode ``idx`` losslessly.  The capacity overrides exist so sharded
    builds can force identical array shapes / static meta across shards
    (stacked pytrees need a common treedef)."""
    sigma, vocab, size = idx.sigma, idx.vocab_size, idx.size
    tb = packing.bits_for_vocab(vocab)
    lw = lcp_width_for(sigma)
    section_start = np.asarray(idx.section_start)
    row_len = _row_lengths(section_start, size)
    counts = np.asarray(idx.counts)
    cw = count_width if count_width is not None else \
        max(1, int(counts.max()).bit_length() if counts.size else 1)

    terms = packing.unpack_terms_np(np.asarray(idx.lanes), vocab_size=vocab,
                                    sigma=sigma)
    heads, lcps, payload, block_base = _front_code(
        terms, row_len, len_off=0, block_size=block_size,
        term_bits=tb, lcp_width=lw, payload_words=payload_words)

    c_terms = packing.unpack_terms_np(np.asarray(idx.cont_prefix),
                                      vocab_size=vocab, sigma=sigma)
    c_heads, c_lcps, c_payload, c_block_base = _front_code(
        c_terms, row_len, len_off=1, block_size=block_size,
        term_bits=tb, lcp_width=lw, payload_words=cont_payload_words)

    fan = np.asarray(idx.fanout, np.int64).reshape(-1)
    c_fan = np.asarray(idx.cont_fanout, np.int64).reshape(-1)
    if head_span is None:
        # widest fanout cell measured in blocks: every head-search bracket is
        # [lo // B, lo // B + head_span), so the fixed-trip head bsearch stops
        # after log2(span) instead of log2(n_blocks) steps -- the compressed
        # layout's analogue of the fanout table shrinking the row search.  The
        # +1 covers a cell straddling one extra block boundary than its row
        # count suggests.
        head_span = 1
        for t in (np.asarray(idx.fanout), np.asarray(idx.cont_fanout)):
            if t.size:
                head_span = max(head_span, int(np.max(
                    -(-t[:, 1:] // block_size) - t[:, :-1] // block_size)) + 1)
        head_span = min(head_span, size // block_size)
    cumsum = np.asarray(idx.cont_cumsum, np.int64)
    for name, seq in (("fanout", fan), ("cont_fanout", c_fan)):
        if seq.size and np.any(np.diff(seq) < 0):
            raise AssertionError(f"{name} table is not monotone when flattened")

    return CompressedNGramIndex(
        heads=jnp.asarray(heads), lcps=jnp.asarray(lcps),
        payload=jnp.asarray(payload), block_base=jnp.asarray(block_base),
        counts_packed=jnp.asarray(pack_bits(counts.astype(np.uint32), cw)),
        ef_section=EliasFano.encode(section_start, universe=size),
        cont_heads=jnp.asarray(c_heads), cont_lcps=jnp.asarray(c_lcps),
        cont_payload=jnp.asarray(c_payload),
        cont_block_base=jnp.asarray(c_block_base),
        cont_last_packed=jnp.asarray(
            pack_bits(np.asarray(idx.cont_last, np.uint32), tb)),
        cont_counts_packed=jnp.asarray(
            pack_bits(np.asarray(idx.cont_counts, np.uint32), cw)),
        ef_cont_fanout=EliasFano.encode(c_fan, universe=size),
        ef_cumsum=EliasFano.encode(
            cumsum, universe=cumsum_universe if cumsum_universe is not None
            else int(cumsum[-1])),
        sec_cache=jnp.asarray(section_start.astype(np.int32)),
        cumsum_cache=jnp.asarray(cumsum.astype(np.uint32)),
        fan_cache=jnp.asarray(_fan_lo_blocks(fan, block_size, size)),
        cont_fan_cache=jnp.asarray(_fan_lo_blocks(c_fan, block_size, size)),
        sigma=sigma, vocab_size=vocab, size=size,
        fanout_shift=idx.fanout_shift, n_fanout=idx.n_fanout,
        block_size=block_size, head_span=head_span,
        head_steps=search_steps(head_span),
        term_bits=tb, count_width=cw, lcp_width=lw,
    )


def build_compressed_index(stats: NGramStats, *, vocab_size: int,
                           pad_to: int | None = None,
                           block_size: int = 4) -> CompressedNGramIndex:
    """Job output -> compressed index (freeze uncompressed, then re-encode)."""
    return compress_index(build_index(stats, vocab_size=vocab_size,
                                      pad_to=pad_to), block_size=block_size)


def decode_view(cidx: CompressedNGramIndex, view: str = "point") -> np.ndarray:
    """Reconstruct the full [size, S] term matrix of one view (host, for tests).

    Exactness here is the structural half of the parity argument: if the decode
    round-trips every row, any query mismatch must be in the search plan.
    """
    if view == "point":
        lcps, payload, base, len_off = (cidx.lcps, cidx.payload,
                                        cidx.block_base, 0)
    elif view == "cont":
        lcps, payload, base, len_off = (cidx.cont_lcps, cidx.cont_payload,
                                        cidx.cont_block_base, 1)
    else:
        raise ValueError(view)
    size, sigma, b = cidx.size, cidx.sigma, cidx.block_size
    sec = np.asarray(cidx.section_starts())
    row_len = _row_lengths(sec, size)
    store_len = np.clip(row_len - len_off, 0, sigma)
    lcp = np.asarray(extract_bits(lcps, jnp.arange(size), cidx.lcp_width)) \
        .astype(np.int64)
    ns = store_len - lcp
    total = int(np.asarray(base)[-1])
    vals = np.asarray(extract_bits(payload, jnp.arange(max(total, 1)),
                                   cidx.term_bits)).astype(np.int64)[:total]
    cum = np.zeros(size + 1, np.int64)
    np.cumsum(ns, out=cum[1:])
    j = np.arange(sigma)[None, :]
    tpos = cum[:-1, None] + (j - lcp[:, None])
    stored_mask = (j >= lcp[:, None]) & (j < store_len[:, None])
    aligned = np.where(stored_mask, vals[np.clip(tpos, 0, max(total - 1, 0))], 0)
    lcp_b = lcp.reshape(-1, b)
    aligned_b = aligned.reshape(-1, b, sigma)
    slen_b = store_len.reshape(-1, b)
    cand = np.where(lcp_b[:, :, None] <= j[None], np.arange(b)[None, :, None], -1)
    prov = np.maximum.accumulate(cand, axis=1)
    taken = np.take_along_axis(aligned_b, prov, axis=1)
    slen_p = np.take_along_axis(
        np.broadcast_to(slen_b[:, :, None], aligned_b.shape), prov, axis=1)
    out = np.where(j[None] < slen_p, taken, 0).reshape(size, sigma)
    return out.astype(np.int64)
