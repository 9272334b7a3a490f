"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.mapreduce import pack as packing
from repro.mapreduce.shuffle import hash_u32


def lcp_boundary_ref(sorted_terms: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(lcp [N], flags [N, L]) of a lexicographically sorted int32 matrix."""
    prev = jnp.roll(sorted_terms, 1, axis=0)
    eq = (sorted_terms == prev).astype(jnp.int32)
    lcp = jnp.sum(jnp.cumprod(eq, axis=1), axis=1).at[0].set(0)
    n, length = sorted_terms.shape
    lengths = jnp.arange(1, length + 1, dtype=jnp.int32)
    flags = (lcp[:, None] < lengths[None, :]) & (sorted_terms != 0)
    return lcp.astype(jnp.int32), flags


def suffix_pack_ref(tokens: jax.Array, *, sigma: int, vocab_size: int) -> jax.Array:
    """Packed sigma-truncated suffix lanes [N, n_lanes] of a PAD-separated stream."""
    n = tokens.shape[0]
    padded = jnp.concatenate([tokens, jnp.zeros((sigma,), tokens.dtype)])
    idx = jnp.arange(n)[:, None] + jnp.arange(sigma)[None, :]
    w = padded[idx]
    keep = jnp.cumprod((w != 0).astype(jnp.int32), axis=1)
    return packing.pack_terms((w * keep).astype(jnp.int32), vocab_size=vocab_size)


def bsearch_ref(lanes: jax.Array, queries: jax.Array, lo: jax.Array,
                hi: jax.Array, *, upper: bool = False,
                steps: int | None = None) -> jax.Array:
    """Batched lexicographic lower/upper bound on sorted packed lanes [R, L].

    Fixed-iteration branchless search, vmapped over queries; semantics match
    ``repro.kernels.bsearch.bsearch`` (its allclose target and the default
    ``use_kernels=False`` serving path)."""
    if steps is None:
        from .bsearch import search_steps
        steps = search_steps(lanes.shape[0])

    def one(q, lo_i, hi_i):
        def body(_, state):
            lo_c, hi_c = state
            mid = (lo_c + hi_c) // 2
            row = lanes[mid]
            eq = row == q
            prefix_eq = jnp.concatenate(
                [jnp.ones((1,), jnp.bool_),
                 jnp.cumprod(eq[:-1].astype(jnp.int32)).astype(bool)])
            go_right = jnp.any(prefix_eq & (row < q))
            if upper:
                go_right = go_right | jnp.all(eq)
            open_ = lo_c < hi_c
            lo_c = jnp.where(open_ & go_right, mid + 1, lo_c)
            hi_c = jnp.where(open_ & ~go_right, mid, hi_c)
            return lo_c, hi_c

        out, _ = jax.lax.fori_loop(0, steps, body,
                                   (lo_i.astype(jnp.int32),
                                    hi_i.astype(jnp.int32)))
        return out

    return jax.vmap(one)(queries, lo, hi)


def block_expand_ref(lcps: jax.Array, payload: jax.Array, block_base: jax.Array,
                     sec_starts: jax.Array, blk: jax.Array, *, term_bits: int,
                     lcp_width: int, block_size: int,
                     len_off: int) -> jax.Array:
    """Decoded term matrix [B, block_size, sigma] int32 of the requested blocks.

    Semantics match ``repro.kernels.block_expand.block_expand`` (its allclose
    target and the ``use_kernels=False`` chunked-decode path).  Decode is the
    parallel form of the coding chain: lane j of row r comes from the last row
    p <= r whose stored span covers j.  When row id and term value pack into an
    int32 together, one running max over ``(row << term_bits) | value`` resolves
    the provider AND fetches its value (rows past a provider's span contribute
    the provider's explicit 0, so the zero-fill rides along); otherwise the
    provider index is cummax'd alone and gathered.  Both equal the sequential
    prev-row substitution the kernel runs.
    """
    from repro.kernels.bitpack import extract_bits

    b, sigma = block_size, sec_starts.shape[0] - 1
    g = blk.astype(jnp.int32)[:, None] * b + jnp.arange(b, dtype=jnp.int32)
    lcp = extract_bits(lcps, g, lcp_width).astype(jnp.int32)        # [Q, B]
    row_len = jnp.sum((g[..., None] >= sec_starts[None, None, :])
                      .astype(jnp.int32), axis=-1)                  # [Q, B]
    store_len = jnp.clip(row_len - len_off, 0, sigma)
    lcp = jnp.minimum(lcp, store_len)
    # no forced reset at row 0: a head lane with lcp > 0 decodes as 0 (negative
    # provider here, the zero-initialized prev carry in the kernel) -- the
    # builder always writes lcp 0 at block heads, so the case only arises in
    # fuzzed streams
    ns = store_len - lcp
    off_in = jnp.concatenate(
        [jnp.zeros((g.shape[0], 1), jnp.int32),
         jnp.cumsum(ns, axis=1)[:, :-1]], axis=1)
    base = block_base[blk].astype(jnp.int32)
    j = jnp.arange(sigma, dtype=jnp.int32)
    tpos = base[:, None, None] + off_in[..., None] + (j - lcp[..., None])
    # gathers dominate on CPU: when a row's suffix span fits a small static word
    # window, fetch the window once per row and mux lanes out of it arithmetically
    # instead of issuing two word gathers per (row, lane)
    # lane words sit up to ((S-1)*tb + 31) >> 5 words past the row's first word
    # (worst case: the row starts at bit 31 of its word)
    span_words = ((sigma - 1) * term_bits + 31) // 32 + 1
    if span_words <= 6:
        nw = payload.shape[0]
        row_bit0 = (base[:, None] + off_in).astype(jnp.uint32) * term_bits
        w0 = (row_bit0 >> 5).astype(jnp.int32)                      # [Q, B]
        win = jnp.stack([jnp.take(payload, jnp.clip(w0 + t, 0, nw - 1))
                         for t in range(span_words + 1)], axis=-1)  # [Q,B,W+1]
        bitp = jnp.maximum(tpos, 0).astype(jnp.uint32) * term_bits
        rel = (bitp >> 5).astype(jnp.int32) - w0[..., None]
        lo_w = hi_w = jnp.zeros(bitp.shape, jnp.uint32)
        for t in range(span_words):
            lo_w = jnp.where(rel == t, win[..., t:t + 1], lo_w)
            hi_w = jnp.where(rel == t, win[..., t + 1:t + 2], hi_w)
        sh = bitp & 31
        stored = ((lo_w >> sh)
                  | jnp.where(sh > 0, hi_w << ((32 - sh) & 31), 0)) \
            & jnp.uint32((1 << term_bits) - 1)
        stored = stored.astype(jnp.int32)
    else:
        stored = extract_bits(payload, tpos, term_bits).astype(jnp.int32)
    valid_store = (j >= lcp[..., None]) & (j < store_len[..., None])
    aligned = jnp.where(valid_store, stored, 0)                     # [Q, B, S]
    covers = lcp[..., None] <= j
    r_id = jnp.arange(b, dtype=jnp.int32)[None, :, None]
    if b.bit_length() + term_bits <= 31:
        kv = jnp.where(covers, (r_id << term_bits) | aligned, -1)
        run = jax.lax.cummax(kv, axis=1)
        # run < 0 == no provider yet (fuzzed streams only): decode 0, not mask
        decoded = jnp.where(run < 0, 0, run & ((1 << term_bits) - 1))
    else:  # row id and value don't co-pack: cummax the provider, then gather
        prov = jax.lax.cummax(jnp.where(covers, r_id, -1), axis=1)
        decoded = jnp.where(
            prov >= 0,
            jnp.take_along_axis(aligned, jnp.maximum(prov, 0), axis=1), 0)
    return decoded


def block_decode_ref(lcps: jax.Array, payload: jax.Array, block_base: jax.Array,
                     sec_starts: jax.Array, blk: jax.Array, q_terms: jax.Array,
                     q_len: jax.Array, *, term_bits: int, lcp_width: int,
                     block_size: int, len_off: int) -> tuple[jax.Array, jax.Array]:
    """(cnt_lt [Q], cnt_eq [Q]): front-coded block decode + in-block rank.

    Semantics match ``repro.kernels.block_decode.block_decode`` (its allclose
    target and the ``use_kernels=False`` compressed-serving path).  The decode
    half is ``block_expand_ref``; this adds the per-query lexicographic
    (row_len, terms) rank against the decoded candidate block.
    """
    b, sigma = block_size, q_terms.shape[1]
    decoded = block_expand_ref(lcps, payload, block_base, sec_starts, blk,
                               term_bits=term_bits, lcp_width=lcp_width,
                               block_size=b, len_off=len_off)
    g = blk.astype(jnp.int32)[:, None] * b + jnp.arange(b, dtype=jnp.int32)
    row_len = jnp.sum((g[..., None] >= sec_starts[None, None, :])
                      .astype(jnp.int32), axis=-1)                  # [Q, B]
    qt = q_terms.astype(jnp.int32)[:, None, :]
    eq = decoded == qt
    prefix_eq = jnp.concatenate(
        [jnp.ones(eq[..., :1].shape, jnp.bool_),
         jnp.cumprod(eq[..., :-1].astype(jnp.int32), axis=-1).astype(bool)],
        axis=-1)
    t_lt = jnp.any(prefix_eq & (decoded < qt), axis=-1)
    t_eq = jnp.all(eq, axis=-1)
    len_eq = row_len == q_len.astype(jnp.int32)[:, None]
    is_lt = (row_len < q_len[:, None]) | (len_eq & t_lt)
    is_eq = len_eq & t_eq
    return (jnp.sum(is_lt.astype(jnp.int32), axis=1),
            jnp.sum(is_eq.astype(jnp.int32), axis=1))


def merge_path_ref(a_keys: jax.Array, b_keys: jax.Array, a_vals: jax.Array,
                   b_vals: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(keys [M+N, K], vals [M+N]): stable two-way merge of sorted key matrices.

    Rows compare lexicographically (uint32 lanes); on ties every A row precedes
    every B row.  Semantics match ``repro.kernels.merge_path.merge_path`` (its
    allclose target; no merge route calls either).  The ref takes
    the rank-and-scatter route -- each A row's output slot is its index plus the
    count of strictly-smaller B rows, each B row's its index plus the count of
    less-or-equal A rows -- deliberately a different algorithm from the kernel's
    diagonal (merge-path) search, so the differential test cross-checks two
    derivations of the same permutation.
    """
    m, n = a_keys.shape[0], b_keys.shape[0]
    zeros_m = jnp.zeros((m,), jnp.int32)
    zeros_n = jnp.zeros((n,), jnp.int32)
    pos_a = jnp.arange(m, dtype=jnp.int32) + bsearch_ref(
        b_keys, a_keys, zeros_m, zeros_m + n, upper=False)
    pos_b = jnp.arange(n, dtype=jnp.int32) + bsearch_ref(
        a_keys, b_keys, zeros_n, zeros_n + m, upper=True)
    keys = jnp.zeros((m + n, a_keys.shape[1]), a_keys.dtype)
    keys = keys.at[pos_a].set(a_keys).at[pos_b].set(b_keys)
    vals = jnp.zeros((m + n,), a_vals.dtype)
    vals = vals.at[pos_a].set(a_vals).at[pos_b].set(b_vals)
    return keys, vals


def hash_combine_ref(keys: jax.Array, weights: jax.Array, *,
                     block: int = 256) -> jax.Array:
    """Redistributed weights [N] of the block-local hash-slot combiner.

    Semantics match ``repro.kernels.hash_combine.hash_combine`` (its allclose
    target and the ``use_kernels=False`` combine route).  Deliberately the
    scatter/gather derivation -- ``.at[].min`` slot winners, row gathers,
    ``segment_sum`` folds -- where the kernel uses dense one-hot planes, so
    the differential test cross-checks two formulations of the same table.
    """
    from repro.mapreduce.shuffle import fold_hash

    n, n_keys = keys.shape
    nb = max(1, -(-n // block))
    n_pad = nb * block
    k = jnp.pad(keys.astype(jnp.uint32), ((0, n_pad - n), (0, 0)))
    w = jnp.pad(weights.astype(jnp.uint32), (0, n_pad - n))
    n_slots = 2 * block
    ids = jnp.arange(block, dtype=jnp.int32)

    def one(kb, wb):
        slot = (fold_hash(kb) % jnp.uint32(n_slots)).astype(jnp.int32)
        winner = jnp.full((n_slots,), block, jnp.int32).at[slot].min(ids)
        rep = winner[slot]
        match = jnp.all(kb[rep] == kb, axis=1)
        contrib = jnp.where(match, wb, jnp.uint32(0))
        totals = jax.ops.segment_sum(contrib, rep, num_segments=block)
        return jnp.where(rep == ids, totals,
                         jnp.where(match, jnp.uint32(0), wb))

    out = jax.vmap(one)(k.reshape(nb, block, n_keys), w.reshape(nb, block))
    return out.reshape(-1)[:n]


def hash_partition_ref(keys: jax.Array, valid: jax.Array,
                       n_parts: int) -> tuple[jax.Array, jax.Array]:
    """(partition ids [N] with n_parts for invalid, histogram [n_parts])."""
    p = (hash_u32(keys) % jnp.uint32(n_parts)).astype(jnp.int32)
    p = jnp.where(valid, p, n_parts)
    hist = jnp.sum(jax.nn.one_hot(p, n_parts + 1, dtype=jnp.int32), axis=0)[:n_parts]
    return p, hist
