"""Vocab-adaptive bit packing of term-id lanes.

The paper (SS-V "Sequence Encoding") replaces textual terms by integer ids assigned in
descending collection-frequency order and varbyte-encodes them so that (a) fewer bytes
are shuffled and (b) comparisons run on integers.  On TPU the analogous win is packing
several term ids into each 32-bit sort lane, most-significant-first, so that

  * ascending lexicographic sort on the packed lanes == ascending lexicographic sort
    on the raw term sequences (PAD = 0 sorts before every real term), and
  * the number of sort passes (one per key lane in ``jax.lax.sort``) drops by the
    packing factor.

Packing is exact and invertible; ``bits_for_vocab`` chooses the lane layout from the
vocabulary size.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PAD_ID = 0  # reserved: sorts first, marks end-of-document / end-of-suffix


def bits_for_vocab(vocab_size: int) -> int:
    """Bits per term id (ids are 1..vocab_size, 0 is PAD)."""
    if vocab_size < 1:
        raise ValueError(f"vocab_size must be >= 1, got {vocab_size}")
    return max(1, math.ceil(math.log2(vocab_size + 1)))


def terms_per_lane(vocab_size: int) -> int:
    return max(1, 32 // bits_for_vocab(vocab_size))


def n_lanes(sigma: int, vocab_size: int) -> int:
    return (sigma + terms_per_lane(vocab_size) - 1) // terms_per_lane(vocab_size)


@partial(jax.jit, static_argnames=("vocab_size",))
def pack_terms(terms: jax.Array, *, vocab_size: int) -> jax.Array:
    """Pack ``terms`` [..., sigma] (int32, PAD=0) into uint32 lanes [..., n_lanes].

    Earlier terms occupy more-significant bits so lane-major ascending order is
    lexicographic term order.
    """
    sigma = terms.shape[-1]
    bits = bits_for_vocab(vocab_size)
    per = terms_per_lane(vocab_size)
    lanes = n_lanes(sigma, vocab_size)
    pad_to = lanes * per
    t = terms.astype(jnp.uint32)
    if pad_to != sigma:
        pad_width = [(0, 0)] * (t.ndim - 1) + [(0, pad_to - sigma)]
        t = jnp.pad(t, pad_width)
    t = t.reshape(t.shape[:-1] + (lanes, per))
    shifts = jnp.arange(per - 1, -1, -1, dtype=jnp.uint32) * jnp.uint32(bits)
    return jnp.sum(t << shifts, axis=-1).astype(jnp.uint32)


def pack_terms_np(terms: np.ndarray, *, vocab_size: int) -> np.ndarray:
    """Host numpy mirror of :func:`pack_terms` -- bit-identical lanes.

    The wave fold packs each wave's (already materialized) partial on the
    host; a device dispatch per wave just to shift-and-sum integers would
    serialize with the next wave's real work.
    """
    sigma = terms.shape[-1]
    bits = bits_for_vocab(vocab_size)
    per = terms_per_lane(vocab_size)
    lanes = n_lanes(sigma, vocab_size)
    pad_to = lanes * per
    t = terms.astype(np.uint32)
    if pad_to != sigma:
        pad_width = [(0, 0)] * (t.ndim - 1) + [(0, pad_to - sigma)]
        t = np.pad(t, pad_width)
    t = t.reshape(t.shape[:-1] + (lanes, per))
    shifts = np.arange(per - 1, -1, -1, dtype=np.uint32) * np.uint32(bits)
    return (t << shifts).sum(axis=-1, dtype=np.uint32)


def prefix_lane_masks(sigma: int, vocab_size: int) -> np.ndarray:
    """AND-masks [sigma + 1, n_lanes] uint32 reducing packed lanes to prefixes.

    ``lanes & masks[l]`` zeroes the bit fields of every term slot past the
    first ``l``, which is exactly ``pack_terms`` of the length-``l`` prefix
    padded with PAD=0 -- each term occupies its own bit field, so zeroing a
    slot's bits equals packing a PAD there.  Lets a collector derive every
    prefix gram's packed key directly from the full suffix lanes, with no
    unpack -> re-pack round trip.
    """
    bits = bits_for_vocab(vocab_size)
    per = terms_per_lane(vocab_size)
    lanes = n_lanes(sigma, vocab_size)
    field = (1 << bits) - 1
    masks = np.zeros((sigma + 1, lanes), np.uint32)
    for l in range(sigma + 1):
        for j in range(lanes):
            m = 0
            for i in range(per):
                if j * per + i < l:
                    m |= field << ((per - 1 - i) * bits)
            masks[l, j] = np.uint32(m & 0xFFFFFFFF)
    return masks


@partial(jax.jit, static_argnames=("vocab_size", "sigma"))
def unpack_terms(lanes_arr: jax.Array, *, vocab_size: int, sigma: int) -> jax.Array:
    """Inverse of :func:`pack_terms` -> int32 [..., sigma]."""
    bits = bits_for_vocab(vocab_size)
    per = terms_per_lane(vocab_size)
    shifts = jnp.arange(per - 1, -1, -1, dtype=jnp.uint32) * jnp.uint32(bits)
    mask = jnp.uint32((1 << bits) - 1) if bits < 32 else jnp.uint32(0xFFFFFFFF)
    t = (lanes_arr[..., None] >> shifts) & mask
    t = t.reshape(t.shape[:-2] + (-1,))
    return t[..., :sigma].astype(jnp.int32)


def unpack_terms_np(lanes_arr: np.ndarray, *, vocab_size: int,
                    sigma: int) -> np.ndarray:
    """Host numpy mirror of :func:`unpack_terms` -- bit-identical terms.

    Index builds unpack on the host: a device unpack would compile anew for
    every distinct row count."""
    bits = bits_for_vocab(vocab_size)
    per = terms_per_lane(vocab_size)
    shifts = np.arange(per - 1, -1, -1, dtype=np.uint32) * np.uint32(bits)
    mask = np.uint32((1 << bits) - 1) if bits < 32 else np.uint32(0xFFFFFFFF)
    t = (np.asarray(lanes_arr, np.uint32)[..., None] >> shifts) & mask
    t = t.reshape(t.shape[:-2] + (t.shape[-2] * per,))
    return t[..., :sigma].astype(np.int32)


def lead_term(lane0: jax.Array, *, vocab_size: int) -> jax.Array:
    """First (most significant) term id of lane 0 -- the shuffle/serving routing key.

    The packer puts earlier terms in more-significant bits, so the lead term is a
    single shift of the first lane: the same key the paper's Algorithm-4 partitioner
    hashes, and the key the serving layer routes queries by so index shards align
    with reducer outputs.
    """
    shift = (terms_per_lane(vocab_size) - 1) * bits_for_vocab(vocab_size)
    return (lane0.astype(jnp.uint32) >> jnp.uint32(shift)).astype(jnp.uint32)


def record_width(sigma: int, vocab_size: int, n_meta: int = 0) -> int:
    """Lanes per shuffle record: packed suffix + weight lane + meta lanes."""
    return n_lanes(sigma, vocab_size) + 1 + n_meta


def record_bytes(sigma: int, vocab_size: int, n_meta: int = 0) -> int:
    return 4 * record_width(sigma, vocab_size, n_meta)
