"""Hash-bucketed all_to_all exchange -- the MapReduce shuffle on a TPU mesh.

Hadoop's shuffle hashes each key to a reducer and streams records over the network.
The TPU-native equivalent is the MoE-dispatch pattern: bucket records into a
fixed-capacity [n_parts, capacity, W] buffer and exchange with
``jax.lax.all_to_all`` over the mesh axis.  Capacity is a head-room knob
(``capacity_factor``); overflow is *counted*, never silently dropped -- the driver
retries the job with doubled capacity (the Hadoop analogue: a reducer re-run after a
spill failure).

The paper's partitioner (Algorithm 4) hashes the suffix's **first term only**, which
is the load-balance-vs-correctness trade-off SUFFIX-sigma needs: all evidence for an
n-gram lands on one reducer.  Zipf skew of lead terms is absorbed by the capacity
factor; we measure the realized skew in the benchmarks.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# host scalars: a device scalar here would start a backend at import
KNUTH = np.uint32(2654435761)
GOLDEN = np.uint32(0x9E3779B9)


def hash_u32(x: jax.Array) -> jax.Array:
    """Multiplicative hashing (Knuth) with an xorshift finalizer."""
    h = x.astype(jnp.uint32) * KNUTH
    h = h ^ (h >> 15)
    h = h * jnp.uint32(2246822519)
    return h ^ (h >> 13)


def fold_hash(lanes: jax.Array) -> jax.Array:
    """Order-sensitive fold hash of packed key lanes [..., L] -> uint32.

    The one whole-record hash of the system: the NAIVE/APRIORI partition key,
    the APRIORI membership-dictionary key, and the map-side hash combiner's
    slot key all come from here, so two phases never disagree on which rows
    are "the same gram"."""
    h = jnp.zeros(lanes.shape[:-1], jnp.uint32)
    for i in range(lanes.shape[-1]):
        h = hash_u32(h ^ lanes[..., i] + GOLDEN)
    return h


def record_key(lanes: jax.Array, *, kind: str, vocab_size: int) -> jax.Array:
    """Partition key of packed gram lanes [..., L] -- the one shuffle-key API.

    ``kind="gram"`` hashes the whole record (any reducer may count any gram --
    NAIVE/APRIORI); ``kind="lead"`` routes by the first term only (all evidence
    of an n-gram shares a reducer -- SUFFIX-sigma, and the serving layer's
    shard router)."""
    if kind == "gram":
        return fold_hash(lanes)
    if kind == "lead":
        from repro.mapreduce import pack as packing
        return packing.lead_term(lanes[..., 0], vocab_size=vocab_size)
    raise ValueError(f"unknown partition key kind {kind!r}")


def partition_ids(keys: jax.Array, valid: jax.Array, n_parts: int) -> jax.Array:
    """Reducer id per record; invalid records go to the drop bucket ``n_parts``."""
    p = (hash_u32(keys) % jnp.uint32(n_parts)).astype(jnp.int32)
    return jnp.where(valid, p, n_parts)


@partial(jax.jit, static_argnames=("n_parts", "capacity"))
def bucketize(records: jax.Array, part: jax.Array, n_parts: int,
              capacity: int) -> tuple[jax.Array, jax.Array]:
    """Scatter records [N, W] into buckets [n_parts, capacity, W].

    ``part`` in [0, n_parts] (n_parts = drop).  Returns (buffer, overflow_count).
    Empty slots are all-zero (weight lane 0 marks them invalid downstream).
    """
    n, w = records.shape
    order = jnp.argsort(part, stable=True)
    p_s = part[order]
    rec_s = records[order]
    counts = jnp.bincount(p_s, length=n_parts + 1)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    within = jnp.arange(n, dtype=jnp.int32) - offsets[p_s].astype(jnp.int32)
    ok = (within < capacity) & (p_s < n_parts)
    slot = jnp.where(ok, p_s * capacity + within, n_parts * capacity)  # OOB -> dropped
    buf = jnp.zeros((n_parts * capacity, w), records.dtype)
    buf = buf.at[slot].set(rec_s, mode="drop")
    overflow = jnp.sum((~ok) & (p_s < n_parts))
    return buf.reshape(n_parts, capacity, w), overflow


def shard_rows(x, mesh, axis_name: str) -> jax.Array:
    """Place a host array [P, ...] on the mesh: row p on the p-th device of
    ``axis_name``, never the whole array on one device first."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.device_put(np.asarray(x),
                          NamedSharding(mesh, PartitionSpec(axis_name)))


def exchange(buffer: jax.Array, axis_name: str) -> jax.Array:
    """all_to_all the bucket buffer: leading dim indexes destination before, source
    after.  Returns local records [n_parts * capacity, W]."""
    out = jax.lax.all_to_all(buffer, axis_name, split_axis=0, concat_axis=0)
    return out.reshape(-1, buffer.shape[-1])


def shuffle(records: jax.Array, keys: jax.Array, valid: jax.Array, *, axis_name: str,
            n_parts: int, capacity: int,
            reduce_overflow: bool = True) -> tuple[jax.Array, jax.Array]:
    """Full map-side shuffle step inside ``shard_map``: partition + bucket + exchange.

    Returns (local_records [n_parts*capacity, W], global_overflow scalar).
    ``reduce_overflow=False`` skips the overflow ``psum`` and returns the
    *local* overflow count instead -- the fused multi-round wave program sums
    every round's local count and runs one ``psum`` per wave, not one per
    round (the caller owns the reduction).
    """
    part = partition_ids(keys, valid, n_parts)
    buf, overflow = bucketize(records, part, n_parts, capacity)
    out = exchange(buf, axis_name)
    if reduce_overflow:
        overflow = jax.lax.psum(overflow, axis_name)
    return out, overflow
