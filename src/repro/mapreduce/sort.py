"""Packed multi-key lexicographic sort -- the MapReduce "sort by key" phase.

Hadoop sorts map outputs with a user comparator (the paper supplies a
reverse-lexicographic one so the streaming reducer can emit early).  The parallel
reducer (``repro.mapreduce.segment``) only needs *contiguity* of equal prefixes, which
any lexicographic order gives, so we use plain ascending order on the packed lanes:
``jax.lax.sort`` with ``num_keys = n_lanes`` performs a lexicographic sort in
``n_lanes`` passes -- bit packing (``repro.mapreduce.pack``) is what keeps that pass
count low (the beyond-paper optimization logged in EXPERIMENTS.md SSPerf).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# The TPU compiler's time for one ``lax.sort`` grows about quadratically with
# its operands (a described v5e: 2.5 s at 6 operands, 51 s at 34), so a sort
# of more operands than this runs as passes over a permutation instead.
_SORT_OPERANDS = 16
# keys compared by each of those passes
_KEYS_PER_PASS = 8


def lex_permutation(keys: list[jax.Array]) -> jax.Array:
    """int32 permutation that orders rows lexicographically by ``keys``
    (equal-length columns, most significant first).

    Least significant group first, each pass stable-sorts the permutation by
    ``_KEYS_PER_PASS`` key columns gathered through it, so the last pass
    leaves rows in lexicographic order.  The passes run in one loop, so the
    program holds a single sort of ``_KEYS_PER_PASS + 1`` operands whatever
    the key count."""
    n = keys[0].shape[0]
    k = _KEYS_PER_PASS
    iota = jnp.arange(n, dtype=jnp.int32)
    if len(keys) <= k:
        return jax.lax.sort(list(keys) + [iota], num_keys=len(keys),
                            is_stable=True)[-1]
    groups = -(-len(keys) // k)
    pad = [jnp.zeros_like(keys[0])] * (groups * k - len(keys))
    stacked = jnp.stack(keys + pad).reshape(groups, k, n)

    def one_pass(i, perm):
        cols = stacked[groups - 1 - i][:, perm]
        return jax.lax.sort([cols[j] for j in range(k)] + [perm],
                            num_keys=k, is_stable=True)[-1]

    return jax.lax.fori_loop(0, groups, one_pass, iota)


def sort_columns(cols: list[jax.Array], num_keys: int) -> list[jax.Array]:
    """``lax.sort`` of equal-length columns by their first ``num_keys``.

    At most ``_SORT_OPERANDS`` columns sort in one ``lax.sort``; more are
    gathered through :func:`lex_permutation` of the keys (stable)."""
    if len(cols) <= _SORT_OPERANDS:
        return list(jax.lax.sort(cols, num_keys=num_keys, is_stable=False))
    perm = lex_permutation(list(cols[:num_keys]))
    return [c[perm] for c in cols]


def sort_records(records: jax.Array, n_keys: int) -> jax.Array:
    """Sort record rows [N, W] lexicographically by their first ``n_keys`` lanes.

    The remaining lanes (weight / meta) ride along.  Stable order among equal keys is
    irrelevant for counting.
    """
    n, w = records.shape
    if w > _SORT_OPERANDS:
        return records[lex_permutation([records[:, i]
                                        for i in range(n_keys)])]
    cols = [records[:, i] for i in range(w)]
    out = jax.lax.sort(cols, num_keys=n_keys, is_stable=False)
    return jnp.stack(out, axis=1)


def sort_with_payload(keys: jax.Array, payloads: list[jax.Array]) -> tuple[jax.Array, list[jax.Array]]:
    """Sort [N, K] key matrix lexicographically, carrying payload arrays [N, ...]."""
    n, k = keys.shape
    cols = [keys[:, i] for i in range(k)]
    out = jax.lax.sort(cols + list(payloads), num_keys=k, is_stable=False)
    return jnp.stack(out[:k], axis=1), list(out[k:])
