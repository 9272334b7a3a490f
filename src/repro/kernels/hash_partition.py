"""Fused hash + partition-id + histogram kernel -- the shuffle partitioner.

Computes each record's reducer (multiplicative hash of the lead term mod P) and the
per-partition record histogram in one pass.  The histogram is what sizes the
all_to_all capacity check; fusing it with the hash avoids a second HBM pass and a
one-hot materialization ([N, P] ints in XLA's unfused form).

Each grid block writes its own [1, P] histogram row (the full trailing dims of
a [nb, 1, P] output, as the TPU's (8, 128) block tiling rule requires); the
caller sums rows (a [nb, P] reduction -- negligible next to the [N] pass).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _make_kernel(n_parts: int):
    def kernel(keys_ref, valid_ref, part_ref, hist_ref):
        k = keys_ref[...].astype(jnp.uint32)
        h = k * jnp.uint32(2654435761)
        h = h ^ (h >> 15)
        h = h * jnp.uint32(2246822519)
        h = h ^ (h >> 13)
        p = (h % jnp.uint32(n_parts)).astype(jnp.int32)
        p = jnp.where(valid_ref[...], p, n_parts)
        part_ref[...] = p
        # iota, not arange (arange would become a captured constant -- rejected)
        ids = jax.lax.broadcasted_iota(jnp.int32, (1, n_parts), 1)
        hist_ref[...] = jnp.sum((p[:, None] == ids).astype(jnp.int32),
                                axis=0, keepdims=True)

    return kernel


@partial(jax.jit, static_argnames=("n_parts", "block", "interpret"))
def hash_partition(keys: jax.Array, valid: jax.Array, *, n_parts: int,
                   block: int = 4096, interpret: bool = True
                   ) -> tuple[jax.Array, jax.Array]:
    """(partition ids [N] int32 -- n_parts marks invalid, histogram [n_parts])."""
    n = keys.shape[0]
    nb = -(-n // block)
    n_pad = nb * block
    k = jnp.pad(keys.astype(jnp.uint32), (0, n_pad - n))
    v = jnp.pad(valid, (0, n_pad - n))  # padding rows invalid -> drop bucket

    part, hist = pl.pallas_call(
        _make_kernel(n_parts),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((None, 1, n_parts), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad,), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1, n_parts), jnp.int32),
        ],
        interpret=interpret,
    )(k, v)
    return part[:n], jnp.sum(hist, axis=(0, 1))
