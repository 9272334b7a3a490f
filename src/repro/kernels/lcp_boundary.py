"""Fused LCP + boundary-flag kernel -- the SUFFIX-sigma reducer inner loop.

For a sorted [N, L] term matrix the reducer needs, per row, the longest common
prefix with the previous row and per-length boundary flags.  XLA emits this as
roll + compare + cumprod + reduce + broadcast-compare (5 HBM-bound passes over the
matrix); the kernel reads each row block once into VMEM and produces both outputs in
a single pass -- the classic memory-bound fusion case (arithmetic intensity ~1 flop/B).

TPU mapping: rows tile the grid; L (<= sigma, e.g. 5..100) rides in lanes.  The
previous-row halo is passed as a second, pre-shifted input ref (Pallas BlockSpecs are
block-aligned; a one-row halo would force element offsets), which costs one extra HBM
read of the matrix but keeps every block independent.  Block rows default to 512 so a
block of sigma=100 int32 terms is ~200 KiB -- comfortably inside the ~16 MiB VMEM
budget with double buffering.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(cur_ref, prev_ref, lcp_ref, flags_ref):
    cur = cur_ref[...]
    prev = prev_ref[...]
    length = cur.shape[1]
    # iota, not arange: arange traces to a materialized constant, which
    # pallas_call rejects ("captures constants ... pass them as inputs")
    cols = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1)
    # the LCP is the first column where the rows differ (length if none);
    # a min over columns, since cumprod has no Mosaic lowering
    lcp = jnp.min(jnp.where(cur == prev, length, cols), axis=1)
    lcp_ref[...] = lcp[None, :]
    flags_ref[...] = (lcp[:, None] <= cols) & (cur != 0)


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def lcp_boundary(sorted_terms: jax.Array, *, block_rows: int = 512,
                 interpret: bool = True) -> tuple[jax.Array, jax.Array]:
    """(lcp [N] int32, flags [N, L] bool).  Row 0 gets lcp 0 (no predecessor)."""
    n, length = sorted_terms.shape
    nb = -(-n // block_rows)
    n_pad = nb * block_rows
    st = jnp.pad(sorted_terms, ((0, n_pad - n), (0, 0)))
    # pre-shifted previous-row matrix; row 0's "previous" is a sentinel that cannot
    # match any real row (forces lcp 0 without an in-kernel special case).
    prev = jnp.concatenate(
        [jnp.full((1, length), -2147483648, st.dtype), st[:-1]], axis=0)

    lcp, flags = pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, length), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, length), lambda i: (i, 0)),
        ],
        # lcp rides as one [1, block_rows] row per block: a 1-D block must
        # match XLA's 1-D tiling (1024 on a TPU), a row need not
        out_specs=[
            pl.BlockSpec((None, 1, block_rows), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_rows, length), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, block_rows), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, length), jnp.bool_),
        ],
        interpret=interpret,
    )(st, prev)
    return lcp.reshape(n_pad)[:n], flags[:n]
