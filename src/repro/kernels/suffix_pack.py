"""Fused suffix-window + bit-pack kernel -- the SUFFIX-sigma map emit.

The map phase turns a token block [B] into packed suffix lanes [B, n_lanes]:
window gather (sigma shifted copies), PAD masking (cumulative AND after the first
separator), and most-significant-first bit packing.  Unfused, XLA materializes the
[B, sigma] window matrix in HBM (sigma x write amplification); the kernel keeps the
window in VREGs and writes only the packed lanes (e.g. sigma=5 packed into 2 lanes:
2.5x less HBM traffic on the hot path).

Halo handling: windows starting near the block end read into the next block, so the
kernel gets the *next* token block as a second ref (index_map i -> i+1, with the
caller appending one all-PAD block so the clamp at the last block is harmless).
Both blocks are copied into one VMEM scratch row, and window j is a static
``pl.ds`` load from it (Mosaic lowers ref slices, not ``dynamic_slice``).  Lanes
are written as rows of a [n_lanes, block] output block and transposed outside.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.mapreduce import pack as packing


def _make_kernel(sigma: int, vocab_size: int, block: int):
    bits = packing.bits_for_vocab(vocab_size)
    per = packing.terms_per_lane(vocab_size)
    lanes = packing.n_lanes(sigma, vocab_size)

    def kernel(cur_ref, nxt_ref, out_ref, both_ref):
        both_ref[pl.ds(0, block)] = cur_ref[...]
        both_ref[pl.ds(block, block)] = nxt_ref[...]
        alive = jnp.ones((block,), jnp.uint32)
        acc = [jnp.zeros((block,), jnp.uint32) for _ in range(lanes)]
        for j in range(sigma):
            tok = both_ref[pl.ds(j, block)].astype(jnp.uint32)
            alive = alive * (tok != 0).astype(jnp.uint32)  # mask after first PAD
            tok = tok * alive
            lane, slot = divmod(j, per)
            acc[lane] = acc[lane] + (tok << jnp.uint32(bits * (per - 1 - slot)))
        for lane in range(lanes):
            out_ref[lane, :] = acc[lane]

    return kernel


@partial(jax.jit, static_argnames=("sigma", "vocab_size", "block", "interpret"))
def suffix_pack(tokens: jax.Array, *, sigma: int, vocab_size: int, block: int = 1024,
                interpret: bool = True) -> jax.Array:
    """Packed sigma-truncated suffixes [N, n_lanes] of a PAD-separated stream."""
    n = tokens.shape[0]
    nb = -(-n // block)
    n_pad = nb * block
    # one extra all-PAD block so the last block's `next` ref stays in bounds
    toks = jnp.pad(tokens.astype(jnp.int32), (0, n_pad - n + block))
    lanes = packing.n_lanes(sigma, vocab_size)
    if sigma > block:
        raise ValueError("sigma must not exceed the block size")

    out = pl.pallas_call(
        _make_kernel(sigma, vocab_size, block),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i + 1,)),
        ],
        out_specs=pl.BlockSpec((lanes, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((lanes, n_pad), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((2 * block,), jnp.int32)],
        interpret=interpret,
    )(toks, toks)
    return out.T[:n]
