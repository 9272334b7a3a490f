"""Jit'd public wrappers around the Pallas kernels.

On a TPU the kernels compile to Mosaic; on any other backend they run in
Pallas interpret mode.  The choice is made per call, from the backend the
call runs on, so a kernel the TPU compiler refuses fails loudly there.
"""
from __future__ import annotations

import jax

from .block_decode import block_decode as _block_decode
from .block_expand import block_expand as _block_expand
from .bsearch import bsearch as _bsearch
from .hash_combine import hash_combine as _hash_combine
from .hash_partition import hash_partition as _hash_partition
from .lcp_boundary import lcp_boundary as _lcp_boundary
from .merge_path import merge_path as _merge_path
from .suffix_pack import suffix_pack as _suffix_pack

def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def lcp_boundary(sorted_terms, *, block_rows: int = 512):
    return _lcp_boundary(sorted_terms, block_rows=block_rows,
                         interpret=_interpret())


def bsearch(lanes, queries, lo, hi, *, upper: bool = False,
            steps: int | None = None, block: int = 1024):
    return _bsearch(lanes, queries, lo, hi, upper=upper, steps=steps,
                    block=block, interpret=_interpret())


def suffix_pack(tokens, *, sigma: int, vocab_size: int, block: int = 1024):
    return _suffix_pack(tokens, sigma=sigma, vocab_size=vocab_size, block=block,
                        interpret=_interpret())


def hash_partition(keys, valid, *, n_parts: int, block: int = 4096):
    return _hash_partition(keys, valid, n_parts=n_parts, block=block,
                           interpret=_interpret())


def hash_combine(keys, weights, *, block: int = 256):
    return _hash_combine(keys, weights, block=block, interpret=_interpret())


def merge_path(a_keys, b_keys, a_vals, b_vals, *, block: int = 1024):
    return _merge_path(a_keys, b_keys, a_vals, b_vals, block=block,
                       interpret=_interpret())


def block_decode(lcps, payload, block_base, sec_starts, blk, q_terms, q_len, *,
                 term_bits: int, lcp_width: int, block_size: int, len_off: int,
                 qblock: int = 256):
    return _block_decode(lcps, payload, block_base, sec_starts, blk, q_terms,
                         q_len, term_bits=term_bits, lcp_width=lcp_width,
                         block_size=block_size, len_off=len_off, qblock=qblock,
                         interpret=_interpret())


def block_expand(lcps, payload, block_base, sec_starts, blk, *, sigma: int,
                 term_bits: int, lcp_width: int, block_size: int, len_off: int,
                 bblock: int = 256):
    return _block_expand(lcps, payload, block_base, sec_starts, blk,
                         sigma=sigma, term_bits=term_bits, lcp_width=lcp_width,
                         block_size=block_size, len_off=len_off, bblock=bblock,
                         interpret=_interpret())
