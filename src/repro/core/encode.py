"""Wire encoding: bit-packing level indices into uint32 words.

s levels need ceil(log2(s)) bits per element. The paper reports
information-theoretic ratios (32/log2(s), e.g. x20.2 for 3 levels); the wire
format here packs whole bits (e.g. 2 bits for 3 levels). ``wire_bits`` returns
both accountings so benchmarks can report the paper's ratio alongside the
achievable packed one.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax.numpy as jnp


def bits_for_levels(s: int) -> int:
    return max(1, math.ceil(math.log2(s)))


def elems_per_word(bits: int) -> int:
    return 32 // bits


def packed_words(d: int, bits: int) -> int:
    epw = elems_per_word(bits)
    return -(-d // epw)


def pack(idx: jnp.ndarray, bits: int) -> jnp.ndarray:
    """(nb, d) int32 indices in [0, 2^bits) -> (nb, nw) uint32 words.

    Slice layout: column c goes to word ``c % nw`` at bit offset
    ``bits * (c // nw)`` — bit field j of every word holds the contiguous
    column slice ``[j*nw, (j+1)*nw)`` (zero-padded past d)."""
    nb, d = idx.shape
    epw = elems_per_word(bits)
    nw = packed_words(d, bits)
    padded = jnp.pad(idx.astype(jnp.uint32), ((0, 0), (0, nw * epw - d)))
    fields = padded.reshape(nb, epw, nw)
    shifts = (jnp.arange(epw, dtype=jnp.uint32) * jnp.uint32(bits))[None, :, None]
    # disjoint bit ranges: addition == bitwise OR
    return (fields << shifts).sum(axis=1, dtype=jnp.uint32)


def unpack(words: jnp.ndarray, bits: int, d: int) -> jnp.ndarray:
    """(nb, nw) uint32 -> (nb, d) int32 indices (inverse of :func:`pack`)."""
    nb, nw = words.shape
    epw = elems_per_word(bits)
    shifts = (jnp.arange(epw, dtype=jnp.uint32) * jnp.uint32(bits))[None, :, None]
    mask = jnp.uint32(2 ** bits - 1)
    fields = (words[:, None, :] >> shifts) & mask
    return fields.reshape(nb, epw * nw)[:, :d].astype(jnp.int32)


def wire_bits(n_elems: int, n_buckets: int, s: int) -> Tuple[float, float]:
    """(paper information-theoretic bits, packed wire bits) for a tensor,
    including the per-bucket level-table overhead (s float32 values)."""
    overhead = n_buckets * s * 32
    info = n_elems * math.log2(s) + overhead
    packed = packed_words(n_elems // max(n_buckets, 1) if n_buckets else n_elems,
                          bits_for_levels(s)) * n_buckets * 32 + overhead
    return info, float(packed)
