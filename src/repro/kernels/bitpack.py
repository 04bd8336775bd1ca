"""Pallas TPU kernel: bit-pack level indices into uint32 wire words.

Packs ``epw = 32 // bits`` indices into each uint32 word via shift-add
(disjoint bit ranges, so addition == OR — avoids any reliance on integer
OR reductions) in the wire's slice layout: column c sits in word
``c % nw`` at bit offset ``bits * (c // nw)``. Unpack is the mirror
shift-mask. These run just before/after the all_to_all so the wire
payload is the packed words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_encode import _pack_words

ROW_BLOCK = 8


def _pack_kernel(bits: int, epw: int, idx_ref, out_ref):
    out_ref[...] = _pack_words(idx_ref[...], bits, epw)   # (R, nw*epw)


def _unpack_kernel(bits: int, epw: int, w_ref, out_ref):
    w = w_ref[...]                                  # (R, nw)
    mask = jnp.uint32(2 ** bits - 1)
    parts = []
    for j in range(epw):                            # static unroll
        parts.append(((w >> jnp.uint32(bits * j)) & mask).astype(jnp.int32))
    out_ref[...] = jnp.concatenate(parts, axis=-1)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def pack(idx: jnp.ndarray, *, bits: int, interpret: bool = True) -> jnp.ndarray:
    """(nb, d) int32 -> (nb, nw) uint32, nw = ceil(d / (32//bits))."""
    nb, d = idx.shape
    epw = 32 // bits
    nw = -(-d // epw)
    rows = -(-nb // ROW_BLOCK) * ROW_BLOCK
    ip = jnp.pad(idx, ((0, rows - nb), (0, nw * epw - d)))
    out = pl.pallas_call(
        functools.partial(_pack_kernel, bits, epw),
        out_shape=jax.ShapeDtypeStruct((rows, nw), jnp.uint32),
        grid=(rows // ROW_BLOCK,),
        in_specs=[pl.BlockSpec((ROW_BLOCK, nw * epw), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROW_BLOCK, nw), lambda i: (i, 0)),
        interpret=interpret,
    )(ip)
    return out[:nb]


@functools.partial(jax.jit, static_argnames=("bits", "d", "interpret"))
def unpack(words: jnp.ndarray, *, bits: int, d: int,
           interpret: bool = True) -> jnp.ndarray:
    """(nb, nw) uint32 -> (nb, d) int32."""
    nb, nw = words.shape
    epw = 32 // bits
    rows = -(-nb // ROW_BLOCK) * ROW_BLOCK
    wp = jnp.pad(words, ((0, rows - nb), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_unpack_kernel, bits, epw),
        out_shape=jax.ShapeDtypeStruct((rows, nw * epw), jnp.int32),
        grid=(rows // ROW_BLOCK,),
        in_specs=[pl.BlockSpec((ROW_BLOCK, nw), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROW_BLOCK, nw * epw), lambda i: (i, 0)),
        interpret=interpret,
    )(wp)
    return out[:nb, :d]
