"""Pallas TPU kernel: fused one-pass gradient DECODE.

The wire decode used to be two kernel families with an HBM round-trip in
between: a vmapped ``unpack`` writing full-size (L, nb, d) int32 indices,
then ``dequant_avg`` (or a per-worker dequantize) reading them back. This
module fuses the shift-mask unpack with the gather-free level-table
decode into one VMEM-tiled sweep over the PACKED words — the int32 index
tensor never exists in HBM (a 32/bits traffic shrink on the decode side):

    decode_fused_mean   the 'server' side of Algorithm 2: unpack L
                        workers' payloads, decode, and average, revisiting
                        the output block across the worker grid axis
                        (each payload is read exactly once, the f32 mean
                        written once);
    decode_fused_each   phase 2's deterministic broadcast decode: every
                        worker reconstructs each server's re-quantized
                        chunk -> (L, nb, d) values, no averaging.

Words are in the wire's slice layout (element c in word ``c % nw`` at
bit offset ``bits * (c // nw)``), as in the multi-pass ``bitpack.unpack``
kernel; the one-hot decode matches ``dequant_avg``, so interpret mode is
bit-identical to both the multi-pass kernels and the jnp oracles in
``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_BLOCK = 8
LEVEL_PAD = 32


def _unpack_decode(w: jnp.ndarray, lv: jnp.ndarray, s: int, bits: int,
                   epw: int) -> jnp.ndarray:
    """(1, R, nw) uint32 + (1, R, LEVEL_PAD) levels -> (1, R, nw*epw) f32
    decoded values (shift-mask unpack + one-hot level select, all in VMEM)."""
    mask = jnp.uint32(2 ** bits - 1)
    parts = []
    for j in range(epw):                          # static unroll
        parts.append(((w >> jnp.uint32(bits * j)) & mask).astype(jnp.int32))
    idx = jnp.concatenate(parts, axis=-1)        # element c = j*nw + word
    val = jnp.zeros(idx.shape, dtype=jnp.float32)
    for j in range(s):                  # static unroll, gather-free decode
        val = val + (idx == j).astype(jnp.float32) * lv[:, :, j][:, :, None]
    return val


def _decode_mean_kernel(s, bits, epw, L, w_ref, lv_ref, out_ref):
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    val = _unpack_decode(w_ref[...], lv_ref[...], s, bits, epw)
    out_ref[...] += (val * (1.0 / L))[0]


def _decode_each_kernel(s, bits, epw, w_ref, lv_ref, out_ref):
    out_ref[...] = _unpack_decode(w_ref[...], lv_ref[...], s, bits, epw)


def _pad3(words, levels, s):
    L, nb, _ = words.shape
    rows = -(-nb // ROW_BLOCK) * ROW_BLOCK
    pad = rows - nb
    wp = jnp.pad(words, ((0, 0), (0, pad), (0, 0)))
    lp = jnp.pad(levels.astype(jnp.float32),
                 ((0, 0), (0, pad), (0, LEVEL_PAD - s)))
    return wp, lp, rows


@functools.partial(jax.jit, static_argnames=("d", "bits", "s", "interpret"))
def decode_fused_mean(words: jnp.ndarray, levels: jnp.ndarray, *, d: int,
                      bits: int, s: int, interpret: bool = True):
    """(L, nb, nw) uint32 + (L, nb, s) levels -> (nb, d) f32 mean values.
    One pallas_call; grid (row-block, worker) accumulating in place."""
    L, nb, nw = words.shape
    assert levels.shape == (L, nb, s), (levels.shape, (L, nb, s))
    epw = 32 // bits
    wp, lp, rows = _pad3(words, levels, s)
    out = pl.pallas_call(
        functools.partial(_decode_mean_kernel, s, bits, epw, L),
        out_shape=jax.ShapeDtypeStruct((rows, nw * epw), jnp.float32),
        grid=(rows // ROW_BLOCK, L),
        in_specs=[
            pl.BlockSpec((1, ROW_BLOCK, nw), lambda i, l: (l, i, 0)),
            pl.BlockSpec((1, ROW_BLOCK, LEVEL_PAD), lambda i, l: (l, i, 0)),
        ],
        out_specs=pl.BlockSpec((ROW_BLOCK, nw * epw), lambda i, l: (i, 0)),
        interpret=interpret,
    )(wp, lp)
    return out[:nb, :d]


@functools.partial(jax.jit, static_argnames=("d", "bits", "s", "interpret"))
def decode_fused_each(words: jnp.ndarray, levels: jnp.ndarray, *, d: int,
                      bits: int, s: int, interpret: bool = True):
    """(L, nb, nw) uint32 + (L, nb, s) levels -> (L, nb, d) f32 values
    (no averaging). One pallas_call."""
    L, nb, nw = words.shape
    assert levels.shape == (L, nb, s), (levels.shape, (L, nb, s))
    epw = 32 // bits
    wp, lp, rows = _pad3(words, levels, s)
    out = pl.pallas_call(
        functools.partial(_decode_each_kernel, s, bits, epw),
        out_shape=jax.ShapeDtypeStruct((L, rows, nw * epw), jnp.float32),
        grid=(rows // ROW_BLOCK, L),
        in_specs=[
            pl.BlockSpec((1, ROW_BLOCK, nw), lambda i, l: (l, i, 0)),
            pl.BlockSpec((1, ROW_BLOCK, LEVEL_PAD), lambda i, l: (l, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, ROW_BLOCK, nw * epw),
                               lambda i, l: (l, i, 0)),
        interpret=interpret,
    )(wp, lp)
    return out[:, :nb, :d]
