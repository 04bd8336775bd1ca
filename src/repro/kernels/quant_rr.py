"""Pallas TPU kernel: fused multi-level random-rounding quantization.

This is the per-step hot loop of Algorithm 2: every gradient element is
mapped to a level index (interval search + unbiased random rounding, Eq. 7).
On GPU this is a searchsorted + bernoulli; the TPU-native formulation here is
branch/gather-free — the small level table (s ≤ 17, padded to a 32-lane tile)
is kept resident in VMEM and the interval search is an unrolled
compare-accumulate over levels, which maps onto the VPU as dense vector ops.

Tiling: grid over row-blocks of buckets; each step processes an
(ROW_BLOCK, d) value tile (d = bucket size, a multiple of 128 in practice)
plus the matching (ROW_BLOCK, LEVEL_PAD) level tile. Random bits are
precomputed threefry uint32 (bit-identical between interpret mode, TPU, and
the jnp oracle in ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import uniform_from_bits

ROW_BLOCK = 8
LEVEL_PAD = 32  # level-table tile width (s <= 17 always)


def _quant_rr_kernel(s: int, v_ref, lv_ref, bits_ref, idx_ref):
    v = v_ref[...].astype(jnp.float32)          # (R, d)
    lv = lv_ref[...].astype(jnp.float32)        # (R, LEVEL_PAD)
    u = uniform_from_bits(bits_ref[...])

    # interval search: k = (#levels <= v) - 1, clipped to [0, s-2]
    k = jnp.zeros(v.shape, dtype=jnp.int32)
    for j in range(s):                           # static unroll, s <= 17
        lj = lv[:, j][:, None]
        k = k + (v >= lj).astype(jnp.int32)
    k = jnp.clip(k - 1, 0, s - 2)
    # lo = levels[k], hi = levels[k+1] via one-hot select (gather-free)
    lo = jnp.zeros(v.shape, dtype=jnp.float32)
    hi = jnp.zeros(v.shape, dtype=jnp.float32)
    for j in range(s - 1):                       # static unroll
        sel = (k == j).astype(jnp.float32)
        lo = lo + sel * lv[:, j][:, None]
        hi = hi + sel * lv[:, j + 1][:, None]

    vc = jnp.clip(v, lo, hi)
    width = hi - lo
    p_up = jnp.where(width > 0, (vc - lo) / jnp.where(width > 0, width, 1.0),
                     0.0)
    up = (u < p_up).astype(jnp.int32)
    idx_ref[...] = k + up


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def quant_rr(v: jnp.ndarray, levels: jnp.ndarray, bits: jnp.ndarray,
             *, s: int, interpret: bool = True) -> jnp.ndarray:
    """(nb, d) values + (nb, s) levels + (nb, d) uint32 bits -> (nb, d) int32.

    Rows are padded to ROW_BLOCK; the level table is padded to LEVEL_PAD
    lanes (padding lanes replicate the top level so the unrolled compare
    never reads garbage).
    """
    nb, d = v.shape
    assert levels.shape == (nb, s) and bits.shape == (nb, d)
    rows = -(-nb // ROW_BLOCK) * ROW_BLOCK
    pad_r = rows - nb
    vp = jnp.pad(v.astype(jnp.float32), ((0, pad_r), (0, 0)))
    bp = jnp.pad(bits, ((0, pad_r), (0, 0)))
    lvp = jnp.pad(levels.astype(jnp.float32), ((0, pad_r), (0, LEVEL_PAD - s)),
                  mode="edge")
    grid = (rows // ROW_BLOCK,)
    out = pl.pallas_call(
        functools.partial(_quant_rr_kernel, s),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROW_BLOCK, d), lambda i: (i, 0)),
            pl.BlockSpec((ROW_BLOCK, LEVEL_PAD), lambda i: (i, 0)),
            pl.BlockSpec((ROW_BLOCK, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROW_BLOCK, d), lambda i: (i, 0)),
        interpret=interpret,
    )(vp, lvp, bp)
    return out[:nb]
