"""Pallas TPU kernel: fully-fused BinGrad-b ENCODE (b₀ search + pack).

BinGrad-b's level fit is moments-only — b₀ = mean(G), then the
conditional means below/above b₀ (Eq. 17), optionally iterated to the
2-means fixed point — so unlike ORQ (whose seven bisections per bucket
run as a kernel of their own, ``orq_fit.py``) the WHOLE scheme fuses: one VMEM-tiled sweep computes the b₀ search, the
(b₋₁, b₁) level table, the threshold assignment at the level midpoint,
and the 1-bit pack. The gradient tile is read from HBM once; the only
writes are the packed (nb, nw) uint32 words and the tiny (nb, 2) level
table that rides the wire next to them.

This replaces what used to be ≥4 sweeps (masked moments, two conditional
reductions, threshold compare, pack) each materializing (nb, d)
intermediates. Numerics mirror ``levels.bingrad_b_levels`` +
``rounding.threshold_round`` term for term (interpret mode is
bit-identical to the jnp oracle ``ref.encode_bingrad_fused_ref``).

Scheduling follows ``fused_encode``: the optional σ-clip REDUCTION runs
once outside the kernel (the (nb, 1) c·σ limit rides in as a side
input), and the row block adapts so small sweeps run as one grid step
within the VMEM tile budget.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_encode import _pack_words, clip_limit, row_block

ROW_BLOCK = 8  # row-block quantum (see fused_encode.row_block)
_EPW = 32      # 1 bit per element -> 32 elements per uint32 word


def _bingrad_encode_kernel(lloyd_iters, has_lim, *refs):
    if has_lim:
        v_ref, m_ref, lim_ref, w_ref, lv_ref = refs
    else:
        v_ref, m_ref, w_ref, lv_ref = refs
    v = v_ref[...].astype(jnp.float32)        # (R, d)
    m = m_ref[...].astype(jnp.float32)        # (R, d) validity
    if has_lim:
        lim = lim_ref[...]
        v = jnp.clip(v, -lim, lim)

    cnt = jnp.maximum(m.sum(axis=-1, keepdims=True), 1.0)
    b0 = (v * m).sum(axis=-1, keepdims=True) / cnt      # paper: b₀ = mean(G)

    def cond_means(b0):
        lo = m * (v < b0)
        hi = m * (v >= b0)
        cl = lo.sum(axis=-1, keepdims=True)
        ch = hi.sum(axis=-1, keepdims=True)
        bm = (v * lo).sum(axis=-1, keepdims=True) / jnp.maximum(cl, 1.0)
        bp = (v * hi).sum(axis=-1, keepdims=True) / jnp.maximum(ch, 1.0)
        # empty side: collapse to the other side's mean (degenerate bucket)
        bm = jnp.where(cl > 0, bm, bp)
        bp = jnp.where(ch > 0, bp, bm)
        return bm, bp

    bm, bp = cond_means(b0)
    for _ in range(lloyd_iters):                 # static unroll
        b0 = 0.5 * (bm + bp)
        bm, bp = cond_means(b0)

    thr = 0.5 * (bm + bp)                        # Eq. (17): midpoint rule
    idx = jnp.where(m > 0, (v >= thr).astype(jnp.int32), 0)
    w_ref[...] = _pack_words(idx, 1, _EPW)
    lv_ref[...] = jnp.concatenate([bm, bp], axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("clip_c", "lloyd_iters", "interpret"))
def encode_bingrad_fused(v: jnp.ndarray, mask: jnp.ndarray, *,
                         clip_c: Optional[float] = None,
                         lloyd_iters: int = 0, interpret: bool = True):
    """(nb, d) values + (nb, d) mask -> ((nb, nw) uint32 words,
    (nb, 2) float32 levels), nw = ceil(d / 32). One pallas_call. Columns
    stay at the true bucket width — the moment reductions must sum over
    exactly the elements the jnp oracle sums (``_pack_words`` zero-pads
    the ragged tail in-register)."""
    nb, d = v.shape
    nw = -(-d // _EPW)
    lim = clip_limit(v, mask, clip_c)
    row_bytes = 4 * (3 * d + nw + 2 + (1 if lim is not None else 0))
    rb = row_block(nb, row_bytes)
    rows = -(-nb // rb) * rb
    pr = rows - nb
    vp = jnp.pad(v.astype(jnp.float32), ((0, pr), (0, 0)))
    mp = jnp.pad(mask.astype(jnp.float32), ((0, pr), (0, 0)))
    inputs = [vp, mp]
    in_specs = [
        pl.BlockSpec((rb, d), lambda i: (i, 0)),
        pl.BlockSpec((rb, d), lambda i: (i, 0)),
    ]
    if lim is not None:
        inputs.append(jnp.pad(lim.astype(jnp.float32), ((0, pr), (0, 0))))
        in_specs.append(pl.BlockSpec((rb, 1), lambda i: (i, 0)))
    words, lv = pl.pallas_call(
        functools.partial(_bingrad_encode_kernel, lloyd_iters,
                          lim is not None),
        out_shape=(
            jax.ShapeDtypeStruct((rows, nw), jnp.uint32),
            jax.ShapeDtypeStruct((rows, 2), jnp.float32),
        ),
        grid=(rows // rb,),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((rb, nw), lambda i: (i, 0)),
            pl.BlockSpec((rb, 2), lambda i: (i, 0)),
        ),
        interpret=interpret,
    )(*inputs)
    return words[:nb], lv[:nb]
