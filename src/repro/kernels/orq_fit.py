"""Pallas TPU kernel: the ORQ level fit (Algorithm 1) without a sort.

The fit used to sort every bucket, take a prefix sum of the sorted copy
and gather from both: on a TPU v5e that cost some 170 reads' worth of
time for one read of its input. Nothing in the solve needs sorted data
(``levels.solve_midpoint``): the counts and the sum over an interval are
masked reductions, the endpoints are a min and a max, and the new level,
the j-th smallest value of the bucket, is found by bisection on the
order-preserving int32 key. So one VMEM-tiled sweep runs all of
Algorithm 1 on a block of bucket rows; the only HBM write is the
``(nb, 2^K + 1)`` level table.

The kernel body is ``levels.orq_levels_block`` itself, called on the
tile, and the jnp oracle (``levels.orq_levels``) calls it on the whole
array with its rows padded to the same lane tiles: interpret mode is
bit-identical to the oracle by construction.

The row block follows ``fused_encode.row_block``: as many bucket rows per
grid step as the operand tiles (values, mask, levels) fit in the VMEM
tile budget, 120 rows of 2,048. The rows' bisections are independent
work for each step of the loop: on a TPU v5e the 209,666-bucket orq-9 fit
took 105 ms at 56 rows, 89 ms at 120 and 87 ms at 248. Ragged rows and
lanes are padded with the mask false.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.levels import LANES, orq_levels_block
from repro.kernels.fused_encode import row_block


def _orq_fit_kernel(K, refine_iters, v_ref, m_ref, o_ref):
    o_ref[...] = orq_levels_block(v_ref[...], m_ref[...] > 0, K,
                                  refine_iters=refine_iters)


@functools.partial(jax.jit,
                   static_argnames=("K", "refine_iters", "interpret"))
def orq_fit(v: jnp.ndarray, mask: jnp.ndarray, *, K: int,
            refine_iters: int = 0, interpret: bool = True) -> jnp.ndarray:
    """(nb, d) values + (nb, d) mask -> (nb, 2^K + 1) f32 ORQ levels, in
    one ``pallas_call`` named ``orq_fit``."""
    nb, d = v.shape
    s = 2 ** K + 1
    dp = -(-d // LANES) * LANES
    rb = row_block(nb, 4 * (2 * dp + s))         # v and mask in, levels out
    rows = -(-nb // rb) * rb
    pad = ((0, rows - nb), (0, dp - d))
    vp = jnp.pad(v.astype(jnp.float32), pad)
    mp = jnp.pad(mask.astype(jnp.float32), pad)
    out = pl.pallas_call(
        functools.partial(_orq_fit_kernel, K, refine_iters),
        out_shape=jax.ShapeDtypeStruct((rows, s), jnp.float32),
        grid=(rows // rb,),
        in_specs=[pl.BlockSpec((rb, dp), lambda i: (i, 0)),
                  pl.BlockSpec((rb, dp), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rb, s), lambda i: (i, 0)),
        interpret=interpret,
        name="orq_fit",
    )(vp, mp)
    return out[:nb]
