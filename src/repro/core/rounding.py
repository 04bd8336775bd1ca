"""Rounding rules mapping gradient values to level indices.

* ``random_round`` — unbiased random rounding (Eq. 7): v in [b_{k-1}, b_k]
  goes up with probability (v − b_{k-1})/(b_k − b_{k-1}). Values outside the
  level range are clipped to the end levels first (for ORQ the ends are the
  bucket min/max so nothing clips; for BinGrad-pb this clip IS the partially
  biased part of Eq. 14).
* ``nearest_round`` / ``threshold_round`` — deterministic rules (BinGrad-b
  Eq. 16, scaled SignSGD).

Uniform randomness is supplied as uint32 counter-based bits from
``jax.random`` so CPU (interpret-mode) and TPU runs are bit-identical; the
Pallas kernels consume the same bits (see kernels/quant_rr.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# uint32 -> [0, 1): the single rule the kernels and the oracle share
from repro.kernels.ref import uniform_from_bits


def find_interval(bkt: jnp.ndarray, levels: jnp.ndarray) -> jnp.ndarray:
    """Index k of the *lower* level of v's interval: levels[k] <= v < levels[k+1].

    bkt (nb, d), levels (nb, s) ascending -> (nb, d) int32 in [0, s-2].
    Values below levels[0] map to 0; above levels[-1] map to s-2 (they are
    clipped by the rounding probability computation).

    Computed as a static unrolled compare-accumulate over the s levels
    (s <= 17), matching the Pallas kernel formulation — an (nb, d, s)
    broadcast would dominate peak memory on multi-billion-element leaves.
    """
    v = bkt.astype(jnp.float32)
    s = levels.shape[-1]
    lv = levels.astype(jnp.float32)
    k = jnp.zeros(v.shape, dtype=jnp.int32)
    for j in range(s):
        k = k + (v >= lv[:, j][:, None]).astype(jnp.int32)
    return jnp.clip(k - 1, 0, s - 2)


def select_levels(levels: jnp.ndarray, k: jnp.ndarray):
    """(lo, hi) = (levels[k], levels[k+1]) via one-hot accumulate (gather-
    free, matches the kernel; avoids take_along_axis relayouts on sharded
    operands)."""
    s = levels.shape[-1]
    lv = levels.astype(jnp.float32)
    lo = jnp.zeros(k.shape, jnp.float32)
    hi = jnp.zeros(k.shape, jnp.float32)
    for j in range(s - 1):
        sel = (k == j).astype(jnp.float32)
        lo = lo + sel * lv[:, j][:, None]
        hi = hi + sel * lv[:, j + 1][:, None]
    return lo, hi


def random_round(
    bkt: jnp.ndarray,
    levels: jnp.ndarray,
    bits: jnp.ndarray,
) -> jnp.ndarray:
    """Unbiased random rounding to level indices. Returns (nb, d) int32 idx."""
    k = find_interval(bkt, levels)
    lo, hi = select_levels(levels, k)
    v = jnp.clip(bkt.astype(jnp.float32), lo, hi)
    width = hi - lo
    p_up = jnp.where(width > 0, (v - lo) / jnp.where(width > 0, width, 1.0), 0.0)
    up = (uniform_from_bits(bits) < p_up).astype(jnp.int32)
    return k + up


def nearest_round(bkt: jnp.ndarray, levels: jnp.ndarray) -> jnp.ndarray:
    """Deterministic nearest-level rounding (midpoint thresholds)."""
    k = find_interval(bkt, levels)
    lo, hi = select_levels(levels, k)
    v = jnp.clip(bkt.astype(jnp.float32), lo, hi)
    up = (v - lo > hi - v).astype(jnp.int32)
    return k + up


def threshold_round(bkt: jnp.ndarray, b0: jnp.ndarray) -> jnp.ndarray:
    """Binary deterministic rule (Eq. 16): idx = 1 iff v >= b0. b0: (nb, 1)."""
    return (bkt.astype(jnp.float32) >= b0).astype(jnp.int32)


def dequantize(idx: jnp.ndarray, levels: jnp.ndarray) -> jnp.ndarray:
    """Level indices back to values: (nb, d) idx + (nb, s) levels -> (nb, d)."""
    return jnp.take_along_axis(levels, idx.astype(jnp.int32), axis=-1)


def random_bits(key: jax.Array, shape) -> jnp.ndarray:
    """Counter-based uint32 bits for the rounding decision."""
    return jax.random.bits(key, shape, dtype=jnp.uint32)
