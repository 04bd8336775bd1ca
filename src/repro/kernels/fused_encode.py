"""Pallas TPU kernel: fused one-pass gradient ENCODE (and local QDQ).

The wire encode used to be 3-4 separate sweeps over the flat buffer —
σ-clip, ``quant_rr`` (its own pallas_call), a masked select, and ``pack``
(another pallas_call) — each materializing a full-size ``(nb, d)``
intermediate in HBM between kernels. This module fuses the whole
per-bucket pipeline into ONE VMEM-tiled sweep:

    encode_fused   σ-clip -> interval search -> random rounding
                   -> mask -> uint32 bit-pack, one ``pallas_call``; the
                   only HBM write is the packed ``(nb, nw)`` wire words
                   (a 32/bits shrink vs the old int32 idx intermediate).
    qdq_fused      the error-feedback hot path: the same clip/round stage
                   followed by an in-register level-table decode — the
                   dequantized ``(nb, d)`` values come straight out, no
                   idx tensor and no pack/unpack round-trip.

Rounding modes (static):
    "rr"    unbiased random rounding (Eq. 7) — orq / terngrad / qsgd /
            linear / minmax2 / bingrad_pb; consumes precomputed threefry
            uint32 bits so the output is bit-identical to the multi-pass
            kernels and the jnp oracle (``ref.encode_fused_ref``).
    "bin"   BinGrad-b threshold at the level midpoint (Eq. 17).
    "sign"  scaled SignSGD threshold at 0 (Eq. 13).

The level FIT for the rr schemes stays outside the kernel: ORQ's Alg. 1
runs as its own sort-free ``pallas_call`` (``orq_fit.py``; its seven
bisections over each bucket cost far more than this sweep), the others
as jnp; the BinGrad-b fit is moments-only and fuses completely — see
``fused_bingrad.py``.

Scheduling (the PR-6 tiling fix):

* The σ-clip REDUCTION runs once, outside the kernel: the per-bucket
  clip limit c·σ is a tiny ``(nb, 1)`` side input computed with the same
  jnp reduction the level fit already performs (XLA CSEs the two), so
  the kernel applies a single ``clip`` instead of re-reducing masked
  moments on every tile. Reduce once, then quantize — not
  reduce-per-block.
* The interval search and the lo/hi neighbour-level selection share one
  unrolled sweep over the (ascending) level table via running selects —
  no second one-hot pass over ``s`` levels.
* The row block adapts to the problem: as many ROW_BLOCK-multiples of
  bucket rows per grid step as fit a VMEM tile budget, so small sweeps
  run as a single grid step instead of paying per-step scheduling
  overhead, while big sweeps still tile within VMEM.

Level tables are padded to a LEVEL_PAD lane tile (edge-replicated so the
unrolled compares never read garbage). Columns stay at the true bucket
width ``d`` and are zero-padded in-register to a whole number of wire
words; the padding is masked so it packs as index 0, exactly like the
zero-pad in the multi-pass ``pack``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import uniform_from_bits

ROW_BLOCK = 8   # row-block quantum (f32 sublane tile)
_LANES = 128    # lane tile
LEVEL_PAD = 32  # level-table tile width (s <= 17 always)
#: VMEM budget per grid-step tile (all operands + outputs). Kept well
#: under the ~16 MB/core VMEM so double-buffered in/out windows fit.
VMEM_TILE_BYTES = 2 * 1024 * 1024

#: rounding modes the fused stage understands
MODES = ("rr", "bin", "sign")


def row_block(nb: int, row_bytes: int) -> int:
    """Rows per grid step: the largest ROW_BLOCK multiple whose tile
    (``row_bytes`` per bucket row across every operand) fits the VMEM
    budget, capped at the padded row count. One grid step whenever the
    whole sweep fits."""
    cap = max(VMEM_TILE_BYTES // max(row_bytes, 1), ROW_BLOCK)
    cap = (cap // ROW_BLOCK) * ROW_BLOCK
    need = -(-nb // ROW_BLOCK) * ROW_BLOCK
    return min(cap, need)


def clip_limit(v: jnp.ndarray, mask: jnp.ndarray,
               clip_c: Optional[float]) -> Optional[jnp.ndarray]:
    """Per-bucket TernGrad clip limit c·σ as an (nb, 1) f32 array (None
    when clipping is off). Mirrors ``clipping.sigma_clip`` term for term
    — the SAME jnp reduction the level fit runs, so inside one jit XLA
    computes it once; the kernels then clip against the precomputed
    limit instead of re-reducing σ per tile."""
    if clip_c is None:
        return None
    m = mask.astype(jnp.float32)
    v = v.astype(jnp.float32)
    cnt = jnp.maximum(m.sum(axis=-1, keepdims=True), 1.0)
    mean = (v * m).sum(axis=-1, keepdims=True) / cnt
    var = (((v - mean) ** 2) * m).sum(axis=-1, keepdims=True) / cnt
    return clip_c * jnp.sqrt(var)


def _clip_round(s: int, mode: str, v: jnp.ndarray, lv: jnp.ndarray,
                m: jnp.ndarray, u: Optional[jnp.ndarray],
                lim: Optional[jnp.ndarray]) -> jnp.ndarray:
    """The shared in-VMEM stage: clip -> round -> mask. All operands are
    (R, d) tiles (lv is (R, LEVEL_PAD), lim is (R, 1) or None); returns
    masked int32 indices.

    Numerics mirror ``clipping.sigma_clip`` + ``rounding.random_round`` /
    ``rounding.threshold_round`` term for term so interpret mode is
    bit-identical to the jnp oracle."""
    if lim is not None:
        v = jnp.clip(v, -lim, lim)
    if mode == "rr":
        # Interval search fused with neighbour-level selection. Level
        # tables are ascending, so (v >= lv_j) is a prefix predicate and
        # the running selects land on exactly levels[k] / levels[k+1]
        # for k = clip(#(levels <= v) - 1, 0, s-2) — the same
        # count-and-gather as ``rounding.find_interval`` +
        # ``select_levels``, in one sweep with no one-hot second pass.
        k = jnp.zeros(v.shape, dtype=jnp.int32)
        lo = jnp.broadcast_to(lv[:, 0][:, None], v.shape)
        hi = jnp.broadcast_to(lv[:, 1][:, None], v.shape)
        ge_prev = None
        for j in range(s):                       # static unroll, s <= 17
            ge = v >= lv[:, j][:, None]
            k = k + ge.astype(jnp.int32)
            if 1 <= j <= s - 2:
                lo = jnp.where(ge, lv[:, j][:, None], lo)
            if j >= 2:
                hi = jnp.where(ge_prev, lv[:, j][:, None], hi)
            ge_prev = ge
        k = jnp.clip(k - 1, 0, s - 2)
        vc = jnp.clip(v, lo, hi)
        width = hi - lo
        p_up = jnp.where(width > 0,
                         (vc - lo) / jnp.where(width > 0, width, 1.0), 0.0)
        idx = k + (u < p_up).astype(jnp.int32)
    elif mode == "bin":
        thr = 0.5 * (lv[:, 0] + lv[:, 1])[:, None]
        idx = (v >= thr).astype(jnp.int32)
    elif mode == "sign":
        idx = (v >= 0.0).astype(jnp.int32)
    else:
        raise ValueError(f"unknown rounding mode {mode!r}")
    return jnp.where(m > 0, idx, 0)


def _pack_words(idx: jnp.ndarray, bits: int, epw: int) -> jnp.ndarray:
    """(R, d) int32 -> (R, nw) uint32, nw = ceil(d/epw), shift-add pack
    (add == OR on disjoint bit ranges) in the wire's slice layout: column
    c goes to word ``c % nw`` at bit offset ``bits * (c // nw)``, so bit
    field j is the column slice ``[j*nw, (j+1)*nw)``. The TPU's Pallas
    compiler refuses the lane-splitting reshape an interleaved layout
    needs, and silently miscompiles lane slices that start off a
    128-lane boundary, so each field is rotated down to lane 0
    (``pltpu.roll``) and sliced from there. The ragged tail is zero-padded
    IN-REGISTER, to a whole number of lane tiles — padding the kernel
    INPUTS instead would widen the row reductions (the BinGrad conditional
    means) and shift their rounding by an ulp vs the jnp oracle."""
    r, d = idx.shape
    nw = -(-d // epw)
    width = -(-nw * epw // _LANES) * _LANES
    if width != d:
        idx = jnp.concatenate(
            [idx, jnp.zeros((r, width - d), dtype=idx.dtype)], axis=-1)
    idx = idx.astype(jnp.uint32)
    acc = idx[:, :nw]
    for j in range(1, epw):                       # static unroll
        field = pltpu.roll(idx, width - j * nw, 1)[:, :nw]
        acc = acc + (field << jnp.uint32(bits * j))
    return acc


def _encode_kernel(s, bits, epw, has_lim, mode, *refs):
    refs = list(refs)
    v_ref, lv_ref, m_ref = refs[:3]
    rest = refs[3:]
    lim = rest.pop(0)[...] if has_lim else None
    if mode == "rr":
        u = uniform_from_bits(rest.pop(0)[...])
    else:
        u = None
    (w_ref,) = rest
    v = v_ref[...].astype(jnp.float32)
    lv = lv_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    idx = _clip_round(s, mode, v, lv, m, u, lim)
    w_ref[...] = _pack_words(idx, bits, epw)


def _qdq_kernel(s, has_lim, mode, *refs):
    refs = list(refs)
    v_ref, lv_ref, m_ref = refs[:3]
    rest = refs[3:]
    lim = rest.pop(0)[...] if has_lim else None
    if mode == "rr":
        u = uniform_from_bits(rest.pop(0)[...])
    else:
        u = None
    (o_ref,) = rest
    v = v_ref[...].astype(jnp.float32)
    lv = lv_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    idx = _clip_round(s, mode, v, lv, m, u, lim)
    val = jnp.zeros(v.shape, dtype=jnp.float32)
    for j in range(s):                  # static unroll, gather-free decode
        val = val + (idx == j).astype(jnp.float32) * lv[:, j][:, None]
    o_ref[...] = val


def _padded(v, levels, bits_arr, mask, lim, *, s: int, mode: str,
            out_cols: int):
    """Pad rows to an adaptive VMEM-budgeted row block and the level
    table to LEVEL_PAD lanes. Columns stay at the true bucket width
    ``d``. Returns (inputs, in_specs, rows, rb)."""
    nb, d = v.shape
    n_wide = 3 if mode == "rr" else 2            # (nb, d)-wide operands + v
    row_bytes = 4 * ((n_wide + 1) * d + LEVEL_PAD + out_cols
                     + (1 if lim is not None else 0))
    rb = row_block(nb, row_bytes)
    rows = -(-nb // rb) * rb
    pr = rows - nb
    vp = jnp.pad(v.astype(jnp.float32), ((0, pr), (0, 0)))
    mp = jnp.pad(mask.astype(jnp.float32), ((0, pr), (0, 0)))
    lvp = jnp.pad(levels.astype(jnp.float32),
                  ((0, pr), (0, LEVEL_PAD - s)), mode="edge")
    inputs = [vp, lvp, mp]
    in_specs = [
        pl.BlockSpec((rb, d), lambda i: (i, 0)),
        pl.BlockSpec((rb, LEVEL_PAD), lambda i: (i, 0)),
        pl.BlockSpec((rb, d), lambda i: (i, 0)),
    ]
    if lim is not None:
        inputs.append(jnp.pad(lim.astype(jnp.float32), ((0, pr), (0, 0))))
        in_specs.append(pl.BlockSpec((rb, 1), lambda i: (i, 0)))
    if mode == "rr":
        inputs.append(jnp.pad(bits_arr, ((0, pr), (0, 0))))
        in_specs.append(pl.BlockSpec((rb, d), lambda i: (i, 0)))
    return inputs, in_specs, rows, rb


@functools.partial(jax.jit,
                   static_argnames=("bits", "s", "clip_c", "mode",
                                    "interpret"))
def encode_fused(v: jnp.ndarray, levels: jnp.ndarray,
                 rbits: Optional[jnp.ndarray], mask: jnp.ndarray, *,
                 bits: int, s: int, clip_c: Optional[float] = None,
                 mode: str = "rr", interpret: bool = True) -> jnp.ndarray:
    """(nb, d) values + (nb, s) levels [+ (nb, d) uint32 bits] + (nb, d)
    mask -> (nb, nw) packed uint32 wire words, nw = ceil(d / (32//bits)).

    One ``pallas_call``: the clip, interval search, rounding, masking and
    bit-pack all happen on the VMEM tile; nothing (nb, d)-sized is written
    back to HBM."""
    nb, d = v.shape
    assert levels.shape == (nb, s), (levels.shape, (nb, s))
    assert mode in MODES, mode
    epw = 32 // bits
    nw = -(-d // epw)
    lim = clip_limit(v, mask, clip_c)
    inputs, in_specs, rows, rb = _padded(v, levels, rbits, mask, lim,
                                         s=s, mode=mode, out_cols=nw)
    out = pl.pallas_call(
        functools.partial(_encode_kernel, s, bits, epw, lim is not None,
                          mode),
        out_shape=jax.ShapeDtypeStruct((rows, nw), jnp.uint32),
        grid=(rows // rb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rb, nw), lambda i: (i, 0)),
        interpret=interpret,
    )(*inputs)
    return out[:nb]


@functools.partial(jax.jit,
                   static_argnames=("s", "clip_c", "mode", "interpret"))
def qdq_fused(v: jnp.ndarray, levels: jnp.ndarray,
              rbits: Optional[jnp.ndarray], mask: jnp.ndarray, *,
              s: int, clip_c: Optional[float] = None, mode: str = "rr",
              interpret: bool = True) -> jnp.ndarray:
    """Fused local quantize->dequantize: same clip/round stage as
    ``encode_fused`` but decoded in-register -> (nb, d) float32 values
    (masked-out slots decode to level 0, like the multi-pass path). The
    error-feedback residual hot loop — one pallas_call, no idx/pack."""
    nb, d = v.shape
    assert levels.shape == (nb, s), (levels.shape, (nb, s))
    assert mode in MODES, mode
    lim = clip_limit(v, mask, clip_c)
    inputs, in_specs, rows, rb = _padded(v, levels, rbits, mask, lim,
                                         s=s, mode=mode, out_cols=d)
    out = pl.pallas_call(
        functools.partial(_qdq_kernel, s, lim is not None, mode),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        grid=(rows // rb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rb, d), lambda i: (i, 0)),
        interpret=interpret,
    )(*inputs)
    return out[:nb]
