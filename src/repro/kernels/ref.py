"""Pure-jnp oracles for every Pallas kernel (bit-exact semantics).

The fused-kernel oracles (``encode_fused_ref`` & co.) are the LEGACY
multi-pass compositions — σ-clip, round, mask, pack as separate jnp
sweeps — kept as the single source of truth the one-pass kernels are
tested bit-identical against (``use_kernels=False`` / ``REPRO_USE_KERNELS=0``
select them at runtime).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def uniform_from_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 rounding bits -> [0, 1) float32: the top 24 bits as an
    int32, times 2^-24. The one conversion rule of every random-round
    path (oracle, multi-pass and fused kernels, ``rounding``). It is exact
    (every value is an f32) and lowers on the TPU, whose Pallas compiler
    has no uint32 -> float32 cast."""
    top = (bits >> jnp.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(2.0 ** -24)


def quant_rr_ref(v: jnp.ndarray, levels: jnp.ndarray,
                 bits: jnp.ndarray) -> jnp.ndarray:
    """Oracle for kernels.quant_rr.quant_rr."""
    s = levels.shape[-1]
    v = v.astype(jnp.float32)
    lv = levels.astype(jnp.float32)
    k = (v[..., None] >= lv[:, None, :]).sum(-1).astype(jnp.int32) - 1
    k = jnp.clip(k, 0, s - 2)
    lo = jnp.take_along_axis(lv, k, axis=-1)
    hi = jnp.take_along_axis(lv, k + 1, axis=-1)
    vc = jnp.clip(v, lo, hi)
    width = hi - lo
    p_up = jnp.where(width > 0, (vc - lo) / jnp.where(width > 0, width, 1.0),
                     0.0)
    return k + (uniform_from_bits(bits) < p_up).astype(jnp.int32)


def bingrad_pass_ref(v: jnp.ndarray, b0: jnp.ndarray, mask: jnp.ndarray):
    """Oracle for kernels.bingrad.bingrad_pass."""
    v = v.astype(jnp.float32)
    m = mask.astype(jnp.float32)
    hi = (v >= b0).astype(jnp.float32) * m
    lo = (1.0 - (v >= b0).astype(jnp.float32)) * m
    idx = (hi > 0).astype(jnp.int32)
    part = jnp.stack(
        [(v * lo).sum(-1), lo.sum(-1), (v * hi).sum(-1), hi.sum(-1)], axis=-1
    )
    return idx, part


def dequant_avg_ref(idx: jnp.ndarray, levels: jnp.ndarray) -> jnp.ndarray:
    """Oracle for kernels.dequant_avg.dequant_avg."""
    L = idx.shape[0]
    vals = jnp.take_along_axis(levels.astype(jnp.float32), idx, axis=-1)
    return vals.sum(0) * (1.0 / L)


def pack_ref(idx: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Oracle for kernels.bitpack.pack."""
    from repro.core import encode

    return encode.pack(idx, bits)


def unpack_ref(words: jnp.ndarray, bits: int, d: int) -> jnp.ndarray:
    """Oracle for kernels.bitpack.unpack."""
    from repro.core import encode

    return encode.unpack(words, bits, d)


# ---------------------------------------------------------------------------
# fused-pipeline oracles (the legacy multi-pass compositions)
# ---------------------------------------------------------------------------

def _round_ref(v: jnp.ndarray, levels: jnp.ndarray,
               rbits: Optional[jnp.ndarray], mask: jnp.ndarray,
               clip_c: Optional[float], mode: str) -> jnp.ndarray:
    """Shared clip+round stage: masked int32 level indices (the exact
    legacy ``wire.assign`` + masked-select composition)."""
    from repro.core import clipping

    v = v.astype(jnp.float32)
    if clip_c is not None:
        v = clipping.sigma_clip(v, mask, clip_c)
    if mode == "rr":
        idx = quant_rr_ref(v, levels, rbits)
    elif mode == "bin":
        b0 = 0.5 * (levels[:, :1] + levels[:, 1:2])   # Eq. (17): midpoint
        idx = (v >= b0).astype(jnp.int32)
    elif mode == "sign":
        idx = (v >= jnp.zeros((v.shape[0], 1))).astype(jnp.int32)
    else:
        raise ValueError(f"unknown rounding mode {mode!r}")
    return jnp.where(mask, idx, 0)


def encode_fused_ref(v: jnp.ndarray, levels: jnp.ndarray,
                     rbits: Optional[jnp.ndarray], mask: jnp.ndarray, *,
                     bits: int, clip_c: Optional[float] = None,
                     mode: str = "rr") -> jnp.ndarray:
    """Oracle for kernels.fused_encode.encode_fused."""
    return pack_ref(_round_ref(v, levels, rbits, mask, clip_c, mode), bits)


def qdq_fused_ref(v: jnp.ndarray, levels: jnp.ndarray,
                  rbits: Optional[jnp.ndarray], mask: jnp.ndarray, *,
                  clip_c: Optional[float] = None,
                  mode: str = "rr") -> jnp.ndarray:
    """Oracle for kernels.fused_encode.qdq_fused."""
    idx = _round_ref(v, levels, rbits, mask, clip_c, mode)
    return jnp.take_along_axis(levels.astype(jnp.float32), idx, axis=-1)


def encode_bingrad_fused_ref(v: jnp.ndarray, mask: jnp.ndarray, *,
                             clip_c: Optional[float] = None,
                             lloyd_iters: int = 0):
    """Oracle for kernels.fused_bingrad.encode_bingrad_fused."""
    from repro.core import clipping
    from repro.core import levels as L

    v = v.astype(jnp.float32)
    if clip_c is not None:
        v = clipping.sigma_clip(v, mask, clip_c)
    lv = L.bingrad_b_levels(v, mask, lloyd_iters=lloyd_iters)
    idx = _round_ref(v, lv, None, mask, None, "bin")
    return pack_ref(idx, 1), lv


# ---------------------------------------------------------------------------
# quantized-KV serving oracles (kernels/fused_kv.py)
# ---------------------------------------------------------------------------

_NEG_INF = -2.0e38
#: full-f32 matmuls: XLA's default on the TPU is one bf16 pass, which
#: would set the oracle apart from the kernel by ~1e-3
_F32 = jax.lax.Precision.HIGHEST


def _kv_decode(w: jnp.ndarray, lv: jnp.ndarray, bits: int, s: int,
               d: int) -> jnp.ndarray:
    """(C, nw) uint32 packed words + (C, s) levels -> (C, d) f32 values:
    shift-mask unpack + gather-free one-hot level decode (the exact
    composition of ``fused_decode._unpack_decode``, 2-D)."""
    epw = 32 // bits
    m = jnp.uint32(2 ** bits - 1)
    parts = []
    for j in range(epw):                          # static unroll
        parts.append(((w >> jnp.uint32(bits * j)) & m).astype(jnp.int32))
    idx = jnp.concatenate(parts, axis=-1)[:, :d]  # element c = j*nw + word
    val = jnp.zeros(idx.shape, dtype=jnp.float32)
    for j in range(s):                  # static unroll, gather-free decode
        val = val + ((idx == j).astype(jnp.float32)
                     * lv[:, j].astype(jnp.float32)[:, None])
    return val


def kv_attend_block(q: jnp.ndarray, kw: jnp.ndarray, klv: jnp.ndarray,
                    vw: jnp.ndarray, vlv: jnp.ndarray, mask: jnp.ndarray, *,
                    bits: int, kv_heads: int, head_dim: int, scale: float,
                    softcap: float = 0.0) -> jnp.ndarray:
    """One sequence of fused dequant-attention: q (T, H*hd) against a
    quantized KV context kw/vw (C, nw) uint32 + klv/vlv (C, s) levels with
    mask (T, C) in {0, 1} -> (T, H*hd) f32.

    This is THE definition of the math: the Pallas kernel body in
    ``fused_kv.py`` calls this very function on its VMEM tile, and the
    oracle ``kv_attend_ref`` runs it once per sequence — bit-identity between
    kernel and oracle is by construction, not by mirroring. Heads stay
    lane slices of the flat rows (one 2-D matmul pair per query head): the
    TPU's Pallas compiler refuses the (T, H*hd) -> (T, H, hd) reshape."""
    hd = head_dim
    H = q.shape[-1] // hd
    g = H // kv_heads
    s = klv.shape[-1]
    k = _kv_decode(kw, klv, bits, s, kv_heads * hd)
    v = _kv_decode(vw, vlv, bits, s, kv_heads * hd)
    q = q.astype(jnp.float32)
    outs = []
    for h in range(H):                            # static unroll
        lo = (h // g) * hd                        # this head's KV columns
        sc = jax.lax.dot_general(
            q[:, h * hd:(h + 1) * hd], k[:, lo:lo + hd],
            (((1,), (1,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32) * scale      # (T, C)
        if softcap:
            sc = jnp.tanh(sc / softcap) * softcap
        sc = jnp.where(mask > 0, sc, _NEG_INF)
        e = jnp.exp(sc - sc.max(axis=-1, keepdims=True))
        p = e / e.sum(axis=-1, keepdims=True)
        outs.append(jnp.dot(p, v[:, lo:lo + hd], precision=_F32,
                            preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=-1)


def kv_attend_ref(q: jnp.ndarray, kw: jnp.ndarray, klv: jnp.ndarray,
                  vw: jnp.ndarray, vlv: jnp.ndarray, mask: jnp.ndarray, *,
                  bits: int, kv_heads: int, scale: float,
                  softcap: float = 0.0) -> jnp.ndarray:
    """Oracle for kernels.fused_kv.decode_attend: :func:`kv_attend_block`
    once per sequence (unbatched, as each kernel program runs it — a vmap
    would batch the matmuls and change their accumulation order). q
    (B, T, H, hd), kw/vw (B, C, nw), klv/vlv (B, C, s), mask (B, T, C) ->
    (B, T, H, hd) f32."""
    B, T, H, hd = q.shape
    q2 = q.astype(jnp.float32).reshape(B, T, H * hd)
    mf = mask.astype(jnp.float32)
    out = jnp.stack([
        kv_attend_block(q2[b], kw[b], klv[b], vw[b], vlv[b], mf[b],
                        bits=bits, kv_heads=kv_heads, head_dim=hd,
                        scale=scale, softcap=softcap)
        for b in range(B)])                       # static unroll
    return out.reshape(B, T, H, hd)


def decode_fused_mean_ref(words: jnp.ndarray, levels: jnp.ndarray, *,
                          d: int, bits: int) -> jnp.ndarray:
    """Oracle for kernels.fused_decode.decode_fused_mean."""
    idx = jax.vmap(lambda w: unpack_ref(w, bits, d))(words)
    return dequant_avg_ref(idx, levels)


def decode_fused_each_ref(words: jnp.ndarray, levels: jnp.ndarray, *,
                          d: int, bits: int) -> jnp.ndarray:
    """Oracle for kernels.fused_decode.decode_fused_each."""
    idx = jax.vmap(lambda w: unpack_ref(w, bits, d))(words)
    return jnp.take_along_axis(levels, idx.astype(jnp.int32), axis=-1)
