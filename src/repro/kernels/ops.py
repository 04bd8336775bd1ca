"""Public wrappers for the Pallas kernels — env-flag resolution + jit'd
dispatch.

Each wrapper is a thin Python dispatcher that resolves the environment
overrides EAGERLY (i.e. at trace time of whatever jit is being built, or
per call when used standalone) and then hands off to a jit'd
implementation — the Pallas kernels are jit'd with static ``bits``/``s``/
``d``/``interpret`` and the pure-jnp reference oracles are jit'd too, so
standalone callers (benchmarks, notebooks) don't re-trace or run eagerly
op-by-op on repeat calls with the same static shapes.

Environment overrides (both read at trace time — set them before the
first jit of a step function; ``tests/test_fused_kernels.py`` pins the
trace-time read) resolve through the central accessor
``repro.utils.env``: ``REPRO_PALLAS_INTERPRET`` (interpret-mode
override; default backend autodetection) and ``REPRO_USE_KERNELS``
(``0`` forces the pure-jnp reference oracle ``ref.py`` for every op —
the CI parity matrix leg).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import bingrad as _bingrad
from repro.kernels import bitpack as _bitpack
from repro.kernels import dequant_avg as _dequant
from repro.kernels import fused_bingrad as _fbin
from repro.kernels import fused_decode as _fdec
from repro.kernels import fused_encode as _fenc
from repro.kernels import fused_kv as _fkv
from repro.kernels import orq_fit as _orq
from repro.kernels import quant_rr as _quant
from repro.kernels import ref as _ref
from repro.utils.env import kernels_enabled  # noqa: F401  (public compat)
from repro.utils.env import pallas_interpret as _interpret


def _use(use_kernels: bool) -> bool:
    return use_kernels and kernels_enabled()


# jit'd reference oracles (static args mirror the kernel wrappers')
_ref_quant_rr = jax.jit(_ref.quant_rr_ref)
_ref_bingrad_pass = jax.jit(_ref.bingrad_pass_ref)
_ref_dequant_avg = jax.jit(_ref.dequant_avg_ref)
_ref_pack = jax.jit(_ref.pack_ref, static_argnums=(1,))
_ref_unpack = jax.jit(_ref.unpack_ref, static_argnums=(1, 2))
_ref_encode_fused = jax.jit(
    _ref.encode_fused_ref, static_argnames=("bits", "clip_c", "mode"))
_ref_qdq_fused = jax.jit(
    _ref.qdq_fused_ref, static_argnames=("clip_c", "mode"))
_ref_encode_bingrad = jax.jit(
    _ref.encode_bingrad_fused_ref, static_argnames=("clip_c", "lloyd_iters"))
_ref_decode_mean = jax.jit(
    _ref.decode_fused_mean_ref, static_argnames=("d", "bits"))
_ref_decode_each = jax.jit(
    _ref.decode_fused_each_ref, static_argnames=("d", "bits"))
_ref_orq_fit = jax.jit(_ref.orq_fit_ref, static_argnums=(2, 3))
_ref_kv_attend = jax.jit(
    _ref.kv_attend_ref,
    static_argnames=("bits", "kv_heads", "scale", "softcap"))


# ---------------------------------------------------------------------------
# multi-pass ops (the PR-1..4 pipeline; kept for parity tests + benchmarks)
# ---------------------------------------------------------------------------

def quant_rr(v, levels, bits, *, use_kernels: bool = True):
    if not _use(use_kernels):
        return _ref_quant_rr(v, levels, bits)
    return _quant.quant_rr(v, levels, bits, s=levels.shape[-1],
                           interpret=_interpret())


def bingrad_pass(v, b0, mask, *, use_kernels: bool = True):
    if not _use(use_kernels):
        return _ref_bingrad_pass(v, b0, mask)
    return _bingrad.bingrad_pass(v, b0, mask, interpret=_interpret())


def dequant_avg(idx, levels, *, use_kernels: bool = True):
    if not _use(use_kernels):
        return _ref_dequant_avg(idx, levels)
    return _dequant.dequant_avg(idx, levels, s=levels.shape[-1],
                                interpret=_interpret())


def pack(idx, bits: int, *, use_kernels: bool = True):
    if not _use(use_kernels):
        return _ref_pack(idx, bits)
    return _bitpack.pack(idx, bits=bits, interpret=_interpret())


def unpack(words, bits: int, d: int, *, use_kernels: bool = True):
    if not _use(use_kernels):
        return _ref_unpack(words, bits, d)
    return _bitpack.unpack(words, bits=bits, d=d, interpret=_interpret())


# ---------------------------------------------------------------------------
# fused one-pass ops (the PR-5 pipeline; wire.py's default path)
# ---------------------------------------------------------------------------

def encode_fused(v, levels, rbits, mask, *, bits: int,
                 clip_c: Optional[float] = None, mode: str = "rr",
                 use_kernels: bool = True):
    """σ-clip + round + mask + bit-pack in ONE pallas_call: (nb, d) values
    -> (nb, nw) uint32 wire words. ``rbits`` is the threefry uint32 stream
    for mode='rr' (None for the deterministic modes)."""
    if not _use(use_kernels):
        return _ref_encode_fused(v, levels, rbits, mask, bits=bits,
                                 clip_c=clip_c, mode=mode)
    return _fenc.encode_fused(v, levels, rbits, mask, bits=bits,
                              s=levels.shape[-1], clip_c=clip_c, mode=mode,
                              interpret=_interpret())


def qdq_fused(v, levels, rbits, mask, *, clip_c: Optional[float] = None,
              mode: str = "rr", use_kernels: bool = True):
    """σ-clip + round + mask + in-register decode in ONE pallas_call:
    (nb, d) values -> (nb, d) dequantized f32 (the error-feedback path)."""
    if not _use(use_kernels):
        return _ref_qdq_fused(v, levels, rbits, mask, clip_c=clip_c,
                              mode=mode)
    return _fenc.qdq_fused(v, levels, rbits, mask, s=levels.shape[-1],
                           clip_c=clip_c, mode=mode, interpret=_interpret())


def orq_fit(v, mask, K: int, *, refine_iters: int = 0,
            use_kernels: bool = True):
    """ORQ level fit (Algorithm 1, no sort) in ONE pallas_call: (nb, d)
    values + (nb, d) mask -> (nb, 2^K + 1) f32 levels."""
    if not _use(use_kernels):
        return _ref_orq_fit(v, mask, K, refine_iters)
    return _orq.orq_fit(v, mask, K=K, refine_iters=refine_iters,
                        interpret=_interpret())


def encode_bingrad(v, mask, *, clip_c: Optional[float] = None,
                   lloyd_iters: int = 0, use_kernels: bool = True):
    """Fully-fused BinGrad-b: b₀ search + conditional-mean levels +
    threshold + 1-bit pack in ONE pallas_call -> ((nb, nw) words,
    (nb, 2) levels)."""
    if not _use(use_kernels):
        return _ref_encode_bingrad(v, mask, clip_c=clip_c,
                                   lloyd_iters=lloyd_iters)
    return _fbin.encode_bingrad_fused(v, mask, clip_c=clip_c,
                                      lloyd_iters=lloyd_iters,
                                      interpret=_interpret())


def decode_attend(q, kw, klv, vw, vlv, mask, *, bits: int, kv_heads: int,
                  scale: float, softcap: float = 0.0,
                  use_kernels: bool = True):
    """Fused dequant-attention over a quantized KV context in ONE
    pallas_call: q (B, T, H, hd) + packed kw/vw (B, C, nw) + klv/vlv
    (B, C, s) + mask (B, T, C) -> (B, T, H, hd) f32 (the serving engine's
    decode hot path — the dequantized K/V never round-trip HBM)."""
    if not _use(use_kernels):
        return _ref_kv_attend(q, kw, klv, vw, vlv, mask, bits=bits,
                              kv_heads=kv_heads, scale=scale,
                              softcap=softcap)
    return _fkv.decode_attend(q, kw, klv, vw, vlv, mask, bits=bits,
                              kv_heads=kv_heads, scale=scale,
                              softcap=softcap, interpret=_interpret())


def decode_fused_mean(words, levels, d: int, *, bits: int,
                      use_kernels: bool = True):
    """Unpack + dequantize + average L workers' payloads in ONE
    pallas_call: (L, nb, nw) + (L, nb, s) -> (nb, d) f32 mean."""
    if not _use(use_kernels):
        return _ref_decode_mean(words, levels, d=d, bits=bits)
    return _fdec.decode_fused_mean(words, levels, d=d, bits=bits,
                                   s=levels.shape[-1],
                                   interpret=_interpret())


def decode_fused_each(words, levels, d: int, *, bits: int,
                      use_kernels: bool = True):
    """Unpack + dequantize (no averaging) in ONE pallas_call:
    (L, nb, nw) + (L, nb, s) -> (L, nb, d) f32 values."""
    if not _use(use_kernels):
        return _ref_decode_each(words, levels, d=d, bits=bits)
    return _fdec.decode_fused_each(words, levels, d=d, bits=bits,
                                   s=levels.shape[-1],
                                   interpret=_interpret())


@partial(jax.jit, static_argnames=("clip_c",))
def _bucket_stats_impl(bkt, mask, clip_c):
    m = mask.astype(bkt.dtype)
    cnt = jnp.maximum(m.sum(axis=-1, keepdims=True), 1.0)
    mean = (bkt * m).sum(axis=-1, keepdims=True) / cnt
    var = (((bkt - mean) ** 2) * m).sum(axis=-1, keepdims=True) / cnt
    total = jnp.maximum(m.sum(), 1.0)
    # per-bucket variance weighted by valid count: the buffer's variance
    # around its per-bucket means (what the level fit actually sees)
    sigma_sq = (var[:, 0] * m.sum(axis=-1)).sum() / total
    if clip_c is None:
        clip_frac = jnp.zeros((), bkt.dtype)
    else:
        lim = clip_c * jnp.sqrt(var)
        clip_frac = ((jnp.abs(bkt) > lim) * m).sum() / total
    l2_sq = ((bkt * m) ** 2).sum()
    return jnp.stack([sigma_sq, clip_frac, l2_sq]).astype(jnp.float32)


def bucket_stats(bkt, mask, *, clip_c: Optional[float] = None):
    """(nb, d) buckets + validity mask -> (3,) f32 ``[sigma_sq,
    clip_frac, l2_sq]``: the count-weighted mean per-bucket variance,
    the fraction of valid elements a ``clip_c``-sigma clip would clamp,
    and the buffer's squared norm. The cheap statistics feed of the
    adaptive bit-budget controller (``core/policy.BitBudgetController``)
    — reductions only, no pallas_call (XLA fuses them into the step's
    existing HBM pass), so there is no kernel/oracle split to keep in
    parity."""
    return _bucket_stats_impl(bkt, mask, clip_c)
