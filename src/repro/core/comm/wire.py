"""Wire format for quantized gradients: level fit + rounding + uint32 packing.

One "wire unit" is a pair ``(words, levels)``:

    words   (nb, nw) uint32 — bit-packed level indices, ``nw`` words per
            bucket at ``qz.wire_bits_per_element`` bits per element;
    levels  (nb, s)  float32 — the per-bucket runtime level tables
            (the paper's level selection happens per bucket, so the tables
            ride the wire next to the payload).

Both collective phases (worker->server and server->worker) speak exactly
this format; the functions here are the single place the encode/decode
pipeline is defined, shared by ``collectives`` and ``exchange``.

Since PR 5 the default path is the FUSED one-pass kernel pipeline
(``kernels/fused_*``): ``encode`` lowers to one ``pallas_call`` for the
σ-clip -> level search -> random rounding -> bit-pack sweep, after the
level FIT — for ORQ its own sort-free ``orq_fit`` kernel, for BinGrad-b
fused into the encode sweep, for the other schemes jnp — and
``decode``/``decode_mean``/``decode_each`` lower to one ``pallas_call``
each (unpack + dequantize [+ average]). The PRNG
bits are threaded in from the same threefry stream as before, so the
fused path is bit-identical to the multi-pass one (``encode_multipass``
et al., kept below as the parity baseline) and to the pure-jnp reference
oracle that ``use_kernels=False`` — or the ``REPRO_USE_KERNELS=0`` env
override — selects. One caveat: the MEAN decode kernels (fused and
multi-pass alike) accumulate ``val/L`` per worker while the oracle sums
then scales, so kernel-vs-oracle equality there is exact only when the
worker count is a power of two (scaling by 2^-k never rounds) and
float-close otherwise. The ORQ fit is exact in interpret mode; on the
chip its kernel sums each interval in another order than XLA, so in a
bucket whose midpoint solve sits on a half-way tie a level may take the
neighbouring value. Every other op is exact everywhere.

Named scopes (``jax.named_scope``, op metadata only): the level fit runs
under ``fit`` (``Quantizer.fit`` with its ``orq_fit`` kernel; BinGrad-b's
fused fit-and-encode kernel too), the rounding stream under ``rbits``,
the rest of an encode or local quantize->dequantize under ``encode``, and
every decode under ``decode``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.quantizers import Quantizer
from repro.kernels import ops

#: schemes that use unbiased random rounding (Eq. 7) on a fitted table
_RR_METHODS = ("orq", "terngrad", "qsgd", "linear", "minmax2", "bingrad_pb")


def bucket_len(chunk: int, d: int) -> int:
    """Effective bucket length for a chunk of ``chunk`` elements."""
    return min(d, max(chunk, 1))


# kept under the historical private name too (monolith-era callers/tests)
_bucket_len = bucket_len


def assign(qz: Quantizer, bkt, levels, key, use_kernels: bool, mask=None):
    """MULTI-PASS rounding dispatch (the PR-1..4 pipeline): random-rounding
    methods go through the Pallas quant_rr kernel. Kept as the building
    block of ``encode_multipass`` / the parity baseline; the default
    ``encode``/``qdq`` path fuses this stage into one kernel instead.

    ``mask`` is the real bucket-validity mask; the σ-clip must see it so
    padded ragged-tail positions feed the σ estimate exactly as in
    ``qz.fit`` (``None`` = all valid)."""
    from repro.core import clipping, rounding as R

    if qz.method in _RR_METHODS:
        if qz.clip_c is not None:
            if mask is None:
                mask = jnp.ones(bkt.shape, dtype=bool)
            bkt = clipping.sigma_clip(bkt, mask, qz.clip_c)
        bits = R.random_bits(key, bkt.shape)
        return ops.quant_rr(bkt, levels, bits, use_kernels=use_kernels)
    return qz.assign(bkt, levels, key, mask=mask)


_assign = assign


def _fused_mode(qz: Quantizer) -> str:
    """Static rounding mode of the fused stage for ``qz`` ('' = no fused
    path; fall back to the multi-pass composition)."""
    if qz.method in _RR_METHODS:
        return "rr"
    if qz.method == "bingrad_b":
        return "bin"
    if qz.method == "signsgd":
        return "sign"
    return ""


@jax.named_scope("rbits")
def encode_rbits(qz: Quantizer, key, shape):
    """The threefry uint32 stream :func:`encode` would draw for a ``shape``
    bucket layout (None for the deterministic schemes). The pipelined
    exchange generates the bits ONCE for the full canonical (nb, d_eff)
    layout and slices the bucket rows per chunk — ``jax.random.bits`` is
    counter-based over the row-major flattened shape, so bits drawn
    per-chunk-shape would differ and break bit-identity with the
    single-shot path."""
    from repro.core import rounding as R

    if _fused_mode(qz) != "rr":
        return None
    return R.random_bits(key, shape)


def encode(qz: Quantizer, bkt, mask, key, *, use_kernels: bool = True,
           rbits=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fit levels on masked buckets, round, and bit-pack — the fused path.

    bkt/mask are (nb, d_eff); returns ``(words, levels)`` wire units with
    masked-out slots forced to index 0 (they never reach the decoder's
    averaged output — callers slice them away). Everything after the
    level fit is ONE ``pallas_call`` (for BinGrad-b the fit fuses too);
    bit-identical to :func:`encode_multipass` given the same key.

    ``rbits`` optionally supplies the precomputed rounding stream (see
    :func:`encode_rbits`); the default draws it from ``key`` here. Every
    stage — fit, clip, round, pack — is independent per bucket row, so
    encoding a row-slice with the matching ``rbits`` slice reproduces the
    full encode's rows exactly (what the pipelined exchange relies on)."""
    mode = _fused_mode(qz)
    if mode == "bin":
        # b₀ search + conditional-mean levels + threshold + pack, one sweep
        with jax.named_scope("fit"):
            return ops.encode_bingrad(bkt, mask, clip_c=qz.clip_c,
                                      lloyd_iters=qz.lloyd_iters,
                                      use_kernels=use_kernels)
    if not mode:
        return encode_multipass(qz, bkt, mask, key, use_kernels=use_kernels)
    levels = qz.fit(bkt, mask, use_kernels=use_kernels)  # runtime levels
    if mode == "rr" and rbits is None:
        rbits = encode_rbits(qz, key, bkt.shape)
    with jax.named_scope("encode"):
        words = ops.encode_fused(bkt, levels,
                                 rbits if mode == "rr" else None, mask,
                                 bits=qz.wire_bits_per_element,
                                 clip_c=qz.clip_c, mode=mode,
                                 use_kernels=use_kernels)
    return words, levels


def encode_multipass(qz: Quantizer, bkt, mask, key, *,
                     use_kernels: bool = True
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The PR-1..4 multi-pass encode (fit -> assign kernel -> masked
    select -> pack kernel, each materializing (nb, d) intermediates).
    Kept as the parity/regression baseline for the fused path."""
    levels = qz.fit(bkt, mask, use_kernels=use_kernels)  # runtime levels
    with jax.named_scope("encode"):
        idx = jnp.where(mask, assign(qz, bkt, levels, key, use_kernels,
                                     mask=mask), 0)
        words = ops.pack(idx, qz.wire_bits_per_element,
                         use_kernels=use_kernels)
    return words, levels


def qdq(qz: Quantizer, bkt, mask, key, *,
        use_kernels: bool = True) -> jnp.ndarray:
    """Fused local quantize->dequantize on the wire layout: (nb, d_eff)
    values -> (nb, d_eff) f32, bit-identical to what :func:`encode` would
    put on the wire (same fit, same clip, same PRNG bits). The
    error-feedback residual hot path — one ``pallas_call``, no idx or
    pack/unpack round-trip (masked-out slots decode to level 0 exactly
    like the multi-pass path)."""
    levels = qz.fit(bkt, mask, use_kernels=use_kernels)
    mode = _fused_mode(qz)
    if not mode:
        with jax.named_scope("encode"):
            idx = jnp.where(mask, assign(qz, bkt, levels, key, use_kernels,
                                         mask=mask), 0)
            return Quantizer.decode(idx, levels)
    rbits = encode_rbits(qz, key, bkt.shape)
    with jax.named_scope("encode"):
        return ops.qdq_fused(bkt, levels, rbits, mask, clip_c=qz.clip_c,
                             mode=mode, use_kernels=use_kernels)


@jax.named_scope("decode")
def decode(qz: Quantizer, words, levels, d_eff: int, *, average: bool = True,
           use_kernels: bool = True) -> jnp.ndarray:
    """Decode L stacked wire units in ONE ``pallas_call``: unpack +
    dequantize [+ average]. ``average=True`` is the 'server' side of
    phase 1 (-> (nb, d_eff) mean); ``average=False`` is phase 2's
    deterministic broadcast decode (-> (L, nb, d_eff))."""
    bits = qz.wire_bits_per_element
    if average:
        return ops.decode_fused_mean(words, levels, d_eff, bits=bits,
                                     use_kernels=use_kernels)
    return ops.decode_fused_each(words, levels, d_eff, bits=bits,
                                 use_kernels=use_kernels)


def decode_mean(qz: Quantizer, words, levels, d_eff: int, *,
                use_kernels: bool = True) -> jnp.ndarray:
    """Decode L stacked wire units and average: (L, nb, nw) u32 + (L, nb, s)
    -> (nb, d_eff) mean values. This is the 'server' side of phase 1."""
    return decode(qz, words, levels, d_eff, average=True,
                  use_kernels=use_kernels)


def decode_each(qz: Quantizer, words, levels, d_eff: int, *,
                use_kernels: bool = True) -> jnp.ndarray:
    """Decode L stacked wire units without averaging: -> (L, nb, d_eff).
    Phase 2's all-gather'ed broadcast is decoded this way (every worker
    reconstructs each server's re-quantized chunk deterministically)."""
    return decode(qz, words, levels, d_eff, average=False,
                  use_kernels=use_kernels)


@jax.named_scope("decode")
def decode_mean_multipass(qz: Quantizer, words, levels, d_eff: int, *,
                          use_kernels: bool = True) -> jnp.ndarray:
    """The PR-1..4 multi-pass mean decode (vmapped unpack kernel writing
    the full (L, nb, d) idx tensor, then dequant_avg). Parity baseline."""
    bits = qz.wire_bits_per_element
    idx_all = jax.vmap(
        lambda w: ops.unpack(w, bits, d_eff, use_kernels=use_kernels)
    )(words)                                              # (L, nb, d_eff)
    return ops.dequant_avg(idx_all, levels, use_kernels=use_kernels)


@jax.named_scope("decode")
def decode_each_multipass(qz: Quantizer, words, levels, d_eff: int, *,
                          use_kernels: bool = True) -> jnp.ndarray:
    """The PR-1..4 multi-pass per-worker decode. Parity baseline."""
    bits = qz.wire_bits_per_element
    idx_all = jax.vmap(
        lambda w: ops.unpack(w, bits, d_eff, use_kernels=use_kernels)
    )(words)                                              # (L, nb, d_eff)
    return jax.vmap(Quantizer.decode)(idx_all, levels)


def encode_stats(qz: Quantizer, flat: jnp.ndarray,
                 d_eff: int) -> jnp.ndarray:
    """(3,) f32 ``[sigma_sq, clip_frac, l2_sq]`` of a flat buffer under
    ``qz``'s bucket layout — the optional statistics output of the encode
    path (per-bucket sigma^2 count-weighted over the buffer, the fraction
    of elements ``qz.clip_c`` would clamp, the squared norm). This is the
    cheap feed the adaptive ``BitBudgetController`` re-solves per-group
    bits from; see ``ops.bucket_stats`` (reductions only, no extra
    ``pallas_call``)."""
    from repro.core import buckets

    bkt, mask = buckets.to_buckets(flat.astype(jnp.float32), d_eff)
    return ops.bucket_stats(bkt, mask, clip_c=qz.clip_c)


def wire_unit_bytes(qz: Quantizer, nb: int, d_eff: int) -> int:
    """Bytes on the wire for one (words, levels) unit of nb buckets."""
    from repro.core import encode as E

    words = E.packed_words(d_eff, qz.wire_bits_per_element)
    return 4 * nb * (words + qz.s)
