"""Pallas TPU kernels for the quantized-KV serving engine.

Two hot paths, one ``pallas_call`` each:

    append_kv      quantize a batch of new tokens' K/V rows into wire
                   format in one sweep. K and V rows are stacked into a
                   single (2R, d) bucket matrix and pushed through
                   ``wire.encode`` — so the whole σ-fit → level-search →
                   round → pack pipeline is the SAME one-pass kernel the
                   training exchange uses (``fused_encode`` for the
                   random-round/sign schemes, ``fused_bingrad`` for
                   BinGrad-b) and inherits its oracles and env overrides
                   for free.

    decode_attend  decode-side fused dequant-attention: unpack the packed
                   uint32 context words + one-hot level decode feeding the
                   GQA attention inner loop, all inside one VMEM block per
                   sequence — the dequantized (C, d) K/V tensors never
                   round-trip HBM. The kernel body calls
                   ``ref.kv_attend_block`` on its tile, the SAME function
                   the jnp oracle (``ref.kv_attend_ref``) runs once per
                   sequence, so kernel/oracle bit-identity holds by
                   construction.

Dispatch (env overrides, ``REPRO_USE_KERNELS=0`` oracle leg) lives in
``kernels/ops.decode_attend``; ``append_kv`` dispatches through
``wire.encode`` like every other encode caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

#: scoped-VMEM limit of one ``decode_attend`` program. Each program holds
#: its sequence's whole dequantized (C, d) K and V plus their unpack
#: temporaries: lm-100m (d=768) at C=1024 needs ~26 MiB, over the
#: compiler's 16 MiB default. 64 MiB (half of a v5e core's 128 MiB VMEM)
#: covers C up to ~2K; longer contexts need the kernel to tile C.
ATTEND_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _attend_kernel(bits, kv_heads, hd, scale, softcap,
                   q_ref, kw_ref, klv_ref, vw_ref, vlv_ref, m_ref, o_ref):
    o_ref[0] = _ref.kv_attend_block(
        q_ref[0], kw_ref[0], klv_ref[0], vw_ref[0], vlv_ref[0], m_ref[0],
        bits=bits, kv_heads=kv_heads, head_dim=hd, scale=scale,
        softcap=softcap)


@functools.partial(jax.jit, static_argnames=("bits", "kv_heads", "scale",
                                             "softcap", "interpret"))
def decode_attend(q: jnp.ndarray, kw: jnp.ndarray, klv: jnp.ndarray,
                  vw: jnp.ndarray, vlv: jnp.ndarray, mask: jnp.ndarray, *,
                  bits: int, kv_heads: int, scale: float,
                  softcap: float = 0.0, interpret: bool = True):
    """Fused dequant-attention over a quantized KV context.

    q (B, T, H, hd) queries; kw/vw (B, C, nw) uint32 packed context words;
    klv/vlv (B, C, s) per-token level tables; mask (B, T, C) attention
    validity (causal ∧ allocated ∧ window, computed by the caller) ->
    (B, T, H, hd) f32 attention output. One ``pallas_call``, grid over the
    batch: each program unpacks + decodes its sequence's full context in
    VMEM and runs the masked-softmax GQA attention on it.
    """
    B, T, H, hd = q.shape
    C, nw = kw.shape[1], kw.shape[2]
    s = klv.shape[-1]
    q2 = q.astype(jnp.float32).reshape(B, T, H * hd)
    mf = mask.astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_attend_kernel, bits, kv_heads, hd, scale,
                          softcap),
        out_shape=jax.ShapeDtypeStruct((B, T, H * hd), jnp.float32),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, T, H * hd), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, C, nw), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, C, s), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, C, nw), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, C, s), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, T, C), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T, H * hd), lambda b: (b, 0, 0)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=ATTEND_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(q2, kw, klv.astype(jnp.float32), vw, vlv.astype(jnp.float32), mf)
    return out.reshape(B, T, H, hd)


def append_kv(qz, k_rows: jnp.ndarray, v_rows: jnp.ndarray, rbits, *,
              use_kernels: bool = True):
    """Quantize R new tokens' K and V rows to wire format in ONE
    ``pallas_call``: k_rows/v_rows (R, d) f32 (d = kv_heads*head_dim, one
    bucket per token spanning all KV heads) -> (kw, klv, vw, vlv) with
    kw/vw (R, nw) uint32 and klv/vlv (R, s) f32.

    ``rbits`` is the caller's deterministic (2R, d) uint32 rounding stream
    for the random-round schemes — K rows first, then V rows, matching the
    internal stacking — or None for the deterministic modes. Every encode
    stage is independent per bucket row, so stacking K and V into one
    (2R, d) matrix changes nothing about each row's bits while halving the
    kernel launches.
    """
    from repro.core.comm import wire

    if not wire._fused_mode(qz):
        raise ValueError(
            f"kv scheme {qz.method!r} has no fused one-pass encode; "
            f"supported: random-round schemes, bingrad-b, signsgd")
    R = k_rows.shape[0]
    stacked = jnp.concatenate(
        [k_rows.astype(jnp.float32), v_rows.astype(jnp.float32)], axis=0)
    mask = jnp.ones(stacked.shape, dtype=bool)
    words, levels = wire.encode(qz, stacked, mask, None, rbits=rbits,
                                use_kernels=use_kernels)
    return words[:R], levels[:R], words[R:], levels[R:]
