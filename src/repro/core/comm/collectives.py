"""Quantized collectives: the distributed half of Algorithm 2, TPU-native.

The paper's parameter-server exchange maps onto two collective phases inside
``shard_map`` (manual axes = the data-parallel mesh axes):

  phase 1 (worker -> server)  ``quantized_reduce_scatter_mean``:
      each worker fits levels on its *local* gradient (the paper's runtime
      level selection), quantizes, bit-packs, and ``all_to_all``s the uint32
      payload + f32 level tables. Every worker then decodes the L received
      copies of its own chunk and averages — it *is* the server for that
      chunk. Wire bytes shrink by ~32/bits vs an f32 reduce-scatter.

  phase 2 (server -> worker)  inside ``quantized_all_reduce_mean``:
      the averaged chunk is re-quantized (fresh levels) and ``all_gather``ed
      — the paper's §4 option (b) "quantize the averaged gradient that the
      server sends back". Decoding is deterministic, so all workers
      reconstruct identical full gradients and replicated parameters stay
      in sync. ``server_requant=False`` gathers the f32 chunk instead
      (exact broadcast, 32-bit downlink).

The wire format (fit + round + uint32 bit-pack) lives in
``repro.core.comm.wire``; this module owns the collective choreography.

Named scopes (``jax.named_scope``, op metadata only): phase 1 runs under
``reduce``, phase 2 under ``requantize``, the error-feedback residual's
local quantize->dequantize under ``ef``, and every collective of the
exchange under ``collective`` (the wrappers below, which the other
exchange modules use too).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.comm import wire
from repro.core.comm.wire import _bucket_len
from repro.core.quantizers import Quantizer


def _names(axis_names) -> Tuple[str, ...]:
    """Normalize ``axis_names`` to an ORDERED tuple.

    Axis order is semantically meaningful here: it fixes the worker
    enumeration every collective in both phases relies on, and it must
    agree with the mesh/PartitionSpec axis order. A ``set`` iterates in
    hash order, which varies with ``PYTHONHASHSEED`` — two processes of a
    multi-process run could then lower the same collective with different
    axis orderings — and any fixed normalization (e.g. sorting) could
    still disagree with the mesh order. So sets are rejected outright;
    pass the ordered tuple the mesh was built with.
    """
    if isinstance(axis_names, str):
        return (axis_names,)
    if isinstance(axis_names, (set, frozenset)):
        raise TypeError(
            "axis_names must be an ordered tuple (or a single name), not "
            f"a set: {sorted(axis_names)!r} — set iteration order is "
            "PYTHONHASHSEED-dependent and the collective axis order must "
            "match the mesh axis order")
    return tuple(axis_names)


def axis_size(axis_names) -> int:
    n = 1
    for a in _names(axis_names):
        n *= lax.axis_size(a)
    return n


# ---------------------------------------------------------------------------
# the exchange's collectives, each under the ``collective`` scope
# ---------------------------------------------------------------------------

@jax.named_scope("collective")
def all_to_all(x, names):
    """Row ``i`` of ``x`` to worker ``i``; row ``i`` back from each."""
    return lax.all_to_all(x, names, split_axis=0, concat_axis=0)


@jax.named_scope("collective")
def all_gather(x, names, **kw):
    return lax.all_gather(x, names, **kw)


@jax.named_scope("collective")
def psum_scatter(x, names, **kw):
    return lax.psum_scatter(x, names, **kw)


@jax.named_scope("collective")
def pmean(x, names):
    return lax.pmean(x, names)


# ---------------------------------------------------------------------------
# phase 1 core: quantized reduce-scatter over explicit (L, chunk) parts
# ---------------------------------------------------------------------------

def _chunk_spans(n_rows: int, k) -> list:
    """Split ``n_rows`` bucket rows into ``k`` contiguous [a, b) spans
    (clamped to [1, n_rows]; the first ``n_rows % k`` spans get the extra
    row). The pipeline schedule is STATIC — span boundaries are Python
    ints, so each chunk lowers to its own encode + collective ops and XLA's
    latency-hiding scheduler can overlap chunk k's transfer with chunk
    k+1's encode."""
    k = max(1, min(int(k), n_rows))
    base, rem = divmod(n_rows, k)
    spans, a = [], 0
    for i in range(k):
        b = a + base + (1 if i < rem else 0)
        spans.append((a, b))
        a = b
    return spans


def _bucket_rows(parts: jnp.ndarray, d_eff: int) -> jnp.ndarray:
    """(L, chunk) -> (L * nbc, d_eff) bucket rows, nbc = ceil(chunk/d_eff):
    each part zero-padded to nbc*d_eff. Built from 1-D slices: the TPU
    compiler turns a row pad of a long 2-D (L, chunk) array into a
    relayout that takes minutes to compile at lm-100m size (~100 s for
    110M elements, against ~1 s this way)."""
    L, chunk = parts.shape
    width = -(-chunk // d_eff) * d_eff
    flat = parts.reshape(-1)
    if width != chunk:
        z = jnp.zeros((width - chunk,), parts.dtype)
        flat = jnp.concatenate([
            piece for i in range(L)
            for piece in (flat[i * chunk:(i + 1) * chunk], z)])
    return flat.reshape(-1, d_eff)


def _unbucket_rows(rows: jnp.ndarray, L: int, chunk: int) -> jnp.ndarray:
    """Inverse of :func:`_bucket_rows`: (L * nbc, d_eff) rows, or the same
    as (L, nbc, d_eff) -> (L * chunk,) with each part's padding dropped,
    from 1-D slices for the same reason."""
    flat = rows.reshape(-1)
    width = flat.shape[0] // L
    if width == chunk:
        return flat
    return jnp.concatenate(
        [flat[i * width:i * width + chunk] for i in range(L)])


@jax.named_scope("reduce")
def _rs_mean_parts(parts, valid, qz: Quantizer, key, names, use_kernels,
                   pipeline_chunks: int = 1):
    """parts (L, chunk) local contributions, one row per destination worker;
    valid (L, chunk) bool. Returns this worker's (chunk,) mean slice.

    ``key`` must already be folded per-worker (callers fold in the dp axis
    index OUTSIDE any nested manual region — axis_index of an outer-manual
    axis cannot lower inside a nested shard_map).

    ``pipeline_chunks > 1`` splits the nbc bucket rows into that many
    contiguous spans and runs fit→encode→all_to_all→decode once per span,
    double-buffered: span k's payload is in flight while span k+1 encodes.
    Bit-identical to the single-shot path — every encode/decode stage is
    independent per bucket row, and the random-rounding stream is drawn
    ONCE at the full (L·nbc, d_eff) layout and sliced per span (threefry
    bits are counter-based over the flattened shape, so drawing them at
    the span's own shape would change them)."""
    L, chunk = parts.shape
    d_eff = _bucket_len(chunk, qz.bucket_size)
    bkt = _bucket_rows(parts.astype(jnp.float32), d_eff)
    mask = _bucket_rows(valid, d_eff)
    nbc = bkt.shape[0] // L
    spans = _chunk_spans(nbc, pipeline_chunks)
    if len(spans) == 1:
        words, levels = wire.encode(qz, bkt, mask, key,
                                    use_kernels=use_kernels)
        words = words.reshape(L, nbc, -1)
        levels = levels.reshape(L, nbc, -1)
        # the wire: uint32 payload + f32 level tables
        words = all_to_all(words, names)
        levels = all_to_all(levels, names)
        mean_bkt = wire.decode_mean(qz, words, levels, d_eff,
                                    use_kernels=use_kernels)
        return mean_bkt.reshape(-1)[:chunk]

    # pipelined: K per-span wire units, each its own pair of all_to_alls.
    rbits = wire.encode_rbits(qz, key, (L * nbc, d_eff))
    bkt = bkt.reshape(L, nbc, d_eff)
    mask = mask.reshape(L, nbc, d_eff)
    rbits = None if rbits is None else rbits.reshape(L, nbc, d_eff)
    means = []
    for a, b in spans:
        sz = b - a
        sw, sl = wire.encode(
            qz, bkt[:, a:b].reshape(L * sz, d_eff),
            mask[:, a:b].reshape(L * sz, d_eff), key,
            use_kernels=use_kernels,
            rbits=None if rbits is None
            else rbits[:, a:b].reshape(L * sz, d_eff))
        sw = sw.reshape(L, sz, -1)
        sl = sl.reshape(L, sz, -1)
        sw = all_to_all(sw, names)
        sl = all_to_all(sl, names)
        means.append(wire.decode_mean(qz, sw, sl, d_eff,
                                      use_kernels=use_kernels))
    mean_bkt = jnp.concatenate(means, axis=0)             # (nbc, d_eff)
    return mean_bkt.reshape(-1)[:chunk]


def _valid_parts(valid, n: int, L: int, chunk: int) -> jnp.ndarray:
    """(L, chunk) bool validity for an (n,) buffer split into L chunks.
    ``valid`` optionally overrides the default arange<n mask — the
    hierarchical exchange passes the GLOBAL validity of an intra-scattered
    shard so its padding can't skew level fits."""
    if valid is None:
        return (jnp.arange(L * chunk) < n).reshape(L, chunk)
    return jnp.pad(valid, (0, L * chunk - n)).reshape(L, chunk)


def quantized_reduce_scatter_mean(
    flat: jnp.ndarray,
    qz: Quantizer,
    key: jax.Array,
    axis_names,
    *,
    worker_id=None,
    use_kernels: bool = True,
    valid=None,
    pipeline_chunks: int = 1,
) -> jnp.ndarray:
    """Each worker holds a full local gradient ``flat`` (n,). Returns this
    worker's (chunk,) slice of the across-worker *mean*, chunk = ceil(n/L).
    FP scheme short-circuits to a plain psum_scatter.

    ``worker_id`` defaults to ``axis_index`` of the dp axes; custom-VJP
    backward callers must pass it explicitly (axis_index cannot lower from
    transposed/hoisted contexts). ``valid`` optionally marks which of the
    n positions are real data (default: all of them). ``pipeline_chunks``
    splits the exchange into that many bucket-row spans whose encodes
    overlap the previous span's transfer — bit-identical to the
    single-shot schedule (see ``_rs_mean_parts``)."""
    n = flat.shape[0]
    names = _names(axis_names)
    L = axis_size(names)
    chunk = -(-n // L)
    padded = jnp.pad(flat, (0, L * chunk - n))
    if qz.is_identity:
        with jax.named_scope("reduce"):
            return psum_scatter(padded.reshape(L, chunk), names,
                                scatter_dimension=0, tiled=False) / L
    valid = _valid_parts(valid, n, L, chunk)
    if worker_id is None:
        worker_id = lax.axis_index(names)
    key = jax.random.fold_in(key, worker_id)
    return _rs_mean_parts(padded.reshape(L, chunk), valid, qz, key, names,
                          use_kernels, pipeline_chunks=pipeline_chunks)


# ---------------------------------------------------------------------------
# phase 1 + 2: quantized all-reduce (mean), replicated-parameter mode
# ---------------------------------------------------------------------------

@jax.named_scope("ef")
def local_qdq_comm_layout(
    flat: jnp.ndarray,
    qz: Quantizer,
    key: jax.Array,
    axis_names,
    *,
    worker_id=None,
    use_kernels: bool = True,
    valid=None,
) -> jnp.ndarray:
    """This worker's own dequantized gradient, bit-identical to what it
    contributed to ``quantized_reduce_scatter_mean`` (same chunk/bucket
    layout, same folded key, same ``valid`` mask). Used by error feedback:
    e ← g − Q⁻¹(Q(g)). Runs the fused ``wire.qdq`` kernel — one
    ``pallas_call``, no idx tensor or pack/unpack round-trip."""
    n = flat.shape[0]
    names = _names(axis_names)
    L = axis_size(names)
    chunk = -(-n // L)
    padded = jnp.pad(flat.astype(jnp.float32), (0, L * chunk - n))
    d_eff = _bucket_len(chunk, qz.bucket_size)
    bkt = _bucket_rows(padded.reshape(L, chunk), d_eff)
    mask = _bucket_rows(_valid_parts(valid, n, L, chunk), d_eff)
    if worker_id is None:
        worker_id = lax.axis_index(names)
    key = jax.random.fold_in(key, worker_id)
    vals = wire.qdq(qz, bkt, mask, key, use_kernels=use_kernels)
    return _unbucket_rows(vals, L, chunk)[:n]


def quantized_all_reduce_mean(
    flat: jnp.ndarray,
    qz: Quantizer,
    key: jax.Array,
    axis_names,
    *,
    worker_id=None,
    server_requant: bool = True,
    use_kernels: bool = True,
    valid=None,
    pipeline_chunks: int = 1,
) -> jnp.ndarray:
    """Full Algorithm 2 exchange. Returns the (n,) mean gradient, identical
    on every worker (the phase-2 decode is deterministic). ``valid``
    optionally marks the real positions of ``flat`` (both phases fit their
    levels on valid data only). ``pipeline_chunks`` chunks BOTH phases —
    phase 2's re-quantize + all_gather pipelines over the same bucket-row
    spans as phase 1 — and stays bit-identical to the single-shot path."""
    names = _names(axis_names)
    if qz.is_identity:
        return pmean(flat, names)

    mean_chunk = quantized_reduce_scatter_mean(
        flat, qz, key, names, worker_id=worker_id, use_kernels=use_kernels,
        valid=valid, pipeline_chunks=pipeline_chunks)

    return _requantize_all_gather(
        mean_chunk, flat, qz, key, names, worker_id=worker_id,
        server_requant=server_requant, use_kernels=use_kernels, valid=valid,
        pipeline_chunks=pipeline_chunks)


@jax.named_scope("requantize")
def _requantize_all_gather(mean_chunk, flat, qz: Quantizer, key, names, *,
                           worker_id, server_requant: bool,
                           use_kernels: bool, valid,
                           pipeline_chunks: int) -> jnp.ndarray:
    """Phase 2 of :func:`quantized_all_reduce_mean`: this worker's averaged
    ``mean_chunk`` re-quantized with fresh levels and all-gathered (f32
    when ``server_requant`` is off); returns the (n,) mean of ``flat``."""
    n = flat.shape[0]
    L = axis_size(names)
    chunk = -(-n // L)
    if not server_requant:
        full = all_gather(mean_chunk, names, axis=0, tiled=False)
        return full.reshape(-1)[:n].astype(flat.dtype)

    # phase 2: re-quantize the averaged chunk; broadcast payload + levels.
    me = lax.axis_index(names) if worker_id is None else worker_id
    d_eff = _bucket_len(chunk, qz.bucket_size)
    pad = -(-chunk // d_eff) * d_eff - chunk
    bkt = jnp.pad(mean_chunk, (0, pad)).reshape(-1, d_eff)
    if valid is None:
        pos = me * chunk + jnp.arange(chunk + pad)
        mask = (pos < n) & (jnp.arange(chunk + pad) < chunk)
    else:
        vchunk = lax.dynamic_slice(
            jnp.pad(valid, (0, L * chunk - n)), (me * chunk,), (chunk,))
        mask = jnp.pad(vchunk, (0, pad))
    mask = mask.reshape(-1, d_eff)
    key2 = jax.random.fold_in(jax.random.fold_in(key, 0x5EC0), me)
    spans = _chunk_spans(bkt.shape[0], pipeline_chunks)
    if len(spans) == 1:
        words, levels = wire.encode(qz, bkt, mask, key2,
                                    use_kernels=use_kernels)
        words = all_gather(words, names, axis=0, tiled=False)
        levels_all = all_gather(levels, names, axis=0, tiled=False)
        vals = wire.decode_each(qz, words, levels_all, d_eff,
                                use_kernels=use_kernels)  # (L, nbc, d_eff)
    else:
        # pipelined downlink: span k's gather flies while k+1 re-quantizes.
        rbits = wire.encode_rbits(qz, key2, bkt.shape)
        parts = []
        for a, b in spans:
            sw, sl = wire.encode(qz, bkt[a:b], mask[a:b], key2,
                                 use_kernels=use_kernels,
                                 rbits=None if rbits is None else rbits[a:b])
            sw = all_gather(sw, names, axis=0, tiled=False)
            sl = all_gather(sl, names, axis=0, tiled=False)
            parts.append(wire.decode_each(qz, sw, sl, d_eff,
                                          use_kernels=use_kernels))
        vals = jnp.concatenate(parts, axis=1)             # (L, nbc, d_eff)
    return _unbucket_rows(vals, L, chunk)[:n].astype(flat.dtype)


def psum_mean_tree(tree, axis_names):
    """FP baseline: plain pmean over the dp axes for a whole pytree."""
    return jax.tree_util.tree_map(lambda x: pmean(x, axis_names), tree)
