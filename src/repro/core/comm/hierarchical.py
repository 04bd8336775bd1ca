"""Hierarchical two-level (ICI/DCN) quantized gradient exchange.

The paper's optimal quantization condition holds for ANY gradient
distribution — in particular for the *intra-pod-averaged* gradient. On a
multi-pod mesh (``("pod", "data")`` dp axes) the fast intra-pod ICI links
can therefore carry full-precision collectives while quantization is
reserved for the scarce inter-pod DCN hops, exactly where DQ-SGD argues
compression should adapt to the communication setting and where TernGrad
reports the bulk of its wall-clock wins:

    phase 0 (ICI, full precision)   ``intra_reduce_scatter_mean``: each
        worker ends with a 1/L_intra shard of the pod-local mean gradient —
        the only data that still needs to cross pods.
    phase 1+2 (DCN, quantized)      the ordinary Algorithm 2 exchange
        (``quantized_all_reduce_mean``) runs on the SHARD over the ``pod``
        axis only: levels are fitted to the intra-averaged shard, so the
        unbiasedness / optimal-condition guarantees apply unchanged to the
        axis that actually gets quantized.
    phase 3 (ICI, full precision)   ``intra_all_gather`` reassembles the
        full global-mean buffer inside each pod.

Quantized wire traffic on the DCN link shrinks by 1/L_intra (each pod
sends shards, not full gradients); the ICI links pay two f32 collectives
they can afford. On a single-pod mesh the split degenerates to
``(intra=(), inter=dp_axes)`` and the exchange is bit-identical to the
flat one — the degenerate path IS the flat path.

This module owns the axis-splitting policy and the full-precision intra
primitives; the quantized inter phases live in ``collectives.py`` and the
engines (``exchange.py``/``fsdp_exchange.py``) compose the two.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
from jax import lax

from repro.core.comm.collectives import (_names, all_gather, axis_size,
                                         psum_scatter)

# dp axes that cross the slow inter-pod (DCN) boundary; everything else in
# the dp tuple is a fast intra-pod (ICI) axis. Matches the mesh layer's
# multi-pod convention (launch/mesh.py: ("pod", "data", "model")).
INTER_AXIS_NAMES: Tuple[str, ...] = ("pod",)

HIERARCHIES = ("flat", "two_level", "two_level_async", "auto")


def resolve_hierarchy(hierarchy: str, dp_axes, local_steps: int = 1) -> str:
    """'flat', 'two_level' or 'two_level_async' for a dp axis tuple; 'auto'
    picks two_level whenever the dp mesh has >= 2 axes (i.e. a pod axis to
    split off) — never the temporal variant, which changes training
    semantics and must be opted into explicitly.

    ``two_level_async`` with ``local_steps <= 1`` resolves to
    ``two_level``: an H=1 window syncs on every step, which IS the spatial
    hierarchy — routing it onto the literal two_level code path makes the
    flat≡H=1 bit-identity hold by construction, the same way a single-pod
    two_level IS flat.
    """
    if hierarchy not in HIERARCHIES:
        raise ValueError(
            f"hierarchy must be one of {HIERARCHIES}, got {hierarchy!r}")
    if hierarchy == "auto":
        return "two_level" if len(tuple(dp_axes)) >= 2 else "flat"
    if hierarchy == "two_level_async" and local_steps <= 1:
        return "two_level"
    return hierarchy


def split_dp_axes(dp_axes, hierarchy: str) -> Tuple[Tuple[str, ...],
                                                    Tuple[str, ...]]:
    """Split the ordered dp axis tuple into ``(intra_axes, inter_axes)``.

    flat: everything is quantized -> ``((), dp_axes)``.
    two_level: the slow :data:`INTER_AXIS_NAMES` axes carry the quantized
    exchange, the rest stay full precision. A mesh with no pod axis (or a
    pod-only dp mesh) degenerates to the flat split, which keeps two_level
    bit-identical to flat on single-pod meshes by construction.

    The inter axes must precede the intra axes in mesh order (they do for
    the canonical ``("pod", "data")`` tuple): the fused fsdp layout relies
    on the combined worker enumeration being inter-major.
    """
    dp = _names(dp_axes)
    if resolve_hierarchy(hierarchy, dp) == "flat":
        return (), dp
    inter = tuple(a for a in dp if a in INTER_AXIS_NAMES)
    intra = tuple(a for a in dp if a not in INTER_AXIS_NAMES)
    if not inter or not intra:
        return (), dp
    if dp != inter + intra:
        raise ValueError(
            f"inter axes {inter} must precede intra axes {intra} in the dp "
            f"tuple {dp}: the combined worker enumeration (and the fused "
            f"fsdp row layout) is inter-major")
    return intra, inter


# ---------------------------------------------------------------------------
# full-precision intra-pod primitives (inside shard_map over the dp axes)
# ---------------------------------------------------------------------------

def intra_chunk_len(n: int, n_intra: int) -> int:
    """Static per-worker shard length of an (n,) buffer scattered over
    ``n_intra`` intra workers (ceil division; the tail shard is padded)."""
    return -(-n // max(n_intra, 1))


def intra_reduce_scatter_mean(flat: jnp.ndarray, intra_names) -> jnp.ndarray:
    """(n,) local buffer -> (ceil(n/L_i),) shard of the intra-axis MEAN.
    Full precision (one psum_scatter on the fast ICI link)."""
    names = _names(intra_names)
    L = axis_size(names)
    n = flat.shape[0]
    chunk = intra_chunk_len(n, L)
    padded = jnp.pad(flat.astype(jnp.float32), (0, L * chunk - n))
    return psum_scatter(padded.reshape(L, chunk), names,
                        scatter_dimension=0, tiled=False) / L


def intra_all_gather(shard: jnp.ndarray, intra_names, n: int) -> jnp.ndarray:
    """(chunk,) per-worker shard -> the reassembled (n,) buffer (one
    all_gather on the fast ICI link; inverse of the scatter above)."""
    names = _names(intra_names)
    full = all_gather(shard, names, axis=0, tiled=False)
    return full.reshape(-1)[:n]


def shard_valid_mask(n: int, intra_names) -> jnp.ndarray:
    """(chunk,) bool: which positions of THIS worker's intra shard map to
    real elements of the original (n,) buffer (False = scatter padding).
    Threaded into the quantized inter exchange so ragged-tail padding can
    never skew a bucket's sigma fit."""
    names = _names(intra_names)
    L = axis_size(names)
    chunk = intra_chunk_len(n, L)
    d = lax.axis_index(names)
    return d * chunk + jnp.arange(chunk) < n
