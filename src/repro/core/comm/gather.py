"""ZeRO-3 parameter gathers whose custom-VJP backward is the quantized
gradient exchange.

For ZeRO-3 training the exchange rides the FSDP parameter gather:
``make_fsdp_gather`` returns an all_gather whose custom-VJP backward is the
phase-1 quantized reduce-scatter — exactly where the data-parallel gradient
communication lives. ``make_replicated_gather`` is the identity-forward
variant for leaves that stay dp-replicated.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.comm.collectives import (_names, all_gather, pmean,
                                         quantized_all_reduce_mean)
from repro.core.comm.fsdp_exchange import reduce_scatter_mean_block
from repro.core.quantizers import Quantizer
from repro.utils.compat import shard_map


def make_fsdp_gather(
    qz: Quantizer,
    axis_names,
    *,
    dim: int,
    tp_dim: Optional[int] = None,
    tp_axis: str = "model",
    compute_dtype=jnp.bfloat16,
    param_dtype=jnp.float32,
    use_kernels: bool = True,
):
    """Returns gather(w_slice, key) -> full ``compute_dtype`` leaf.

    fwd: cast + all_gather along ``dim`` over the dp axes (the FSDP
         parameter broadcast; bf16 wire).
    bwd: the paper — quantized reduce-scatter of the full-size local
         gradient cotangent; the f32 slice matches the stored shard.

    When the leaf is also tensor-parallel (``tp_dim`` over the auto
    ``tp_axis``), the backward runs inside a NESTED manual shard_map over
    that axis: every device quantizes its own contiguous gradient shard and
    the all_to_all stays within the dp axes. Without this, XLA has to
    replicate the strided flatten of a TP-sharded cotangent — terabytes of
    involuntary all-gather on 100B-parameter models.
    """
    names = _names(axis_names)

    @jax.custom_vjp
    @jax.named_scope("exchange")
    def gather(w, key):
        del key
        return all_gather(w.astype(compute_dtype), names, axis=dim,
                          tiled=True)

    def fwd(w, key):
        # capture the worker id in the PRIMAL context: axis_index cannot
        # lower from the transposed/hoisted backward context
        wid = lax.axis_index(names)
        return gather(w, key), (key, wid)

    def _local_rs(g, key):
        """Quantized RS of one (possibly per-tp-shard) cotangent block —
        the shared single-leaf primitive from ``fsdp_exchange``."""
        return reduce_scatter_mean_block(g, qz, key, names, dim=dim,
                                         use_kernels=use_kernels,
                                         param_dtype=param_dtype)

    @jax.named_scope("exchange")
    def bwd(res, g):
        key, wid = res
        key_w = jax.random.fold_in(key, wid)
        if tp_dim is not None:
            spec = [None] * g.ndim
            spec[tp_dim] = tp_axis
            pspec = jax.sharding.PartitionSpec(*spec)

            # NOTE: the rounding bits are shared across tp shards (the
            # shards quantize disjoint data, so unbiasedness is unaffected)
            out = shard_map(
                _local_rs,
                in_specs=(pspec, jax.sharding.PartitionSpec()),
                out_specs=pspec, axis_names={tp_axis},
                check_vma=False)(g, key_w)
        else:
            out = _local_rs(g, key_w)
        key_ct = np.zeros(key.shape, dtype=jax.dtypes.float0)
        return out, key_ct

    gather.defvjp(fwd, bwd)
    return gather


def make_replicated_gather(
    qz: Quantizer,
    axis_names,
    *,
    compute_dtype=jnp.bfloat16,
    param_dtype=jnp.float32,
    server_requant: bool = True,
    use_kernels: bool = True,
):
    """Identity 'gather' for dp-replicated leaves whose backward runs the
    full Algorithm 2 quantized all-reduce (leaves too small / indivisible to
    FSDP-shard still need their gradients exchanged and must stay bit-
    identical across workers — the deterministic phase-2 decode guarantees
    that)."""
    names = _names(axis_names)

    @jax.custom_vjp
    def gather(w, key):
        del key
        return w.astype(compute_dtype)

    def fwd(w, key):
        wid = lax.axis_index(names)   # primal context (see make_fsdp_gather)
        return gather(w, key), (key, wid)

    @jax.named_scope("exchange")
    def bwd(res, g):
        key, wid = res
        flat = g.astype(jnp.float32).reshape(-1)
        if qz.is_identity:
            mean = pmean(flat, names)
        else:
            mean = quantized_all_reduce_mean(
                flat, qz, key, names, worker_id=wid,
                server_requant=server_requant, use_kernels=use_kernels)
        out = mean.reshape(g.shape).astype(param_dtype)
        key_ct = np.zeros(key.shape, dtype=jax.dtypes.float0)
        return out, key_ct

    gather.defvjp(fwd, bwd)
    return gather
