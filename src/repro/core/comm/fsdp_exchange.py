"""Fused policy-aware FSDP (ZeRO-3) gradient exchange.

The per-leaf fsdp gather (``make_fsdp_gather``) issues one quantized
reduce-scatter per parameter leaf — a 100+ leaf model pays 100+ collective
launches, ragged-bucket paddings, and level-table transfers per step, and
there is nowhere to hang an error-feedback residual because each leaf's
exchange lives inside its own custom-VJP. This module is the shard-aware
sibling of ``PolicyLayout``/``PartitionedExchange`` (``exchange.py``):

    FsdpLayout     static partition plan: leaves grouped by resolved
                   QuantConfig into contiguous per-group flat buffers whose
                   element order respects each leaf's dp-shard coordinates —
                   worker w's reduce-scatter chunk is exactly the
                   concatenation of worker w's parameter-shard slices;
    FsdpExchange   one fused quantized reduce-scatter per SHARDED policy
                   group (phase 1 only: fsdp has no server->worker
                   broadcast, the next forward's parameter all-gather is
                   the downlink) plus one fused quantized all-reduce per
                   REPLICATED group (leaves with no dp-divisible dim), with
                   per-group wire accounting and error-feedback residuals;
    make_fused_tree_gather
                   the custom-VJP whole-tree gather the train step calls:
                   forward = one fused bf16 all-gather per sharded group
                   (the ZeRO-3 parameter broadcast), backward = the fused
                   exchange above. Error-feedback residuals ride the
                   cotangent of the residual-buffer input, so
                   ``value_and_grad(loss, argnums=(0, 1))`` returns
                   (sharded grads, new residuals) in one pass and the
                   residual stream persists in ``TrainState.ef``.

Buffer layout of one sharded group (L dp workers, leaves a, b):

        row 0: [ a.shard0 | b.shard0 ]      rows = all_to_all'd chunks;
        row 1: [ a.shard1 | b.shard1 ]      worker w keeps the mean of
        ...                                 row w == grads for exactly
        row L-1: [ a.shardL-1 | b.shardL-1 ]   its own param shards.

Collective launches are O(#policy groups), never O(#leaves). Tensor
parallelism: flattening a TP-sharded cotangent into a single dp buffer
would force XLA to replicate it over the ``model`` axis, so callers keep
the per-leaf gather (with its nested-manual trick) whenever
``n_model > 1`` — see ``train/step.py``.

Every quantized phase here (the reduce-scatter encode/decode and the
error-feedback ``local_qdq``) goes through ``collectives``/``wire`` and
therefore rides the FUSED one-pass Pallas kernels by default since PR 5
(one ``pallas_call`` per sweep; ``use_kernels=False`` /
``REPRO_USE_KERNELS=0`` select the bit-identical jnp oracle).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.api import QuantConfig
from repro.core.comm import wire
from repro.core.comm.collectives import (_names, _rs_mean_parts,
                                         all_gather, axis_size,
                                         local_qdq_comm_layout,
                                         psum_scatter,
                                         quantized_reduce_scatter_mean)
from repro.core.comm.exchange import GradientExchange, link_stats
from repro.core.policy import QuantPolicy
from repro.core.quantizers import Quantizer
from repro.utils.pytree import tree_flatten_with_path_strs


def reduce_scatter_mean_block(g, qz: Quantizer, key, axis_names, *, dim: int,
                              use_kernels: bool = True,
                              param_dtype=jnp.float32,
                              pipeline_chunks: int = 1):
    """Quantized reduce-scatter of ONE full-size cotangent block along
    ``dim``: returns this worker's shard of the across-worker mean, in the
    stored-shard shape. The single-leaf primitive shared by the per-leaf
    fsdp gather backward (``make_fsdp_gather``) and by tests.

    ``key`` must already be folded per-worker (callers fold in the dp axis
    index in the primal context — see ``make_fsdp_gather``)."""
    names = _names(axis_names)
    L = axis_size(names)
    gm = jnp.moveaxis(g.astype(jnp.float32), dim, 0)
    lead, rest = gm.shape[0], gm.shape[1:]
    chunk = (lead // L) * int(np.prod(rest)) if rest else lead // L
    parts = gm.reshape(L, chunk)
    if qz.is_identity:
        mean_chunk = psum_scatter(
            parts, names, scatter_dimension=0, tiled=False) / L
    else:
        valid = jnp.ones((L, chunk), dtype=bool)
        mean_chunk = _rs_mean_parts(parts, valid, qz, key, names,
                                    use_kernels,
                                    pipeline_chunks=pipeline_chunks)
    out = mean_chunk.reshape((lead // L,) + rest)
    return jnp.moveaxis(out, 0, dim).astype(param_dtype)


# ---------------------------------------------------------------------------
# layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FsdpSlot:
    """One leaf's span inside its group buffer (FULL-leaf coordinates)."""

    path: str
    shape: Tuple[int, ...]       # full (unsharded) leaf shape
    dtype: Any
    dim: Optional[int]           # dp-shard dim in full coords; None = repl.
    offset: int                  # sharded: offset inside each worker ROW
                                 # (elements of one shard); replicated:
                                 # offset inside the full group buffer
    size: int                    # full element count


@dataclasses.dataclass(frozen=True)
class FsdpGroup:
    """One policy group's contiguous segment. ``rule_id`` is the policy
    rule index (``by_rule`` layouts only) a ``BitSchedule`` phase
    specialization re-resolves the config through."""

    cfg: QuantConfig
    sharded: bool                # True: reduce-scatter; False: all-reduce
    leaf_ids: Tuple[int, ...]    # canonical leaf order indices, ascending
    size: int                    # full element count of the group buffer
    rule_id: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class FsdpLayout:
    """Static shard-aware partition plan for a ZeRO-3 parameter tree.

    Leaves are grouped by ``(resolved QuantConfig, sharded?)``; sharded
    groups are laid out worker-major (row w = worker w's shard slices of
    every leaf, concatenated in canonical order), so a reduce-scatter of
    the flattened buffer hands each worker a chunk that unflattens
    directly onto its stored parameter shards.
    """

    treedef: Any
    slots: Tuple[FsdpSlot, ...]
    groups: Tuple[FsdpGroup, ...]
    leaf_group: Tuple[int, ...]          # leaf i -> index into groups
    n_shards: int                        # L, the dp worker count

    @classmethod
    def from_tree(cls, tree, policy: QuantPolicy, *, paths, shard_dims,
                  n_shards: int, by_rule: bool = False) -> "FsdpLayout":
        """``paths``: pytree of path strings aligned with ``tree``;
        ``shard_dims``: path -> dp-shard dim in FULL leaf coords (None =
        replicated); ``n_shards``: dp worker count. Every sharded leaf's
        ``shape[dim]`` must divide by ``n_shards`` (``plan_sharding``
        guarantees it). ``by_rule=True`` keys the grouping on
        ``(policy rule index, sharded)`` instead of ``(config,
        sharded)`` — the bits-invariant partition a ``BitSchedule``
        skeleton needs (see ``PolicyLayout.from_tree``)."""
        pairs, treedef = tree_flatten_with_path_strs(tree)
        path_strs = list(jax.tree_util.tree_leaves(paths))
        assert len(path_strs) == len(pairs), (len(path_strs), len(pairs))

        group_ix: Dict[Tuple[Any, bool], int] = {}
        g_cfg: List[Tuple[QuantConfig, bool, Optional[int]]] = []
        g_leaves: List[List[int]] = []
        g_off: List[int] = []
        slots: List[FsdpSlot] = []
        leaf_group: List[int] = []
        for i, ((_, leaf), path) in enumerate(zip(pairs, path_strs)):
            cfg = policy.resolve(path)
            rid = policy.resolve_ix(path) if by_rule else None
            dim = shard_dims.get(path)
            if dim is not None and (not leaf.shape
                                    or leaf.shape[dim] % n_shards):
                raise ValueError(
                    f"leaf {path!r} shape {leaf.shape} is not divisible "
                    f"by {n_shards} along dim {dim}")
            sharded = dim is not None
            gkey = (rid if by_rule else cfg, sharded)
            gi = group_ix.setdefault(gkey, len(g_cfg))
            if gi == len(g_cfg):
                g_cfg.append((cfg, sharded, rid))
                g_leaves.append([])
                g_off.append(0)
            size = int(np.prod(leaf.shape)) if leaf.shape else 1
            slots.append(FsdpSlot(path=path, shape=tuple(leaf.shape),
                                  dtype=leaf.dtype, dim=dim,
                                  offset=g_off[gi], size=size))
            # sharded rows advance by ONE shard's elements; replicated
            # buffers by the full leaf
            g_off[gi] += size // n_shards if sharded else size
            g_leaves[gi].append(i)
            leaf_group.append(gi)
        groups = tuple(
            FsdpGroup(cfg=c, sharded=sh, leaf_ids=tuple(ls),
                      size=off * (n_shards if sh else 1), rule_id=r)
            for (c, sh, r), ls, off in zip(g_cfg, g_leaves, g_off))
        return cls(treedef=treedef, slots=tuple(slots), groups=groups,
                   leaf_group=tuple(leaf_group), n_shards=n_shards)

    def with_configs(self, policy: QuantPolicy) -> "FsdpLayout":
        """Specialize a ``by_rule`` skeleton to one phase's configs
        (identical slots/offsets/group membership; see
        ``PolicyLayout.with_configs``)."""
        for g in self.groups:
            if g.rule_id is None:
                raise ValueError(
                    "with_configs needs a by_rule layout (group rule_ids "
                    "are unset — build with from_tree(by_rule=True))")
        groups = tuple(
            dataclasses.replace(g, cfg=policy.cfg_for_rule(g.rule_id))
            for g in self.groups)
        return dataclasses.replace(self, groups=groups)

    @property
    def size(self) -> int:
        return sum(g.size for g in self.groups)

    # -- forward: fused parameter all-gather -------------------------------
    def gather_full(self, tree, axis_names, *, compute_dtype=jnp.bfloat16):
        """Sharded-param pytree -> full-leaf pytree (``compute_dtype``),
        ONE all_gather per sharded group (the ZeRO-3 parameter broadcast;
        replicated leaves just cast). Runs inside shard_map over the dp
        axes."""
        names = _names(axis_names)
        L = self.n_shards
        leaves = jax.tree_util.tree_leaves(tree)
        assert len(leaves) == len(self.slots), (len(leaves), len(self.slots))
        full: List[Any] = [None] * len(leaves)
        for g in self.groups:
            if not g.sharded:
                for i in g.leaf_ids:
                    full[i] = leaves[i].astype(compute_dtype)
                continue
            row = jnp.concatenate([
                jnp.moveaxis(leaves[i].astype(compute_dtype),
                             self.slots[i].dim, 0).reshape(-1)
                for i in g.leaf_ids])
            # worker-major (L * width,); every leaf is cut from it with
            # 1-D slices (see collectives._bucket_rows for why not 2-D)
            rows = all_gather(row, names, axis=0, tiled=True)
            width = row.shape[0]
            for i in g.leaf_ids:
                s = self.slots[i]
                shard = s.size // L
                rest = s.shape[:s.dim] + s.shape[s.dim + 1:]
                seg = jnp.concatenate([
                    rows[w * width + s.offset:w * width + s.offset + shard]
                    for w in range(L)])
                seg = seg.reshape((s.shape[s.dim],) + rest)
                full[i] = jnp.moveaxis(seg, 0, s.dim)
        return jax.tree_util.tree_unflatten(self.treedef, full)

    # -- backward: buffers <-> trees ---------------------------------------
    def flatten_groups(self, tree) -> Tuple[jnp.ndarray, ...]:
        """Full-leaf cotangent pytree -> one (group.size,) f32 buffer per
        group. Sharded groups are worker-major (see class docstring)."""
        L = self.n_shards
        leaves = jax.tree_util.tree_leaves(tree)
        assert len(leaves) == len(self.slots), (len(leaves), len(self.slots))
        bufs = []
        for g in self.groups:
            if not g.sharded:
                bufs.append(jnp.concatenate(
                    [leaves[i].astype(jnp.float32).reshape(-1)
                     for i in g.leaf_ids]))
                continue
            flat = [jnp.moveaxis(leaves[i].astype(jnp.float32),
                                 self.slots[i].dim, 0).reshape(-1)
                    for i in g.leaf_ids]
            sizes = [self.slots[i].size // L for i in g.leaf_ids]
            bufs.append(jnp.concatenate([
                f[w * n:(w + 1) * n] for w in range(L)
                for f, n in zip(flat, sizes)]))
        return tuple(bufs)

    def unflatten_outputs(self, outs: Sequence[jnp.ndarray], *,
                          param_dtype=jnp.float32):
        """Per-group exchange outputs -> pytree aligned with the STORED
        (sharded) parameters: sharded groups receive their own
        (group.size / L,) mean chunk, replicated groups the full
        (group.size,) mean buffer."""
        assert len(outs) == len(self.groups), (len(outs), len(self.groups))
        L = self.n_shards
        leaves = []
        for i, s in enumerate(self.slots):
            out = outs[self.leaf_group[i]]
            if s.dim is None:
                leaf = out[s.offset:s.offset + s.size].reshape(s.shape)
            else:
                shard = s.size // L
                rest = s.shape[:s.dim] + s.shape[s.dim + 1:]
                seg = out[s.offset:s.offset + shard]
                seg = seg.reshape((s.shape[s.dim] // L,) + rest)
                leaf = jnp.moveaxis(seg, 0, s.dim)
            leaves.append(leaf.astype(param_dtype))
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FsdpExchange:
    """Per-policy-group fused ZeRO-3 exchange over an ``FsdpLayout``.

    Sharded groups run ONE quantized reduce-scatter (phase 1 only — the
    next forward's fused parameter all-gather is the downlink); replicated
    groups run the full Algorithm 2 all-reduce via a ``GradientExchange``.
    ``exchange_bufs``/``residual_bufs`` share one key schedule so
    error-feedback residuals stay bit-consistent with what was sent.

    With ``intra_axes`` set (the two-level ICI/DCN mode, see
    ``core/comm/hierarchical.py``) every group's quantized phase runs over
    the inter (``pod``) axes only, on data already averaged in full
    precision over the fast intra axes:

      * sharded groups: the worker-major buffer ``(L_p, L_i, chunk)`` is
        fp-psum_scattered over the intra axes (each worker keeps the
        intra-mean rows destined for its data-column across pods), then
        quantized-reduce-scattered over ``pod`` — the DCN uplink shrinks
        by 1/L_i and each worker still ends with exactly its param-shard
        mean chunk;
      * replicated groups: fp intra scatter -> quantized Algorithm 2 over
        ``pod`` -> fp intra gather (``GradientExchange`` two-level mode).

    Error-feedback residuals then live on the intra SHARD — the quantized
    inter axis only — so ``ef_group_sizes`` shrinks by the same 1/L_i.
    """

    layout: FsdpLayout
    engines: Tuple[GradientExchange, ...]    # aligned with layout.groups;
                                             # sharded groups use only .qz
    dp_axes: Tuple[str, ...] = ("data",)     # FULL ordered dp tuple (the
                                             # parameter all-gather axes)
    intra_axes: Tuple[str, ...] = ()         # fast fp axes; () = flat
    n_intra: int = 1                         # static size of intra_axes
    use_kernels: bool = True
    pipeline_chunks: int = 1                 # bit-identical chunked schedule

    @classmethod
    def build(cls, policy: QuantPolicy, tree, axis_names, *, paths,
              shard_dims, n_shards: int, use_kernels: bool = True,
              max_chunk_elems: Optional[int] = None,
              intra_axes=(), n_intra: int = 1,
              pipeline_chunks: int = 1,
              by_rule: bool = False) -> "FsdpExchange":
        """``axis_names`` is the FULL ordered dp tuple; a non-empty
        ``intra_axes`` (with its static size ``n_intra``) switches on the
        two-level mode — the quantized collectives then run over the
        remaining (inter) axes only, which must precede the intra axes in
        ``axis_names`` (the worker-major rows are inter-major).
        ``max_chunk_elems`` caps replicated-group collectives only: a
        sharded group's buffer must reduce-scatter in one piece (its rows
        are the worker chunks). ``pipeline_chunks`` pipelines every
        group's quantized collective (bit-identical schedule knob, see
        ``GradientExchange``)."""
        dp = _names(axis_names)
        intra = tuple(intra_axes)
        inter = tuple(a for a in dp if a not in intra)
        if intra:
            if dp != inter + intra:
                raise ValueError(
                    f"inter axes {inter} must precede intra axes {intra} "
                    f"in the dp tuple {dp} (worker-major rows are "
                    f"inter-major)")
            if n_intra <= 1 or n_shards % n_intra:
                raise ValueError(
                    f"n_intra must be > 1 and divide n_shards="
                    f"{n_shards}, got {n_intra}")
        else:
            n_intra = 1
        layout = FsdpLayout.from_tree(tree, policy, paths=paths,
                                      shard_dims=shard_dims,
                                      n_shards=n_shards, by_rule=by_rule)
        engines = tuple(
            GradientExchange(
                g.cfg.to_quantizer(), inter,
                server_requant=g.cfg.server_requant,
                use_kernels=use_kernels,
                max_chunk_elems=None if g.sharded else max_chunk_elems,
                intra_axes=intra, pipeline_chunks=pipeline_chunks)
            for g in layout.groups)
        return cls(layout=layout, engines=engines, dp_axes=dp,
                   intra_axes=intra, n_intra=n_intra,
                   use_kernels=use_kernels, pipeline_chunks=pipeline_chunks)

    def specialize(self, policy: QuantPolicy) -> "FsdpExchange":
        """One phase's engine from a ``by_rule`` skeleton: reuse the
        bits-independent layout, rebuild only per-group quantizers from
        the phase's concrete configs. Group structure — and therefore
        ``ef_group_sizes`` shapes — is identical across phases (ramps
        never materialize to identity, so the None pattern is static
        too)."""
        layout = self.layout.with_configs(policy)
        engines = tuple(
            dataclasses.replace(
                eng, qz=g.cfg.to_quantizer(),
                server_requant=g.cfg.server_requant)
            for eng, g in zip(self.engines, layout.groups))
        return dataclasses.replace(self, layout=layout, engines=engines)

    @property
    def axis_names(self):
        return self.dp_axes

    @property
    def inter_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.dp_axes if a not in self.intra_axes)

    @property
    def n_inter(self) -> int:
        return self.layout.n_shards // self.n_intra

    @property
    def is_identity(self) -> bool:
        return all(e.qz.is_identity for e in self.engines)

    def _group_key(self, key: jax.Array, gi: int) -> jax.Array:
        # mirrors PartitionedExchange: a single group keeps the unfolded key
        return key if len(self.engines) == 1 else jax.random.fold_in(key, gi)

    def _split_wid(self, worker_id):
        """Combined dp worker id -> (inter_id, intra_id). The combined
        enumeration is inter-major (inter axes precede intra axes), so the
        split is arithmetic — no extra primal-context captures needed."""
        if not self.intra_axes:
            return worker_id, None
        return worker_id // self.n_intra, worker_id % self.n_intra

    # -- two-level sharded-group primitive ---------------------------------
    def _sharded_intra_scatter(self, buf: jnp.ndarray) -> jnp.ndarray:
        """(L_p*L_i*chunk,) worker-major group buffer -> this worker's
        (L_p*chunk,) fp intra-mean: the rows destined for its data-column
        across all pods (exactly what the quantized inter reduce-scatter
        consumes)."""
        L = self.layout.n_shards
        chunk = buf.shape[0] // L
        parts = buf.reshape(self.n_inter, self.n_intra, chunk)
        intra_mean = psum_scatter(
            parts, _names(self.intra_axes), scatter_dimension=1,
            tiled=False) / self.n_intra
        return intra_mean.reshape(-1)

    # -- distributed paths (inside shard_map over the dp axes) -------------
    def exchange_with_residuals(
        self, bufs: Sequence[jnp.ndarray], key: jax.Array, worker_id,
        ef_bufs=None,
    ) -> Tuple[Tuple[jnp.ndarray, ...], Optional[Tuple[Any, ...]]]:
        """The one-pass backward exchange: per-group local cotangent
        buffers -> (per-group outputs, new EF residuals or None).

        ``worker_id`` is the COMBINED dp axis index captured in the primal
        context (axis_index cannot lower in transposed contexts).
        ``ef_bufs`` (group-aligned, None entries for identity groups) are
        added to each group's quantizer input — the raw buffer in flat
        mode, the intra-mean shard in two-level mode — and the matching
        residuals e = b − Q⁻¹(Q(b)) come back as the second result.
        Sharded groups get this worker's (size/L,) mean chunk, replicated
        groups the full (size,) mean."""
        want_ef = ef_bufs is not None
        if not want_ef:
            ef_bufs = (None,) * len(self.engines)
        wid_inter, wid_intra = self._split_wid(worker_id)
        outs: List[jnp.ndarray] = []
        res: List[Optional[jnp.ndarray]] = []
        for gi, (eng, g) in enumerate(zip(self.engines, self.layout.groups)):
            gk = self._group_key(key, gi)
            ef = ef_bufs[gi]
            if not self.intra_axes:
                b = bufs[gi] if ef is None else bufs[gi] + ef
                if g.sharded:
                    outs.append(quantized_reduce_scatter_mean(
                        b, eng.qz, gk, self.dp_axes,
                        worker_id=worker_id, use_kernels=self.use_kernels,
                        pipeline_chunks=self.pipeline_chunks))
                    if want_ef and not eng.qz.is_identity:
                        res.append(b - local_qdq_comm_layout(
                            b, eng.qz, gk, self.dp_axes,
                            worker_id=worker_id,
                            use_kernels=self.use_kernels))
                    else:
                        res.append(None)
                else:
                    outs.append(eng.exchange_flat(b, gk,
                                                  worker_id=worker_id))
                    if want_ef and not eng.qz.is_identity:
                        res.append(b - eng.local_qdq_flat(
                            b, gk, worker_id=worker_id))
                    else:
                        res.append(None)
                continue
            # two-level: quantize only on the inter (pod) axes
            if g.sharded:
                shard = self._sharded_intra_scatter(bufs[gi])
                b = shard if ef is None else shard + ef
                kk = eng._intra_fold(gk, wid_intra)
                outs.append(quantized_reduce_scatter_mean(
                    b, eng.qz, kk, eng.axis_names, worker_id=wid_inter,
                    use_kernels=self.use_kernels,
                    pipeline_chunks=self.pipeline_chunks))
                if want_ef and not eng.qz.is_identity:
                    res.append(b - local_qdq_comm_layout(
                        b, eng.qz, kk, eng.axis_names, worker_id=wid_inter,
                        use_kernels=self.use_kernels))
                else:
                    res.append(None)
            else:
                shard, valid = eng.intra_scatter(bufs[gi])
                b = shard if ef is None else shard + ef
                mean_shard = eng.exchange_shard(
                    b, gk, valid=valid, worker_id=wid_inter,
                    intra_id=wid_intra)
                outs.append(eng.intra_gather(mean_shard, g.size))
                if want_ef and not eng.qz.is_identity:
                    res.append(b - eng.local_qdq_shard(
                        b, gk, valid=valid, worker_id=wid_inter,
                        intra_id=wid_intra))
                else:
                    res.append(None)
        return tuple(outs), (tuple(res) if want_ef else None)

    def exchange_bufs(self, bufs: Sequence[jnp.ndarray], key: jax.Array,
                      worker_id) -> Tuple[jnp.ndarray, ...]:
        """Per-group local cotangent buffers -> per-group outputs (see
        :meth:`exchange_with_residuals`, which the train step's backward
        uses to also stream the EF residuals in the same pass)."""
        return self.exchange_with_residuals(bufs, key, worker_id)[0]

    def residual_bufs(self, bufs: Sequence[jnp.ndarray], key: jax.Array,
                      worker_id) -> Tuple[Optional[jnp.ndarray], ...]:
        """Error-feedback residuals e = b − Q⁻¹(Q(b)), bit-consistent with
        ``exchange_bufs`` (same spans, same folded keys); identity groups
        have no quantization error and carry no residual buffer (None —
        matching ``ef_group_sizes``). Two-level residuals live on the
        intra-mean shard (this standalone path re-runs the fp intra
        scatter; the train step uses the combined
        :meth:`exchange_with_residuals` instead)."""
        wid_inter, wid_intra = self._split_wid(worker_id)
        res = []
        for gi, (eng, g) in enumerate(zip(self.engines, self.layout.groups)):
            if eng.qz.is_identity:
                res.append(None)
                continue
            gk = self._group_key(key, gi)
            if not self.intra_axes:
                if g.sharded:
                    local = local_qdq_comm_layout(
                        bufs[gi], eng.qz, gk, self.dp_axes,
                        worker_id=worker_id, use_kernels=self.use_kernels)
                else:
                    local = eng.local_qdq_flat(bufs[gi], gk,
                                               worker_id=worker_id)
                res.append(bufs[gi] - local)
                continue
            if g.sharded:
                shard = self._sharded_intra_scatter(bufs[gi])
                kk = eng._intra_fold(gk, wid_intra)
                res.append(shard - local_qdq_comm_layout(
                    shard, eng.qz, kk, eng.axis_names, worker_id=wid_inter,
                    use_kernels=self.use_kernels))
            else:
                shard, valid = eng.intra_scatter(bufs[gi])
                res.append(shard - eng.local_qdq_shard(
                    shard, gk, valid=valid, worker_id=wid_inter,
                    intra_id=wid_intra))
        return tuple(res)

    def ef_group_sizes(self) -> Tuple[Optional[int], ...]:
        """Per-group residual-buffer element counts, group-aligned: the
        quantizer-input length for quantized groups (the FULL group size in
        flat mode; the 1/L_i intra shard in two-level mode — residuals
        live on the quantized inter axis only), None for identity groups
        (an exact exchange leaves nothing to feed back — no buffer is
        allocated)."""
        sizes = []
        for eng, g in zip(self.engines, self.layout.groups):
            if eng.qz.is_identity:
                sizes.append(None)
            elif not self.intra_axes:
                sizes.append(g.size)
            elif g.sharded:
                sizes.append(g.size // self.n_intra)
            else:
                sizes.append(-(-g.size // self.n_intra))
        return tuple(sizes)

    # -- runtime statistics (the BitBudgetController feed) -----------------
    def group_stats_stored(self, grads_tree, ef=None) -> jnp.ndarray:
        """(n_groups, 3) f32 rows ``[sigma_sq, clip_frac, ef_norm_sq]``
        from the STORED-shard gradient tree the exchange hands back
        (each worker's param-shard slice of the across-worker mean).
        Unlike the replicated ``PartitionedExchange.group_stats`` (exact,
        pre-exchange) this is a post-exchange approximation — the mean is
        already quantized — but the controller only consumes RELATIVE
        group magnitudes, which survive. ``jax.lax.pmean`` over the dp
        axes yields the fleet view."""
        leaves = jax.tree_util.tree_leaves(grads_tree)
        assert len(leaves) == len(self.layout.slots), \
            (len(leaves), len(self.layout.slots))
        rows = []
        for gi, (eng, g) in enumerate(zip(self.engines, self.layout.groups)):
            buf = jnp.concatenate([
                leaves[i].astype(jnp.float32).reshape(-1)
                for i in g.leaf_ids])
            d_eff = wire.bucket_len(buf.shape[0], eng.qz.bucket_size)
            st = wire.encode_stats(eng.qz, buf, d_eff)
            e = None if ef is None else ef[gi]
            ef_sq = (jnp.zeros((), jnp.float32) if e is None
                     else jnp.sum(jnp.square(e.astype(jnp.float32))))
            rows.append(jnp.stack([st[0], st[1], ef_sq]))
        return jnp.stack(rows)

    # -- static cost accounting (benchmarks / tests) -----------------------
    def quantized_group_count(self) -> int:
        return sum(1 for e in self.engines if not e.qz.is_identity)

    def _group_link_stats(self, eng: GradientExchange, g) -> dict:
        return link_stats(
            eng.qz, g.size, n_intra=self.n_intra, n_inter=self.n_inter,
            two_level=bool(self.intra_axes),
            server_requant=eng.server_requant, sharded=g.sharded,
            max_chunk_elems=eng.max_chunk_elems,
            pipeline_chunks=eng.pipeline_chunks)

    def collective_launches(self) -> int:
        """Backward launches for one step: sharded groups pay phase 1 only
        (``GradientExchange.rs_stats``: 2 all_to_all per pipeline chunk;
        fp = 1 psum_scatter), replicated groups the full Algorithm 2
        count; two-level adds the fp intra scatter (and, for replicated
        groups, gather)."""
        if self.intra_axes:
            return int(sum(self._group_link_stats(eng, g)["launches"]
                           for eng, g in zip(self.engines,
                                             self.layout.groups)))
        L = self.layout.n_shards
        return sum(
            GradientExchange.rs_stats(
                eng.qz, g.size, L,
                pipeline_chunks=eng.pipeline_chunks)[0] if g.sharded
            else eng.collective_launches(g.size, L)
            for eng, g in zip(self.engines, self.layout.groups))

    def wire_bytes_per_worker(self) -> float:
        """Gradient bytes one worker transmits per step (sharded groups:
        phase-1 uplink only; the parameter all-gather downlink is bf16
        and belongs to the forward). Two-level mode counts both links
        (fp ICI + quantized DCN); see ``link_bytes_per_worker`` for the
        split."""
        if self.intra_axes:
            lb = self.link_bytes_per_worker()
            return lb["ici_bytes"] + lb["dcn_bytes"]
        L = self.layout.n_shards
        return sum(
            GradientExchange.rs_stats(eng.qz, g.size, L)[1] if g.sharded
            else eng.wire_bytes_per_worker(g.size, L)
            for eng, g in zip(self.engines, self.layout.groups))

    def link_bytes_per_worker(self) -> dict:
        """Per-link accounting {ici_bytes, dcn_bytes, dcn_q_bytes,
        launches} summed over groups (``exchange.link_stats`` model)."""
        total = {"ici_bytes": 0.0, "dcn_bytes": 0.0, "dcn_q_bytes": 0.0,
                 "launches": 0.0}
        for eng, g in zip(self.engines, self.layout.groups):
            st = self._group_link_stats(eng, g)
            for k in total:
                total[k] += st[k]
        return total


# ---------------------------------------------------------------------------
# the custom-VJP whole-tree gather
# ---------------------------------------------------------------------------

def make_fused_tree_gather(ex: FsdpExchange, *,
                           compute_dtype=jnp.bfloat16,
                           param_dtype=jnp.float32):
    """Returns ``gather(shard_params, ef_bufs, key) -> full_params``.

    fwd: one fused bf16 all-gather per sharded policy group (replicated
         leaves cast in place) — the whole-tree ZeRO-3 parameter broadcast.
    bwd: the fused policy-aware exchange — cotangents are flattened into
         per-group buffers, error-feedback residuals (if ``ef_bufs`` is not
         None) are added, each group runs its single quantized
         reduce-scatter (sharded) or all-reduce (replicated), and the
         result unflattens onto the STORED parameter shards. The NEW
         residual stream is returned as the cotangent of ``ef_bufs``, so

             value_and_grad(loss_fn, argnums=(0, 1))(params, ef)

         yields ``(sharded_grads, new_ef)`` in one backward pass; the
         train step persists ``new_ef`` in ``TrainState.ef``.

    Pass ``ef_bufs=None`` to disable error feedback (no residual compute,
    no residual cotangent). Both directions run under the ``exchange``
    named scope."""
    names = _names(ex.axis_names)

    @jax.custom_vjp
    @jax.named_scope("exchange")
    def gather(shard_params, ef_bufs, key):
        del ef_bufs, key
        return ex.layout.gather_full(shard_params, names,
                                     compute_dtype=compute_dtype)

    def fwd(shard_params, ef_bufs, key):
        # capture the worker id in the PRIMAL context: axis_index cannot
        # lower from the transposed/hoisted backward context
        wid = lax.axis_index(names)
        return gather(shard_params, ef_bufs, key), (key, wid, ef_bufs)

    @jax.named_scope("exchange")
    def bwd(res, g_full):
        key, wid, ef_bufs = res
        bufs = ex.layout.flatten_groups(g_full)
        # e_{t-1} compensates this step's send: b = g + e, added to each
        # group's quantizer input (the raw buffer in flat mode, the
        # intra-mean shard in two-level mode — identity groups carry no
        # residual buffer; see ef_group_sizes). One pass computes both the
        # exchange outputs and the new residual stream.
        outs, new_ef = ex.exchange_with_residuals(bufs, key, wid, ef_bufs)
        shard_ct = ex.layout.unflatten_outputs(outs, param_dtype=param_dtype)
        key_ct = np.zeros(key.shape, dtype=jax.dtypes.float0)
        return shard_ct, new_ef, key_ct

    gather.defvjp(fwd, bwd)
    return gather
