"""Distributed train step: shard_map(manual=dp axes, auto=model when
model > 1, else manual over every axis) with the paper's quantized
gradient exchange at the FSDP boundary.

Layout (ZeRO-3):
  * every f32 master-param leaf is sharded over the combined dp axes
    (``pod`` x ``data``) along its d_model-sized dim, and over ``model``
    along its largest remaining dim (tensor/expert parallelism — XLA auto);
  * with the FUSED exchange (``fused_exchange=True``, pure-dp meshes) the
    whole parameter tree is gathered bf16 up front through ONE custom-VJP
    (``core/comm/fsdp_exchange.py``): forward = one fused all-gather per
    policy group, backward = one fused quantized reduce-scatter per
    sharded group (+ one fused quantized all-reduce per replicated group)
    with an error-feedback residual stream persisted in ``TrainState.ef``
    — O(#policy groups) gradient collectives per step;
  * with the per-leaf fallback (``fused_exchange=False``, or whenever
    ``model`` parallelism is active — flattening TP-sharded cotangents
    into a dp buffer would replicate them over ``model``) each leaf is
    gathered bf16 at its point of use (per scanned layer group) through a
    custom-VJP whose backward is the quantized reduce-scatter;
  * leaves with no dp-divisible dim stay replicated and exchange gradients
    through the quantized all-reduce (Algorithm 2 incl. server re-quant).

``mode='replicated'`` keeps all parameters replicated and is the
paper-faithful Algorithm 2 loop used by the convergence benchmarks (with a
1-device mesh it degenerates to the paper's single-machine experiments:
the gradient is quantize->dequantized locally every step).

On multi-pod meshes ``TrainConfig.hierarchy`` ("auto" by default) selects
the two-level ICI/DCN topology: every fused exchange first averages in
full precision over the fast intra-pod ``data`` axis and runs the
quantized phases only over the slow inter-pod ``pod`` axis, with EF
residuals living on the quantized intra-shard (see
``core/comm/hierarchical.py`` and EXPERIMENTS.md).

Quantization is configured through ``TrainConfig.policy`` (a
``repro.core.QuantPolicy`` or anything coercible to one): each leaf's
scheme is resolved from its gather path, the replicated fused exchange
partitions leaves into per-policy-group segments (O(#groups) collectives
per step), and fsdp gathers quantize each leaf's backward with its
resolved quantizer. (The historical ``TrainConfig.quant`` uniform alias
is gone — passing it raises with a pointer at ``policy=``.)

ADAPTIVE BIT BUDGET: ``ScheduledTrainStep`` drives a ``BitSchedule`` /
``BitBudgetController`` (``repro.core.policy``) over this machinery —
per-group wire bit-width becomes a function of the training step via a
recompile-on-phase-boundary design: one bits-independent engine skeleton
(leaves grouped by policy RULE, so EF-residual shapes are invariant),
specialized per phase into concrete engines held in an LRU keyed by the
bits tuple. Within a phase the step is bit-identical to the equivalent
static policy; bit-width is never traced, so the one-``pallas_call``
property is untouched.
"""
from __future__ import annotations

import dataclasses
import warnings
import zlib
from collections import OrderedDict
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import QuantConfig, QuantPolicy, comm
from repro.models.model import LM
from repro.optim import optimizers as opt_lib
from repro.optim.schedule import constant_lr
from repro.train.state import OuterState, TrainState
from repro.utils.compat import shard_map
from repro.utils.sharding import (choose_fsdp_dim, dp_axis_names,
                                  spec_dp_dim)

# key-fold salt separating the fused whole-tree exchange stream from the
# legacy per-leaf (crc32-of-path) streams
_FUSED_SALT = zlib.crc32(b"fused_exchange") & 0x7FFFFFFF


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    # ``policy`` is the sole quantization surface: a QuantPolicy (or
    # anything QuantPolicy.coerce accepts — policy string, dict,
    # QuantConfig). The historical ``quant`` uniform alias is REMOVED;
    # the sentinel below turns old call sites into a clear error.
    policy: Optional[Any] = None
    quant: Any = None               # REMOVED — kept only to fail loudly
    mode: str = "fsdp"              # fsdp | replicated
    hierarchy: str = "auto"         # flat | two_level | two_level_async |
                                    # auto: two_level quantizes only over
                                    # the slow inter-pod ("pod", DCN) axes
                                    # after a full-precision intra-pod
                                    # mean — "auto" switches it on
                                    # whenever the dp mesh has >= 2 axes;
                                    # two_level_async additionally makes
                                    # the hierarchy TEMPORAL (see
                                    # local_steps below and
                                    # core/comm/hierarchical.py)
    local_steps: int = 1            # two_level_async window H: run H
                                    # inner optimizer steps synced only
                                    # over the fast intra (ICI) axes,
                                    # then ONE quantized outer exchange
                                    # of the window's parameter delta
                                    # over the DCN axes feeding the outer
                                    # optimizer below. H=1 resolves to
                                    # the literal two_level path
                                    # (bit-identity by construction).
    optimizer: str = "sgd"          # sgd | adamw  (paper: SGD+momentum 0.9)
    momentum: float = 0.9
    weight_decay: float = 0.0
    outer_optimizer: str = "nesterov"   # nesterov | sgd — applied to the
                                        # outer pseudo-gradient
                                        # (anchor - local params) at sync
                                        # steps (two_level_async only)
    outer_lr: float = 0.7           # DiLoCo-style outer step size
    outer_momentum: float = 0.9
    use_kernels: bool = True
    error_feedback: bool = False    # beyond-paper: EF residual accumulation
                                    # (replicated mode + fused fsdp;
                                    # see EXPERIMENTS.md)
    fused_exchange: bool = True     # one flat-buffer collective per policy
                                    # group per step (False = legacy
                                    # per-leaf exchange; fsdp also falls
                                    # back per-leaf when n_model > 1)
    exchange_chunk_elems: Optional[int] = None  # size cap per fused
                                                # collective (memory knob)
    pipeline_chunks: int = 1        # split each fused exchange into K
                                    # bucket-row chunks so chunk k's
                                    # collective overlaps chunk k+1's
                                    # encode — bit-identical to K=1
                                    # (latency knob; see
                                    # core/comm/collectives.py)
    group_by_rule: bool = False     # key fused-exchange groups on the
                                    # policy RULE index instead of the
                                    # resolved QuantConfig: same partition
                                    # when configs are all distinct, but
                                    # invariant under per-phase config
                                    # re-materialization — what the
                                    # bit-schedule skeleton/specialize
                                    # machinery needs so EF shapes survive
                                    # phase boundaries
    collect_stats: bool = False     # emit an ``exchange_stats`` metric:
                                    # (n_groups, 3) f32 [sigma_sq,
                                    # clip_frac, ef_norm_sq] per policy
                                    # group, pmean'd over dp — the
                                    # BitBudgetController's feed (fused
                                    # paths only; per-leaf paths have no
                                    # group buffers to measure)
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.quant is not None:
            raise ValueError(
                "TrainConfig.quant was removed — pass policy= instead "
                "(QuantPolicy.coerce accepts a QuantConfig, a scheme "
                "name, a policy string like 'embed=fp,default=orq-9', or "
                "a dict); a uniform policy is just "
                "policy=QuantConfig(name=...)")
        if self.local_steps < 1:
            raise ValueError(
                f"local_steps must be >= 1, got {self.local_steps}")
        if self.local_steps > 1 and self.hierarchy != "two_level_async":
            raise ValueError(
                "local_steps > 1 is the two_level_async inner-window "
                "length — set hierarchy='two_level_async' (got "
                f"hierarchy={self.hierarchy!r})")
        if self.hierarchy == "two_level_async":
            # the temporal tier rides the fused replicated two-level
            # machinery; silently falling back to a per-step exchange
            # would change training semantics, so validation is strict
            if self.mode != "replicated":
                raise ValueError(
                    "hierarchy='two_level_async' needs mode='replicated' "
                    "(the outer delta exchange rides the fused replicated "
                    f"engines), got mode={self.mode!r}")
            if not self.fused_exchange:
                raise ValueError(
                    "hierarchy='two_level_async' needs the fused exchange "
                    "(fused_exchange=True)")
        if self.outer_optimizer not in ("nesterov", "sgd"):
            raise ValueError(
                "outer_optimizer must be 'nesterov' or 'sgd', got "
                f"{self.outer_optimizer!r}")

    def resolved_policy(self) -> QuantPolicy:
        """The effective QuantPolicy (``policy``, else uniform fp)."""
        if self.policy is None:
            return QuantPolicy.uniform(QuantConfig(name="fp"))
        return QuantPolicy.coerce(self.policy)


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    specs: Any                      # pytree of PartitionSpec, aligned to params
    paths: Any                      # pytree of path strings
    gather_dims: Dict[str, Optional[int]]   # path -> fsdp dim (slice coords)
    tp_dims: Dict[str, Optional[int]]       # path -> TP dim (slice coords)
    dp_axes: Tuple[str, ...]
    n_dp: int
    n_model: int

    def shardings(self, mesh):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), self.specs)

    def manual_specs(self):
        """in_specs for shard_map: only the manual (dp) part of each spec."""
        dp = set(self.dp_axes)

        def strip(spec):
            ent = []
            for e in spec:
                if isinstance(e, (tuple, list)):
                    kept = tuple(a for a in e if a in dp)
                    ent.append(kept if kept else None)
                else:
                    ent.append(e if e in dp else None)
            return P(*ent)

        return jax.tree_util.tree_map(
            strip, self.specs, is_leaf=lambda x: isinstance(x, P))

    def full_shard_dims(self) -> Dict[str, Optional[int]]:
        """path -> dp-shard dim in FULL leaf coordinates (stacked leading
        dims included; ``gather_dims`` is in per-repeat slice coords). The
        fused fsdp exchange lays its group buffers out by these."""
        specs = jax.tree_util.tree_leaves(
            self.specs, is_leaf=lambda x: isinstance(x, P))
        paths = jax.tree_util.tree_leaves(self.paths)
        return {p: spec_dp_dim(s, self.dp_axes)
                for p, s in zip(paths, specs)}


def _manual_axes(mesh, plan: "ShardingPlan") -> Tuple[str, ...]:
    """The train step's manual ``shard_map`` axes: every mesh axis, unless
    tensor parallelism needs ``model`` left to the SPMD partitioner. The
    exchange's Pallas kernels cannot be auto-partitioned, so a size-1
    ``model`` axis left auto stops the step from compiling on a TPU."""
    return tuple(mesh.axis_names) if plan.n_model == 1 else plan.dp_axes


def _dp_axes(mesh) -> Tuple[str, ...]:
    # the single shared dp-axis selection (utils/sharding.dp_axis_names):
    # the hierarchy split below relies on this exact ordering, so per-file
    # copies of the tuple comprehension are an actual correctness bug
    return dp_axis_names(mesh)


def _async_local_steps(tcfg: TrainConfig, dp_axes) -> int:
    """Effective inner-window length H: > 1 only when the temporal
    ``two_level_async`` hierarchy is active after resolution (H=1 resolves
    to the literal ``two_level`` path, so everything below behaves as if
    the temporal tier didn't exist — the bit-identity anchor)."""
    if comm.resolve_hierarchy(tcfg.hierarchy, dp_axes,
                              tcfg.local_steps) == "two_level_async":
        return tcfg.local_steps
    return 1


def _exchange_axes(tcfg: TrainConfig, dp_axes: Tuple[str, ...], mesh,
                   plan: Optional["ShardingPlan"] = None
                   ) -> Tuple[Tuple[str, ...], Tuple[str, ...], int]:
    """Resolve ``tcfg.hierarchy`` against the mesh and the active exchange
    path: ``(intra_axes, inter_axes, n_intra)``. Flat mode (and every
    degenerate case) returns ``((), dp_axes, 1)``.

    Two-level needs the fused engines (the per-leaf fallbacks keep the
    flat combined-axis exchange): an explicitly requested "two_level" that
    cannot run warns; "auto" falls back silently.

    ``two_level_async`` with H > 1 validates strictly instead of falling
    back: it needs an inter-pod axis to run the outer sync over, and
    dropping the sync silently would train the pods independently. When
    the intra half degenerates (no ``data`` axis, or size 1) the OUTER
    exchange runs flat over all dp axes — inner steps then sync over
    nothing, which is plain DiLoCo local SGD.
    """
    flat = (), tuple(dp_axes), 1
    if _async_local_steps(tcfg, dp_axes) > 1:
        if not dp_axes or not any(a in comm.INTER_AXIS_NAMES
                                  for a in dp_axes):
            raise ValueError(
                "hierarchy='two_level_async' with local_steps="
                f"{tcfg.local_steps} needs an inter-pod dp axis "
                f"({comm.INTER_AXIS_NAMES}) to run the outer sync over — "
                f"dp axes are {tuple(dp_axes)}; build the mesh with "
                "--pods >= 2")
        intra, inter = comm.split_dp_axes(dp_axes, "two_level")
        if not intra:
            return flat
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n_intra = int(np.prod([sizes[a] for a in intra]))
        return flat if n_intra <= 1 else (intra, inter, n_intra)
    if not dp_axes:
        return flat
    intra, inter = comm.split_dp_axes(dp_axes, tcfg.hierarchy)
    if not intra:
        return flat
    if tcfg.mode == "replicated":
        fused_ok = tcfg.fused_exchange
        why = "fused_exchange=False (per-leaf replicated exchange)"
    else:
        fused_ok = plan is not None and _fused_fsdp_active(tcfg, plan)
        why = "the per-leaf fsdp gather path (fused_exchange=False or " \
              "model parallelism active)"
    if not fused_ok:
        if tcfg.hierarchy == "two_level":
            warnings.warn(
                f"hierarchy='two_level' needs the fused exchange but {why} "
                f"is selected — falling back to the flat combined-axis "
                f"exchange", stacklevel=2)
        return flat
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_intra = int(np.prod([sizes[a] for a in intra]))
    if n_intra <= 1:
        return flat
    return intra, inter, n_intra


def plan_sharding(model: LM, aparams, mesh) -> ShardingPlan:
    """Choose per-leaf FSDP + TP dims from abstract parameter shapes."""
    return plan_sharding_shapes(
        model, aparams, dp_axes=_dp_axes(mesh),
        axis_sizes=dict(zip(mesh.axis_names, mesh.devices.shape)))


def plan_sharding_shapes(model: LM, aparams, *, dp_axes: Tuple[str, ...],
                         axis_sizes: Dict[str, int]) -> ShardingPlan:
    """Mesh-free core of :func:`plan_sharding`: the plan depends only on
    the axis names/sizes, so static accounting callers (benchmarks) can
    build one without constructing a device mesh."""
    cfg = model.cfg
    n_dp = int(np.prod([axis_sizes[a] for a in dp_axes])) if dp_axes else 1
    n_model = axis_sizes.get("model", 1)
    paths = model.param_paths(aparams)
    gather_dims: Dict[str, Optional[int]] = {}
    tp_dims: Dict[str, Optional[int]] = {}

    def leaf_spec(path: str, leaf):
        shape = leaf.shape
        stacked = path.startswith("g") or path.startswith("enc/g")
        off = 1 if stacked else 0
        slice_shape = shape[off:]
        # no dp axes (e.g. a model-only mesh) -> nothing to shard over
        fdim = (choose_fsdp_dim(slice_shape, n_dp,
                                prefer_sizes=(cfg.d_model,))
                if dp_axes else None)
        gather_dims[path] = fdim
        # TP dim: prefer the experts dim, else the largest remaining dim
        tp_candidates = [
            i for i, s in enumerate(slice_shape)
            if i != fdim and s % n_model == 0 and s >= n_model
        ]
        tdim = None
        if tp_candidates:
            n_exp = cfg.moe.num_experts if cfg.moe else -1
            pref = [i for i in tp_candidates if slice_shape[i] == n_exp]
            tdim = pref[0] if pref else max(tp_candidates,
                                            key=lambda i: slice_shape[i])
        tp_dims[path] = tdim if n_model > 1 else None
        ent = [None] * len(shape)
        if fdim is not None:
            ent[off + fdim] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        if tdim is not None and n_model > 1:
            ent[off + tdim] = "model"
        return P(*ent)

    specs = jax.tree_util.tree_map(leaf_spec, paths, aparams)
    return ShardingPlan(specs=specs, paths=paths, gather_dims=gather_dims,
                        tp_dims=tp_dims, dp_axes=dp_axes, n_dp=n_dp,
                        n_model=n_model)


def _make_optimizer(tcfg: TrainConfig):
    if tcfg.optimizer == "sgd":
        return opt_lib.sgd_momentum(momentum=tcfg.momentum,
                                    weight_decay=tcfg.weight_decay)
    if tcfg.optimizer == "adamw":
        return opt_lib.adamw(weight_decay=tcfg.weight_decay)
    raise ValueError(tcfg.optimizer)


def _fused_fsdp_active(tcfg: TrainConfig, plan: ShardingPlan) -> bool:
    """Whether the fused whole-tree fsdp exchange runs. Pure-dp meshes
    only: flattening TP-sharded cotangents into a dp buffer would force
    XLA to replicate them over ``model``, so TP keeps the per-leaf gather
    (with its nested-manual trick)."""
    return (tcfg.mode == "fsdp" and tcfg.fused_exchange
            and bool(plan.dp_axes) and plan.n_model == 1)


def _ef_group_sizes(aparams, tcfg: TrainConfig, plan: ShardingPlan,
                    mesh) -> Optional[Tuple[Optional[int], ...]]:
    """Group-aligned per-worker residual-buffer sizes for the TUPLE form
    of error feedback (fused fsdp, and the two-level fused replicated
    exchange whose residuals live on the quantized inter axis), with None
    entries for identity groups. Returns None overall when EF is off, a
    fully-fp policy leaves nothing to feed back, or EF rides the
    params-shaped tree instead (flat replicated mode)."""
    if not tcfg.error_feedback:
        return None
    intra, inter, n_intra = _exchange_axes(tcfg, plan.dp_axes, mesh, plan)
    if tcfg.mode == "fsdp":
        if not _fused_fsdp_active(tcfg, plan):
            return None
        fex = comm.FsdpExchange.build(
            tcfg.resolved_policy(), aparams, plan.dp_axes, paths=plan.paths,
            shard_dims=plan.full_shard_dims(), n_shards=plan.n_dp,
            intra_axes=intra, n_intra=n_intra, by_rule=tcfg.group_by_rule)
        sizes = fex.ef_group_sizes()
        return sizes if any(n is not None for n in sizes) else None
    if not intra and _async_local_steps(tcfg, plan.dp_axes) <= 1:
        return None          # flat replicated EF stays params-shaped
    # two-level shards — or, in two_level_async mode with a degenerate
    # intra half (n_intra == 1), full per-worker group buffers: the outer
    # delta stream only exists at sync steps, so its residuals live in
    # group-aligned buffers either way, never a params-shaped tree
    pex = comm.PartitionedExchange.build(
        tcfg.resolved_policy(), aparams, inter, paths=plan.paths,
        intra_axes=intra, by_rule=tcfg.group_by_rule)
    sizes = pex.ef_shard_sizes(n_intra)
    return sizes if any(n is not None for n in sizes) else None


def init_state(model: LM, mesh, tcfg: TrainConfig, key) -> TrainState:
    """Initialize TrainState with plan-consistent shardings.

    In ``two_level_async`` mode (H > 1) params/opt leaves are STACKED with
    a leading worker axis sharded over the dp axes: inner steps make them
    pod-divergent, and the stacked layout keeps every pod's copy visible
    to shardings, ``device_get`` and checkpoints (required for bit-exact
    mid-window resume). The replicated outer anchor/momentum live in
    ``TrainState.outer``.
    """
    aparams = jax.eval_shape(model.init, key)
    plan = plan_sharding(model, aparams, mesh)
    optimizer = _make_optimizer(tcfg)
    ef_sizes = _ef_group_sizes(aparams, tcfg, plan, mesh)
    dp_ent = (plan.dp_axes if len(plan.dp_axes) > 1
              else (plan.dp_axes[0] if plan.dp_axes else None))
    h_async = _async_local_steps(tcfg, plan.dp_axes)
    if h_async > 1:
        _exchange_axes(tcfg, plan.dp_axes, mesh, plan)  # strict validation

    def build(key):
        params = model.init(key)
        if ef_sizes is not None:
            # per-worker residual buffers, stacked over the dp axes
            # (group-aligned; identity groups carry None). Covers fused
            # fsdp AND the two-level replicated exchange, whose residuals
            # are intra shards on the quantized inter axis.
            ef = tuple(None if n is None
                       else jnp.zeros((plan.n_dp * n,), jnp.float32)
                       for n in ef_sizes)
        elif (tcfg.error_feedback and tcfg.mode == "replicated"
              and h_async <= 1):
            ef = jax.tree_util.tree_map(jnp.zeros_like, params)
        else:
            ef = None
        if h_async > 1:
            def stack(t):
                return jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x[None],
                                               (plan.n_dp,) + x.shape), t)

            return TrainState(
                params=stack(params), opt=stack(optimizer.init(params)),
                step=jnp.int32(0), ef=ef,
                outer=OuterState(
                    anchor=params,
                    mom=jax.tree_util.tree_map(
                        lambda p: jnp.zeros(p.shape, jnp.float32),
                        params)))
        return TrainState(params=params, opt=optimizer.init(params),
                          step=jnp.int32(0), ef=ef)

    if tcfg.mode == "replicated":
        # replicated over the whole mesh, not left on the default device
        out_sh = NamedSharding(mesh, P())
        if h_async > 1:
            rep = NamedSharding(mesh, P())
            stk = NamedSharding(mesh, P(dp_ent))
            aout = jax.eval_shape(build, key)
            out_sh = jax.tree_util.tree_map(lambda _: rep, aout)
            out_sh = out_sh._replace(
                params=jax.tree_util.tree_map(lambda _: stk, aout.params),
                opt=jax.tree_util.tree_map(lambda _: stk, aout.opt),
                ef=(None if aout.ef is None else jax.tree_util.tree_map(
                    lambda _: stk, aout.ef)))
    else:
        psh = plan.shardings(mesh)
        out_sh = TrainState(params=psh,
                            opt=jax.tree_util.tree_map(lambda s: s, psh),
                            step=NamedSharding(mesh, P()))
        if tcfg.optimizer == "adamw":
            out_sh = out_sh._replace(opt=opt_lib.AdamState(
                mu=psh, nu=psh, count=NamedSharding(mesh, P())))
        if ef_sizes is not None:
            out_sh = out_sh._replace(ef=tuple(
                None if n is None else NamedSharding(mesh, P(dp_ent))
                for n in ef_sizes))
    return jax.jit(build, out_shardings=out_sh)(key)


class ExchangeEngines(NamedTuple):
    """The exchange machinery one train step is built around. Produced
    by :func:`exchange_engines` and consumed by both
    :func:`make_train_step` and the ``repro.analysis`` auditor — the
    collective-budget expectations are derived from these SAME objects,
    so the accounting and the traced step cannot drift apart."""

    pex: Any                        # PartitionedExchange (replicated path)
    fex: Any                        # FsdpExchange | None (fused fsdp path)
    plan: Any                       # ShardingPlan
    policy: Any                     # resolved QuantPolicy
    intra_axes: Tuple[str, ...]     # fast fp (ICI) axes; () = flat
    inter_axes: Tuple[str, ...]     # quantized (DCN) axes
    n_intra: int
    fused_fsdp: bool


def exchange_engines(model: LM, mesh, tcfg: TrainConfig,
                     aparams=None) -> ExchangeEngines:
    """Build the exchange engines exactly as :func:`make_train_step`
    wires them (same policy resolution, hierarchy split, chunking)."""
    dp_axes = _dp_axes(mesh)
    if aparams is None:
        aparams = jax.eval_shape(model.init, jax.random.key(0))
    plan = plan_sharding(model, aparams, mesh)
    policy = tcfg.resolved_policy()
    # hierarchy resolution: two_level splits the dp axes into fast intra
    # (ICI, full-precision mean) and slow inter (DCN, quantized Algorithm
    # 2) halves; flat (and every degenerate case) keeps intra empty and
    # the engines behave exactly as before
    intra_axes, inter_axes, n_intra = _exchange_axes(tcfg, dp_axes, mesh,
                                                     plan)
    # partitioned fused engine: leaves grouped by resolved quantizer into
    # contiguous segments, one fused exchange per policy group (a uniform
    # policy degenerates to the single-group engine, bit-identical to the
    # pre-policy fused exchange)
    pex = comm.PartitionedExchange.build(
        policy, aparams, inter_axes, paths=plan.paths,
        use_kernels=tcfg.use_kernels,
        max_chunk_elems=tcfg.exchange_chunk_elems,
        intra_axes=intra_axes,
        pipeline_chunks=tcfg.pipeline_chunks,
        by_rule=tcfg.group_by_rule)
    # fused fsdp engine: ONE custom-VJP over the whole sharded tree whose
    # forward is a fused per-group parameter all-gather and whose backward
    # is one fused quantized reduce-scatter per sharded policy group (+
    # one fused all-reduce per replicated group) with the EF residual
    # stream riding the residual-buffer cotangent — O(#groups) gradient
    # collectives per step (see core/comm/fsdp_exchange.py)
    fused_fsdp = _fused_fsdp_active(tcfg, plan)
    fex = None
    if fused_fsdp:
        fex = comm.FsdpExchange.build(
            policy, aparams, dp_axes, paths=plan.paths,
            shard_dims=plan.full_shard_dims(), n_shards=plan.n_dp,
            use_kernels=tcfg.use_kernels,
            max_chunk_elems=tcfg.exchange_chunk_elems,
            intra_axes=intra_axes, n_intra=n_intra,
            pipeline_chunks=tcfg.pipeline_chunks,
            by_rule=tcfg.group_by_rule)
    return ExchangeEngines(pex=pex, fex=fex, plan=plan, policy=policy,
                           intra_axes=intra_axes, inter_axes=inter_axes,
                           n_intra=n_intra, fused_fsdp=fused_fsdp)


def specialize_engines(eng: ExchangeEngines,
                       policy: QuantPolicy) -> ExchangeEngines:
    """Re-materialize a by-rule-grouped engine bundle for a new concrete
    policy WITHOUT rebuilding layouts: same groups, same order, same EF
    shapes — only the per-group QuantConfigs/quantizers change. This is
    the per-phase specialization step of the adaptive bit schedule."""
    pex = eng.pex.specialize(policy)
    fex = eng.fex.specialize(policy) if eng.fex is not None else None
    return eng._replace(pex=pex, fex=fex, policy=policy)


def make_train_step(model: LM, mesh, tcfg: TrainConfig, lr_fn=None,
                    aparams=None, engines: Optional[ExchangeEngines] = None):
    """Returns (step_fn, plan). step_fn(state, batch, key) ->
    (state, metrics); jit-compiled shard_map over the dp axes.

    ``engines`` optionally supplies a prebuilt :class:`ExchangeEngines`
    (e.g. a specialized per-phase bundle from :func:`specialize_engines`);
    its policy must match ``tcfg.resolved_policy()``."""
    lr_fn = lr_fn or constant_lr(0.1)
    cfg = model.cfg
    dp_axes = _dp_axes(mesh)
    if aparams is None:
        aparams = jax.eval_shape(model.init, jax.random.key(0))
    eng = (engines if engines is not None
           else exchange_engines(model, mesh, tcfg, aparams=aparams))
    plan, policy = eng.plan, eng.policy
    optimizer = _make_optimizer(tcfg)
    intra_axes, inter_axes, n_intra = (eng.intra_axes, eng.inter_axes,
                                       eng.n_intra)
    two_level = bool(intra_axes)
    pex, fex, fused_fsdp = eng.pex, eng.fex, eng.fused_fsdp
    tree_gather = None
    if fused_fsdp:
        if fex.layout.size > 1_000_000_000:
            # the fused path holds the whole gathered bf16 tree + full
            # f32 cotangent buffers per device during the step, vs the
            # per-leaf path's one-scanned-layer-group residency — make
            # the trade-off visible before a 27B+ config OOMs on it
            warnings.warn(
                f"fused fsdp exchange gathers all {fex.layout.size:.2e} "
                f"parameters per device each step (O(full model) live "
                f"memory); if parameter-memory-bound, set "
                f"fused_exchange=False for per-layer-group ZeRO-3 "
                f"residency (see EXPERIMENTS.md)", stacklevel=2)
        tree_gather = comm.make_fused_tree_gather(
            fex, compute_dtype=tcfg.compute_dtype)
    # a fully-fp policy has nothing to feed back: no ef buffers at all
    # (matches _fsdp_ef_group_sizes / init_state)
    use_fsdp_ef = (tcfg.error_feedback and fused_fsdp
                   and not fex.is_identity)
    if tcfg.error_feedback and tcfg.mode == "fsdp" and not fused_fsdp:
        warnings.warn(
            "error_feedback needs the fused fsdp exchange (fused_exchange="
            "True on a pure-dp mesh); the per-leaf fsdp path has no "
            "residual stream — ignoring error_feedback", stacklevel=2)
    collect_stats = tcfg.collect_stats
    if collect_stats and not (
            fused_fsdp or (tcfg.mode == "replicated"
                           and tcfg.fused_exchange)):
        warnings.warn(
            "collect_stats needs a fused exchange path (there are no "
            "per-group wire buffers to measure on the per-leaf paths) — "
            "ignoring collect_stats", stacklevel=2)
        collect_stats = False

    leaf_qz_cache: Dict[QuantConfig, Any] = {}

    def resolve_leaf(path):
        """(QuantConfig, Quantizer) for one leaf path under the policy."""
        cfg = policy.resolve(path)
        if cfg not in leaf_qz_cache:
            leaf_qz_cache[cfg] = cfg.to_quantizer()
        return cfg, leaf_qz_cache[cfg]

    def make_gather_fn(step_key):
        if tcfg.mode == "replicated":
            return None  # identity gather inside model

        cache: Dict[str, Any] = {}

        def gather(path, leaf, salt):
            dim = plan.gather_dims.get(path)
            if path not in cache:
                # each leaf's backward quantizes with its POLICY-resolved
                # quantizer (mixed-precision gradient compression in fsdp
                # mode rides the per-leaf gather)
                cfg_l, qz_l = resolve_leaf(path)
                if dim is None:
                    cache[path] = comm.make_replicated_gather(
                        qz_l, dp_axes, compute_dtype=tcfg.compute_dtype,
                        server_requant=cfg_l.server_requant,
                        use_kernels=tcfg.use_kernels)
                else:
                    cache[path] = comm.make_fsdp_gather(
                        qz_l, dp_axes, dim=dim,
                        tp_dim=plan.tp_dims.get(path),
                        compute_dtype=tcfg.compute_dtype,
                        use_kernels=tcfg.use_kernels)
            key = jax.random.fold_in(step_key,
                                     zlib.crc32(path.encode()) & 0x7FFFFFFF)
            key = jax.random.fold_in(key, salt)
            return cache[path](leaf, key)

        return gather

    def local_step(state: TrainState, batch, key):
        step_key = jax.random.fold_in(key, state.step)

        if fused_fsdp:
            # whole-tree fused gather/exchange: grads come back aligned
            # with the STORED parameter shards; the new EF residuals ride
            # the cotangent of the residual-buffer argument
            k = jax.random.fold_in(step_key, _FUSED_SALT)

            def fsdp_loss_fn(params, ef_bufs):
                return model.loss(tree_gather(params, ef_bufs, k), batch)

            if use_fsdp_ef:
                (loss, metrics), (grads, new_ef) = jax.value_and_grad(
                    fsdp_loss_fn, argnums=(0, 1), has_aux=True)(
                        state.params, state.ef)
            else:
                (loss, metrics), grads = jax.value_and_grad(
                    fsdp_loss_fn, has_aux=True)(state.params, None)
                new_ef = state.ef
            stats = None
            if collect_stats:
                # post-exchange approximation from the stored shards (the
                # pre-exchange cotangent buffers live inside the custom
                # VJP); pmean over dp in _finish gives the fleet view
                stats = fex.group_stats_stored(
                    grads, new_ef if use_fsdp_ef else None)
            return _finish(state, grads, new_ef, loss, metrics, stats)

        gather = make_gather_fn(step_key)

        def loss_fn(params):
            if gather is None:
                return model.loss(params, batch)
            return model.loss(params, batch, gather=gather)

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)

        with jax.named_scope("exchange"):
            grads, new_ef, stats = exchange_grads(state, grads, step_key)
        return _finish(state, grads, new_ef, loss, metrics, stats)

    def exchange_grads(state: TrainState, grads, step_key):
        """The exchange of the replicated and single-device paths: EF
        compensation, the quantized all-reduce (or local qdq) and the new
        residuals -> ``(grads, new_ef, stats)``."""
        new_ef = state.ef
        stats = None
        use_ef = (tcfg.error_feedback and state.ef is not None
                  and not pex.is_identity)
        if use_ef and not two_level:
            # error feedback: compensate last step's local quantization
            # error before quantizing (Karimireddy et al. line of work,
            # cited by the paper as complementary). Two-level residuals
            # are intra SHARDS (added after the fp intra scatter below),
            # not a params-shaped tree.
            grads = jax.tree_util.tree_map(
                lambda g, e: g + e.astype(g.dtype), grads, state.ef)

        if tcfg.mode == "replicated" and dp_axes:
            if tcfg.fused_exchange and two_level:
                # two-level fused exchange: fp intra-pod scatter-mean ->
                # quantized Algorithm 2 on the shard over the inter (pod)
                # axes only -> fp intra gather. EF residuals live on the
                # quantized shard (per-group tuple in TrainState.ef).
                k = jax.random.fold_in(step_key, _FUSED_SALT)
                bufs = pex.layout.flatten_groups(grads)
                shards, valids = pex.intra_scatter_parts(bufs)
                if use_ef:
                    shards = tuple(s if e is None else s + e
                                   for s, e in zip(shards, state.ef))
                    local = pex.local_qdq_shard_parts(shards, k, valids)
                    new_ef = tuple(None if e is None else s - l
                                   for e, s, l in zip(state.ef, shards,
                                                      local))
                if collect_stats:
                    # measured on the EF-compensated intra shards — what
                    # the quantized inter exchange actually encodes
                    stats = pex.group_stats(
                        shards, new_ef if use_ef else None)
                mean_shards = pex.exchange_shard_parts(shards, k, valids)
                grads = pex.layout.unflatten_groups(
                    pex.intra_gather_parts(mean_shards))
            elif tcfg.fused_exchange:
                # partitioned fused Algorithm 2: leaves grouped by resolved
                # quantizer into contiguous segments, one fused quantized
                # all-reduce per policy group — O(#groups) collectives per
                # step, never O(#leaves) (see core/comm/exchange.py)
                k = jax.random.fold_in(step_key, _FUSED_SALT)
                bufs = pex.layout.flatten_groups(grads)
                ef_bufs = None
                if use_ef:
                    local = pex.local_qdq_parts(bufs, k)
                    ef_bufs = [f - l for f, l in zip(bufs, local)]
                    new_ef = pex.layout.unflatten_groups(
                        ef_bufs, restore_dtype=False)
                if collect_stats:
                    stats = pex.group_stats(bufs, ef_bufs)
                grads = pex.layout.unflatten_groups(
                    pex.exchange_parts(bufs, k))
            else:
                # legacy per-leaf quantized all-reduce of local grads
                def exchange(path, g):
                    cfg_l, qz_l = resolve_leaf(path)
                    flat = g.astype(jnp.float32).reshape(-1)
                    k = jax.random.fold_in(
                        step_key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
                    out = comm.quantized_all_reduce_mean(
                        flat, qz_l, k, dp_axes,
                        server_requant=cfg_l.server_requant,
                        use_kernels=tcfg.use_kernels)
                    return out.reshape(g.shape).astype(g.dtype)

                if use_ef:
                    def residual(path, g):
                        _, qz_l = resolve_leaf(path)
                        if qz_l.is_identity:
                            return jnp.zeros(g.shape, jnp.float32)
                        flat = g.astype(jnp.float32).reshape(-1)
                        k = jax.random.fold_in(
                            step_key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
                        local = comm.local_qdq_comm_layout(
                            flat, qz_l, k, dp_axes,
                            use_kernels=tcfg.use_kernels)
                        return (flat - local).reshape(g.shape)

                    new_ef = jax.tree_util.tree_map(
                        residual, model.param_paths(state.params), grads)
                grads = jax.tree_util.tree_map(
                    exchange, model.param_paths(state.params), grads)
        elif tcfg.mode == "replicated" and not dp_axes:
            # single-machine Algorithm 2: quantize->dequantize locally
            if not pex.is_identity and tcfg.fused_exchange:
                k = jax.random.fold_in(step_key, _FUSED_SALT)
                bufs = pex.layout.flatten_groups(grads)
                qbufs = pex.qdq_local_parts(bufs, k)
                ef_bufs = None
                if use_ef:
                    ef_bufs = [f - q for f, q in zip(bufs, qbufs)]
                    new_ef = pex.layout.unflatten_groups(
                        ef_bufs, restore_dtype=False)
                if collect_stats:
                    stats = pex.group_stats(bufs, ef_bufs)
                grads = pex.layout.unflatten_groups(qbufs)
            elif not pex.is_identity:
                def qdq(path, g):
                    _, qz_l = resolve_leaf(path)
                    if qz_l.is_identity:
                        return g
                    k = jax.random.fold_in(
                        step_key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
                    return qz_l.qdq(g.astype(jnp.float32).reshape(-1), k
                                    ).reshape(g.shape).astype(g.dtype)

                quantized = jax.tree_util.tree_map(
                    qdq, model.param_paths(state.params), grads)
                if use_ef:
                    new_ef = jax.tree_util.tree_map(
                        lambda g, q: (g - q).astype(jnp.float32),
                        grads, quantized)
                grads = quantized

        return grads, new_ef, stats

    def _finish(state: TrainState, grads, new_ef, loss, metrics,
                stats=None):
        lr = lr_fn(state.step)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, state.opt,
                                                state.params, lr)
            new_params = opt_lib.apply_updates(state.params, updates)
        if stats is not None:
            # (n_groups, 3) controller feed; pmean'd with the rest below
            metrics = dict(metrics, exchange_stats=stats)
        if dp_axes:
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m, dp_axes), metrics)
            loss = jax.lax.pmean(loss, dp_axes)
        metrics = dict(metrics, loss=loss, lr=lr)
        return TrainState(params=new_params, opt=new_opt,
                          step=state.step + 1, ef=new_ef), metrics

    # NOTE both jit paths donate the train state (params + optimizer + EF
    # residuals update in place); axis_names is an ORDERED tuple end-to-end
    # — a set would iterate in PYTHONHASHSEED-dependent order and
    # multi-process workers could lower collectives with different axis
    # orderings (see core/comm/collectives._names).
    if not dp_axes or tcfg.mode == "replicated":
        # replicated mode still runs under shard_map for the dp collectives
        if not dp_axes:
            return jax.jit(local_step, donate_argnums=(0,)), plan
        if _async_local_steps(tcfg, dp_axes) > 1:
            # temporal two_level_async hierarchy: H inner steps synced
            # only over the intra (ICI) axes, then ONE quantized outer
            # exchange of the window's parameter delta — a two-function
            # dispatcher instead of a single compiled step
            return _make_async_train_step(model, mesh, tcfg, lr_fn,
                                          optimizer, eng, collect_stats,
                                          aparams), plan
        pspec = jax.tree_util.tree_map(lambda _: P(), aparams)
        rep_ef_sizes = None
        if tcfg.error_feedback and two_level:
            # two-level EF: per-group intra-shard buffers stacked over the
            # dp axes (mirrors _ef_group_sizes / init_state)
            sizes = pex.ef_shard_sizes(n_intra)
            rep_ef_sizes = (sizes if any(n is not None for n in sizes)
                            else None)
        rep_dp_ent = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        if rep_ef_sizes is not None:
            ef_specs = tuple(None if n is None else P(rep_dp_ent)
                             for n in rep_ef_sizes)
        else:
            ef_specs = pspec if tcfg.error_feedback else None
        state_specs = TrainState(
            params=pspec, opt=_opt_specs(optimizer, tcfg, pspec), step=P(),
            ef=ef_specs)
        batch_specs = {"tokens": P(dp_axes if len(dp_axes) > 1
                                   else dp_axes[0])}
        if cfg.encoder:
            batch_specs["enc_embeds"] = P(dp_axes if len(dp_axes) > 1
                                          else dp_axes[0])
        rep_metric_specs = {"nll": P(), "aux": P(), "tokens": P(),
                            "loss": P(), "lr": P()}
        if collect_stats:
            rep_metric_specs["exchange_stats"] = P()
        fn = shard_map(local_step, mesh=mesh,
                       in_specs=(state_specs, batch_specs, P()),
                       out_specs=(state_specs, rep_metric_specs),
                       axis_names=_manual_axes(mesh, plan), check_vma=False)
        return jax.jit(fn, donate_argnums=(0,)), plan

    # fsdp mode
    manual = plan.manual_specs()
    dp_ent = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    state_specs = TrainState(
        params=manual, opt=_opt_specs(optimizer, tcfg, manual), step=P(),
        ef=(tuple(None if n is None else P(dp_ent)
                  for n in fex.ef_group_sizes())
            if use_fsdp_ef else None))
    batch_specs = {"tokens": P(dp_ent)}
    if cfg.encoder:
        batch_specs["enc_embeds"] = P(dp_ent)
    metric_specs = {"nll": P(), "aux": P(), "tokens": P(), "loss": P(),
                    "lr": P()}
    if collect_stats:
        metric_specs["exchange_stats"] = P()
    fn = shard_map(local_step, mesh=mesh,
                   in_specs=(state_specs, batch_specs, P()),
                   out_specs=(state_specs, metric_specs),
                   axis_names=_manual_axes(mesh, plan), check_vma=False)
    return jax.jit(fn, donate_argnums=(0,)), plan


def _opt_specs(optimizer, tcfg: TrainConfig, pspec):
    if tcfg.optimizer == "adamw":
        return opt_lib.AdamState(mu=pspec, nu=pspec, count=P())
    return pspec  # sgd momentum mirrors params


class AsyncTrainStep:
    """Two-time-scale ``step_fn(state, batch, key)`` for the temporal
    ``two_level_async`` hierarchy: a host-side dispatcher over TWO
    compiled shard_maps —

      ``inner_fn``   one inner optimizer step on the worker's local
                     (stacked) params, gradients pmean'd over the fast
                     intra (ICI) axes only: ZERO wire collectives, no
                     rounding-stream draws, the DCN tier is never touched;
      ``sync_fn``    the window's H-th inner update followed by ONE
                     quantized Algorithm-2 exchange of the outer
                     pseudo-gradient (``anchor - local_params``) over the
                     DCN axes through the same fused engines the spatial
                     two_level step uses (policy groups, EF residuals,
                     ``pipeline_chunks`` all compose), feeding the outer
                     SGD-momentum/Nesterov optimizer in
                     ``TrainState.outer`` — after which every worker holds
                     the identical new anchor.

    The window position is read host-side from the ABSOLUTE step counter
    (like :class:`ScheduledTrainStep` reads its phase), so a checkpoint
    restored mid-window resumes at the right phase with no extra
    bookkeeping: sync fires on steps H-1, 2H-1, ... — the H-th update of
    every window."""

    def __init__(self, inner_fn, sync_fn, local_steps: int):
        self.inner_fn, self.sync_fn = inner_fn, sync_fn
        self.local_steps = int(local_steps)

    def is_sync_step(self, step: int) -> bool:
        return (int(step) + 1) % self.local_steps == 0

    def __call__(self, state: TrainState, batch, key):
        if self.is_sync_step(int(state.step)):
            return self.sync_fn(state, batch, key)
        return self.inner_fn(state, batch, key)


def _make_async_train_step(model: LM, mesh, tcfg: TrainConfig, lr_fn,
                           optimizer, eng: ExchangeEngines, collect_stats,
                           aparams) -> AsyncTrainStep:
    """Build the two compiled halves of :class:`AsyncTrainStep`.

    State layout (see :func:`init_state`): params/opt leaves carry a
    leading worker axis sharded over the dp axes (inner steps make them
    pod-divergent; the stacked layout keeps that divergence honest in
    shardings and checkpoints — each worker sees its own ``leaf[0]``
    slice inside the shard_map), while ``outer.anchor``/``outer.mom`` are
    truly replicated (rewritten only at sync steps from the exchange's
    identical output)."""
    cfg = model.cfg
    dp_axes = eng.plan.dp_axes
    pex, intra_axes, n_intra = eng.pex, eng.intra_axes, eng.n_intra
    two_level = bool(intra_axes)
    nesterov = tcfg.outer_optimizer == "nesterov"
    outer_lr, outer_mu = tcfg.outer_lr, tcfg.outer_momentum
    ef_sizes = pex.ef_shard_sizes(n_intra)
    use_ef = (tcfg.error_feedback
              and any(s is not None for s in ef_sizes))

    def unstack(t):
        return jax.tree_util.tree_map(lambda x: x[0], t)

    def stack(t):
        return jax.tree_util.tree_map(lambda x: x[None], t)

    def _inner_update(state: TrainState, batch):
        """The shared inner computation: pod-synchronous gradient + one
        inner optimizer step on this worker's local parameter view."""
        params = unstack(state.params)
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch)
        if intra_axes:
            # ONE multi-operand psum over the fast ICI axes; inner steps
            # never touch the DCN tier (the point of the temporal split)
            with jax.named_scope("exchange"):
                grads = comm.collectives.pmean(grads, intra_axes)
        lr = lr_fn(state.step)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, unstack(state.opt),
                                                params, lr)
            new_params = opt_lib.apply_updates(params, updates)
        return new_params, new_opt, loss, metrics, lr

    def _pack(state, new_params, new_opt, new_ef, outer, loss, metrics,
              lr, stats=None):
        if stats is not None:
            metrics = dict(metrics, exchange_stats=stats)
        # scalar logging reductions over the FULL dp mesh (negligible
        # bytes; the gradient payload itself never crosses pods here)
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.pmean(m, dp_axes), metrics)
        loss = jax.lax.pmean(loss, dp_axes)
        metrics = dict(metrics, loss=loss, lr=lr)
        return TrainState(params=stack(new_params), opt=stack(new_opt),
                          step=state.step + 1, ef=new_ef,
                          outer=outer), metrics

    def inner_step(state: TrainState, batch, key):
        del key              # inner steps draw no rounding bits at all
        new_params, new_opt, loss, metrics, lr = _inner_update(state,
                                                               batch)
        return _pack(state, new_params, new_opt, state.ef, state.outer,
                     loss, metrics, lr)

    def sync_step(state: TrainState, batch, key):
        new_params, new_opt, loss, metrics, lr = _inner_update(state,
                                                               batch)
        # outer pseudo-gradient: the window's parameter delta — identical
        # within a pod (inner grads are intra-pmean'd), divergent across
        # pods; exactly the arbitrary-distribution input the optimal-
        # condition level fits are built for
        delta = jax.tree_util.tree_map(
            lambda a, p: (a - p).astype(jnp.float32),
            state.outer.anchor, new_params)
        step_key = jax.random.fold_in(key, state.step)
        k = jax.random.fold_in(step_key, _FUSED_SALT)
        with jax.named_scope("exchange"):
            bufs = pex.layout.flatten_groups(delta)
            new_ef = state.ef
            stats = None
            if two_level:
                # the literal two_level wire path, fed the delta: fp intra
                # scatter -> EF add on the shard -> quantized Algorithm 2
                # over the pod axes only -> fp intra gather
                shards, valids = pex.intra_scatter_parts(bufs)
                if use_ef:
                    shards = tuple(s if e is None else s + e
                                   for s, e in zip(shards, state.ef))
                    local = pex.local_qdq_shard_parts(shards, k, valids)
                    new_ef = tuple(None if e is None else s - l
                                   for e, s, l in zip(state.ef, shards,
                                                      local))
                if collect_stats:
                    stats = pex.group_stats(shards,
                                            new_ef if use_ef else None)
                mean_shards = pex.exchange_shard_parts(shards, k, valids)
                delta_mean = pex.layout.unflatten_groups(
                    pex.intra_gather_parts(mean_shards), restore_dtype=False)
            else:
                # degenerate intra half (pods-only dp mesh): the outer
                # exchange runs flat over all dp axes, EF on the full
                # buffers
                if use_ef:
                    bufs = tuple(b if e is None else b + e
                                 for b, e in zip(bufs, state.ef))
                    local = pex.local_qdq_parts(bufs, k)
                    new_ef = tuple(None if e is None else b - l
                                   for e, b, l in zip(state.ef, bufs,
                                                      local))
                if collect_stats:
                    stats = pex.group_stats(bufs,
                                            new_ef if use_ef else None)
                delta_mean = pex.layout.unflatten_groups(
                    pex.exchange_parts(bufs, k), restore_dtype=False)
        # outer optimizer on the exchanged mean pseudo-gradient; its
        # output is globally identical, so anchor/mom stay replicated
        with jax.named_scope("optimizer"):
            mom = jax.tree_util.tree_map(
                lambda m, d: outer_mu * m + d, state.outer.mom, delta_mean)
            upd = (jax.tree_util.tree_map(
                       lambda d, m: d + outer_mu * m, delta_mean, mom)
                   if nesterov else mom)
            outer_params = jax.tree_util.tree_map(
                lambda a, u: (a - outer_lr * u).astype(a.dtype),
                state.outer.anchor, upd)
        outer = OuterState(anchor=outer_params, mom=mom)
        return _pack(state, outer_params, new_opt, new_ef, outer, loss,
                     metrics, lr, stats)

    dp_ent = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    stacked = jax.tree_util.tree_map(lambda _: P(dp_ent), aparams)
    aopt = jax.eval_shape(optimizer.init, aparams)
    rep = lambda t: jax.tree_util.tree_map(lambda _: P(), t)  # noqa: E731
    state_specs = TrainState(
        params=stacked,
        opt=jax.tree_util.tree_map(lambda _: P(dp_ent), aopt),
        step=P(),
        ef=(tuple(None if s is None else P(dp_ent) for s in ef_sizes)
            if use_ef else None),
        outer=OuterState(anchor=rep(aparams), mom=rep(aparams)))
    batch_specs = {"tokens": P(dp_ent)}
    if cfg.encoder:
        batch_specs["enc_embeds"] = P(dp_ent)
    inner_metric_specs = {"nll": P(), "aux": P(), "tokens": P(),
                          "loss": P(), "lr": P()}
    sync_metric_specs = dict(inner_metric_specs)
    if collect_stats:
        sync_metric_specs["exchange_stats"] = P()
    inner_fn = jax.jit(
        shard_map(inner_step, mesh=mesh,
                  in_specs=(state_specs, batch_specs, P()),
                  out_specs=(state_specs, inner_metric_specs),
                  axis_names=_manual_axes(mesh, eng.plan), check_vma=False),
        donate_argnums=(0,))
    sync_fn = jax.jit(
        shard_map(sync_step, mesh=mesh,
                  in_specs=(state_specs, batch_specs, P()),
                  out_specs=(state_specs, sync_metric_specs),
                  axis_names=_manual_axes(mesh, eng.plan), check_vma=False),
        donate_argnums=(0,))
    return AsyncTrainStep(inner_fn, sync_fn, tcfg.local_steps)


class ScheduledTrainStep:
    """Host-side driver of the adaptive bit budget: a drop-in
    ``step_fn(state, batch, key)`` whose per-group wire bit-width follows
    a :class:`~repro.core.policy.BitBudgetController`.

    Design (recompile-on-phase-boundary, NEVER traced bit-width):

      * ONE bits-independent engine skeleton is built up front with
        ``group_by_rule=True`` — leaves partition by policy RULE index,
        so the group structure (and every EF-residual shape) is identical
        for every bits assignment the schedule can produce;
      * each phase's assignment is materialized into a concrete static
        ``QuantPolicy`` (``schedule.policy_at``), the skeleton is
        re-specialized (:func:`specialize_engines` — swaps quantizers,
        keeps layouts) and compiled into a normal :func:`make_train_step`
        function, held in an LRU keyed by the bits tuple;
      * within a phase the compiled step is BIT-IDENTICAL to a static
        run at that policy (same layouts, same PRNG streams, same single
        ``pallas_call`` encode); a schedule that never changes bits
        compiles exactly one engine and reproduces the static run's
        params stream exactly;
      * with ``tcfg.collect_stats`` the step emits the per-group
        ``exchange_stats`` metric, which is folded per schedule entry and
        fed back to ``controller.observe`` so the next phase's
        water-filling solve is statistics-driven.

    The step counter is read host-side from ``state.step`` — callers must
    keep it consistent with the training loop (the launcher does)."""

    def __init__(self, model: LM, mesh, tcfg: TrainConfig, controller,
                 lr_fn=None, *, aparams=None, max_engines: int = 4):
        if tcfg.policy is not None:
            raise ValueError(
                "ScheduledTrainStep derives the per-phase policy from the "
                "controller's BitSchedule — leave TrainConfig.policy unset")
        self.model, self.mesh, self.lr_fn = model, mesh, lr_fn
        self.controller = controller
        self.schedule = controller.schedule
        # skeleton at the ceiling assignment: any valid assignment yields
        # the same layouts/EF shapes (by-rule grouping), the ceiling just
        # makes the warning-size accounting conservative
        base_policy = self.schedule.policy_at(
            self.schedule.ceil_assignment())
        self.tcfg = dataclasses.replace(tcfg, policy=base_policy,
                                        group_by_rule=True)
        if aparams is None:
            aparams = jax.eval_shape(model.init, jax.random.key(0))
        self.aparams = aparams
        self.skeleton = exchange_engines(model, mesh, self.tcfg,
                                         aparams=aparams)
        self.plan = self.skeleton.plan
        groups = (self.skeleton.fex.layout.groups
                  if self.skeleton.fused_fsdp
                  else self.skeleton.pex.layout.groups)
        self._group_rules = tuple(g.rule_id for g in groups)
        self._group_sizes = tuple(g.size for g in groups)
        if self.controller.group_sizes is None:
            sizes = [0] * self.schedule.n_entries
            for rid, size in zip(self._group_rules, self._group_sizes):
                sizes[rid] += size
            self.controller.group_sizes = tuple(sizes)
        self.max_engines = max(1, int(max_engines))
        self._cache: "OrderedDict[Tuple[Optional[int], ...], Any]" = \
            OrderedDict()
        self.last_assignment: Optional[Tuple[Optional[int], ...]] = None

    @property
    def init_config(self) -> TrainConfig:
        """TrainConfig to ``init_state`` with: by-rule grouping + a
        concrete schedule policy, so EF buffers come out with the (bits-
        invariant) shapes every phase's compiled step expects."""
        return self.tcfg

    @property
    def decisions(self):
        return self.controller.decisions

    def step_fn(self, assignment) -> Any:
        """The compiled step function for one bits assignment (LRU'd)."""
        key = tuple(assignment)
        if key in self._cache:
            self._cache.move_to_end(key)
            return self._cache[key]
        policy = self.schedule.policy_at(key)
        eng = specialize_engines(self.skeleton, policy)
        fn, _ = make_train_step(
            self.model, self.mesh,
            dataclasses.replace(self.tcfg, policy=policy), self.lr_fn,
            aparams=self.aparams, engines=eng)
        self._cache[key] = fn
        while len(self._cache) > self.max_engines:
            self._cache.popitem(last=False)
        return fn

    def entry_stats(self, group_stats) -> Tuple[Dict[str, float], ...]:
        """Fold the (n_groups, 3) ``exchange_stats`` metric into one row
        per schedule entry (size-weighted means for sigma_sq/clip_frac,
        summed ef_norm_sq — fsdp splits one rule into sharded +
        replicated groups)."""
        g = np.asarray(jax.device_get(group_stats), dtype=np.float64)
        n = self.schedule.n_entries
        acc, w = np.zeros((n, 3)), np.zeros(n)
        for rid, size, row in zip(self._group_rules, self._group_sizes, g):
            acc[rid, 0] += row[0] * size
            acc[rid, 1] += row[1] * size
            acc[rid, 2] += row[2]
            w[rid] += size
        nz = w > 0
        acc[nz, 0] /= w[nz]
        acc[nz, 1] /= w[nz]
        return tuple({"sigma_sq": float(r[0]), "clip_frac": float(r[1]),
                      "ef_norm_sq": float(r[2])} for r in acc)

    def __call__(self, state: TrainState, batch, key):
        step = int(state.step)
        assignment = self.controller.assignment_at(step)
        self.last_assignment = assignment
        state, metrics = self.step_fn(assignment)(state, batch, key)
        if "exchange_stats" in metrics:
            self.controller.observe(
                self.entry_stats(metrics["exchange_stats"]))
        return state, metrics
