"""Matrix audit: trace the REAL programs and attach computed invariants.

Builders for the three bundle families the CI ``static-analysis`` job
(and ``tests/test_analysis.py``) runs rules against:

  * :func:`wire_bundles`    — ``wire.encode``/``qdq``/``decode_mean``/
    ``decode_each`` traced per registered scheme (the PR-5 one-launch
    pins).
  * :func:`train_bundles`   — the actual jitted train step traced for
    replicated/FSDP x flat/two_level x pipeline_chunks on an 8-fake-
    device mesh, with collective budgets derived from the SAME
    ``ExchangeEngines`` objects ``make_train_step`` uses (span schedule,
    pipeline clamp, requant mode all come from the engine — no parallel
    accounting model to drift).
  * :func:`serve_bundles`   — the serving ``Engine._fwd`` traced at the
    decode shape per KV scheme (the PR-7 one-launch + donation pins).

Expected collective counts pin only the gradient-wire primitives
(``all_to_all``/``all_gather``/``reduce_scatter``); ``psum`` carries
loss/metric reductions too, so it is constrained by axis (``psum`` may
only touch the dp axes) rather than by exact count.

Every builder needs >= 8 local devices for the train meshes — call
``repro.utils.env.force_host_device_count(8)`` before importing jax
(``python -m repro.analysis`` does; tests go through a subprocess).
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis.engine import TraceBundle
from repro.core import QuantPolicy, all_methods, make_quantizer
from repro.core.comm import hierarchical, wire
from repro.core.comm.exchange import GradientExchange
from repro.utils.env import kernels_enabled

#: smoke arch every trace uses (2 attention layers, one orq + one fp
#: policy group under the mixed policy below)
ARCH = "lm-100m"
MIXED_POLICY = "norm|bias=fp,default=orq-9"
UNIFORM_POLICY = "orq-9"

#: (mode, hierarchy, pipeline_chunks, mesh shape, mesh axes, policy) —
#: every leg needs 8 fake devices; K=1 legs double as the
#: materialization baseline for the K=3 legs
TRAIN_MATRIX: List[Tuple[str, str, int, tuple, tuple, str]] = [
    ("replicated", "flat", 1, (8,), ("data",), MIXED_POLICY),
    ("replicated", "flat", 3, (8,), ("data",), MIXED_POLICY),
    ("replicated", "two_level", 1, (2, 4), ("pod", "data"), MIXED_POLICY),
    ("replicated", "two_level", 3, (2, 4), ("pod", "data"), MIXED_POLICY),
    ("replicated", "flat", 1, (8,), ("data",), UNIFORM_POLICY),
    ("fsdp", "flat", 1, (8,), ("data",), MIXED_POLICY),
    ("fsdp", "flat", 3, (8,), ("data",), MIXED_POLICY),
    ("fsdp", "two_level", 1, (2, 4), ("pod", "data"), MIXED_POLICY),
    ("fsdp", "two_level", 3, (2, 4), ("pod", "data"), MIXED_POLICY),
]

#: KV schemes the serve audit traces: rr + bin + sign rounding families
#: plus the bf16 escape hatch (zero kernel launches)
SERVE_SCHEMES = ("orq-9", "bingrad-b", "signsgd", "bf16")

#: adaptive bit-schedule audit: the per-phase specialized steps of ONE
#: by-rule engine skeleton, traced at every distinct phase the schedule
#: produces — collective/pallas/prng budgets must track the phase bits
#: while the group structure (EF shapes) stays put
SCHED_SCHEDULE = "embed=orq@5..3,norm|bias=fp,default=orq@4..2"
SCHED_STEPS, SCHED_RESOLVE = 100, 50
SCHED_MATRIX: List[Tuple[str, str, tuple, tuple]] = [
    ("replicated", "flat", (8,), ("data",)),
    ("replicated", "two_level", (2, 4), ("pod", "data")),
    ("fsdp", "flat", (8,), ("data",)),
]

#: temporal-hierarchy audit: (local_steps H, pipeline_chunks) legs of the
#: two_level_async step pair on the 2x4 pod mesh. The INNER step must
#: carry ZERO wire collectives (the whole point of the time hierarchy:
#: quantized traffic exists only on DCN axes and only on sync steps) and
#: the SYNC step must carry exactly the two_level outer-exchange budget.
ASYNC_MATRIX: List[Tuple[int, int]] = [
    (4, 1),
    (4, 3),
]


# ---------------------------------------------------------------------------
# wire-op bundles (per registered scheme)
# ---------------------------------------------------------------------------

def fit_launches(qz) -> int:
    """Kernel launches of one level fit: ORQ's ``orq_fit``; the other
    schemes fit in jnp or inside their encode kernel."""
    return 1 if qz.method == "orq" else 0


def wire_bundles(schemes: Optional[Sequence[str]] = None, *, nb: int = 5,
                 d: int = 37, workers: int = 3) -> List[TraceBundle]:
    """One bundle per (scheme, op) on a ragged (nb, d) buffer: exactly one
    pallas_call per fused op, after the fit's own for ORQ (zero under the
    reference oracle), and the rounding stream drawn exactly once for the
    'rr' schemes."""
    names = [n for n in (schemes or all_methods())
             if not make_quantizer(n, bucket_size=d).is_identity]
    key = jax.random.key(11)
    bkt = jax.random.laplace(jax.random.key(1), (nb, d)) * 0.1
    mask = jnp.arange(nb * d).reshape(nb, d) < (nb * d - 3)
    kern = 1 if kernels_enabled() else 0
    out: List[TraceBundle] = []
    for name in names:
        qz = make_quantizer(name, bucket_size=d)
        draws = 1 if wire._fused_mode(qz) == "rr" else 0
        fit_enc = kern * (1 + fit_launches(qz))
        enc = jax.make_jaxpr(
            lambda b, m, k: wire.encode(qz, b, m, k))(bkt, mask, key)
        out.append(TraceBundle(
            label=f"wire/{name}/encode", kind="wire_op", closed=enc,
            meta={"expect_pallas_calls": fit_enc,
                  "prng": {"random_bits": draws}}))
        qdq = jax.make_jaxpr(
            lambda b, m, k: wire.qdq(qz, b, m, k))(bkt, mask, key)
        out.append(TraceBundle(
            label=f"wire/{name}/qdq", kind="wire_op", closed=qdq,
            meta={"expect_pallas_calls": fit_enc,
                  "prng": {"random_bits": draws}}))
        # decode input shapes come from the encoder itself, not a
        # hand-maintained words-per-bucket table
        w_sds, l_sds = jax.eval_shape(
            lambda b, m, k: wire.encode(qz, b, m, k), bkt, mask, key)
        ws = jnp.zeros((workers,) + w_sds.shape, w_sds.dtype)
        lvs = jnp.zeros((workers,) + l_sds.shape, l_sds.dtype)
        for avg in (True, False):
            dec = jax.make_jaxpr(
                lambda w, l, a=avg: wire.decode(qz, w, l, d, average=a))(
                    ws, lvs)
            out.append(TraceBundle(
                label=f"wire/{name}/decode_{'mean' if avg else 'each'}",
                kind="wire_op", closed=dec,
                meta={"expect_pallas_calls": kern,
                      "prng": {"random_bits": 0}}))
    return out


# ---------------------------------------------------------------------------
# train-step bundles (the scheme x mode matrix)
# ---------------------------------------------------------------------------

def _axis_prod(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(np.prod([sizes[a] for a in axes])) if axes else 1


def _replicated_group_budget(exp: Counter, e: GradientExchange, n: int,
                             mesh) -> None:
    """Wire collectives one PartitionedExchange group contributes: the
    span schedule, pipeline clamp, and requant mode are read off the
    engine itself."""
    intra, inter = tuple(e.intra_axes), tuple(e.axis_names)
    if intra:
        # fp scatter + reassembly gather bracket EVERY group (identity
        # included) in two-level mode
        exp[("reduce_scatter", intra)] += 1
        exp[("all_gather", intra)] += 1
        n = hierarchical.intra_chunk_len(n, _axis_prod(mesh, intra))
    if e.qz.is_identity:
        return                      # flat identity is a pmean (psum)
    n_inter = _axis_prod(mesh, inter)
    for a, b in e.spans(n):
        k = e._pipeline_k(b - a, n_inter)
        exp[("all_to_all", inter)] += 2 * k       # words + levels / chunk
        if e.server_requant:
            exp[("all_gather", inter)] += 2 * k   # requantized broadcast
        else:
            exp[("all_gather", inter)] += 1       # one f32 gather / span


def expected_train_collectives(eng, mesh,
                               pipeline_chunks: int) -> Dict[str, object]:
    """{"expected_collectives", "exclusive_prims"} for one traced train
    step, derived from the ``ExchangeEngines`` the step itself built."""
    exp: Counter = Counter()
    intra = tuple(eng.intra_axes)
    inter = tuple(eng.inter_axes)
    full_dp = inter + intra         # worker-major: inter axes lead
    if eng.fused_fsdp:
        n_intra = eng.fex.n_intra
        for e, g in zip(eng.fex.engines, eng.fex.layout.groups):
            if not g.sharded:
                _replicated_group_budget(exp, e, g.size, mesh)
                continue
            # ZeRO-3 parameter broadcast in the next forward
            exp[("all_gather", full_dp)] += 1
            if intra:
                exp[("reduce_scatter", intra)] += 1   # worker-major rows
                m, workers = g.size // n_intra, eng.fex.n_inter
            else:
                m, workers = g.size, eng.fex.layout.n_shards
            if e.qz.is_identity:
                exp[("reduce_scatter", inter if intra else full_dp)] += 1
            else:
                launches, _ = GradientExchange.rs_stats(
                    e.qz, m, workers, pipeline_chunks)
                exp[("all_to_all", inter if intra else full_dp)] += launches
    else:
        for e, g in zip(eng.pex.engines, eng.pex.layout.groups):
            _replicated_group_budget(exp, e, g.size, mesh)
    wire_axes = [ax for (_, ax) in exp]
    return {
        "expected_collectives": dict(exp),
        # psum carries metric reductions (un-pinned counts) but may only
        # ever touch dp axes; a2a is the quantized payload and may only
        # run where the budget above placed it (the DCN-only claim)
        "exclusive_prims": {
            "all_to_all": [ax for (p, ax) in exp if p == "all_to_all"],
            "all_gather": [ax for (p, ax) in exp if p == "all_gather"],
            "reduce_scatter": [ax for (p, ax) in exp
                               if p == "reduce_scatter"],
            "psum": [ax for ax in (full_dp, inter, intra) if ax],
            "psum_scatter": wire_axes,
        },
    }


def expected_train_pallas(eng, mesh, pipeline_chunks: int, *,
                          ef: bool = False) -> Optional[int]:
    """Kernel launches one step makes: replicated requant = encode +
    server decode_each + re-encode + worker decode per chunk (4K);
    fsdp reduce-scatter = encode + decode_mean per chunk (2K); and for
    ORQ a level fit before each encode (2K, K). ``ef`` adds the
    replicated error-feedback residual's fused local qdq, and its fit —
    per span of every quantized group (``local_qdq_shard``)."""
    if not kernels_enabled():
        return 0
    total = 0
    intra = tuple(eng.intra_axes)
    if eng.fused_fsdp:
        n_intra = eng.fex.n_intra
        for e, g in zip(eng.fex.engines, eng.fex.layout.groups):
            if e.qz.is_identity:
                continue
            if not g.sharded:
                if not e.server_requant:
                    return None     # non-requant split not modelled yet
                m = g.size
                if intra:
                    m = hierarchical.intra_chunk_len(
                        m, _axis_prod(mesh, intra))
                total += sum(
                    (4 + 2 * fit_launches(e.qz))
                    * e._pipeline_k(b - a, _axis_prod(mesh, e.axis_names))
                    for a, b in e.spans(m))
                continue
            m = g.size // n_intra if intra else g.size
            workers = eng.fex.n_inter if intra else eng.fex.layout.n_shards
            launches, _ = GradientExchange.rs_stats(
                e.qz, m, workers, pipeline_chunks)
            # 2K: encode + fused decode per chunk, and K fits
            total += launches * (2 + fit_launches(e.qz)) // 2
    else:
        for e, g in zip(eng.pex.engines, eng.pex.layout.groups):
            if e.qz.is_identity:
                continue
            if not e.server_requant:
                return None
            m = g.size
            if intra:
                m = hierarchical.intra_chunk_len(m, _axis_prod(mesh, intra))
            total += sum(
                (4 + 2 * fit_launches(e.qz))
                * e._pipeline_k(b - a, _axis_prod(mesh, e.axis_names))
                for a, b in e.spans(m))
            if ef:
                total += (1 + fit_launches(e.qz)) * len(e.spans(m))
    return total


def expected_train_draws(eng, mesh, *, ef: bool = False) -> int:
    """Rounding-stream draws per step: one per quantized encode site
    (worker encode + server re-encode per span when re-quantizing; the
    fsdp reduce-scatter has no server phase). Invariant in K — the
    pipelined schedule slices ONE full-shape stream."""
    draws = 0
    intra = tuple(eng.intra_axes)
    if eng.fused_fsdp:
        for e, g in zip(eng.fex.engines, eng.fex.layout.groups):
            if e.qz.is_identity or wire._fused_mode(e.qz) != "rr":
                continue
            if g.sharded:
                draws += 1
            else:
                m = g.size
                if intra:
                    m = hierarchical.intra_chunk_len(
                        m, _axis_prod(mesh, intra))
                draws += len(e.spans(m)) * (2 if e.server_requant else 1)
    else:
        for e, g in zip(eng.pex.engines, eng.pex.layout.groups):
            if e.qz.is_identity or wire._fused_mode(e.qz) != "rr":
                continue
            m = g.size
            if intra:
                m = hierarchical.intra_chunk_len(m, _axis_prod(mesh, intra))
            draws += len(e.spans(m)) * (2 if e.server_requant else 1)
            if ef:
                # the residual's local qdq folds the same span keys and
                # draws its own stream copy per span
                draws += len(e.spans(m))
    return draws


def _smoke_setup():
    from repro.configs.base import get_smoke_config
    from repro.data import SyntheticLM
    from repro.models import LM

    cfg = get_smoke_config(ARCH)
    model = LM(cfg)
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16, batch_size=8,
                      seed=0)
    return model, data


def train_bundles(matrix: Optional[Sequence[tuple]] = None
                  ) -> List[TraceBundle]:
    """Trace the real train step for every matrix leg. The K=1 trace of a
    (mode, hierarchy, policy) leg is the materialization baseline its
    K>1 legs are checked against (a chunked schedule may never hold MORE
    group-sized f32 buffers than the single-shot one)."""
    from repro.analysis import stats
    from repro.optim.schedule import constant_lr
    from repro.train import TrainConfig, make_train_step
    from repro.train.step import exchange_engines, init_state

    model, data = _smoke_setup()
    batch = data.batch(0)
    out: List[TraceBundle] = []
    mat_baseline: Dict[tuple, int] = {}
    for mode, hier, k, shape, axes, policy in (matrix or TRAIN_MATRIX):
        mesh = jax.make_mesh(shape, axes)
        tcfg = TrainConfig(policy=QuantPolicy.parse(policy), mode=mode,
                           hierarchy=hier, pipeline_chunks=k)
        state = jax.eval_shape(
            lambda key: init_state(model, mesh, tcfg, key),
            jax.random.key(0))
        step_fn, _ = make_train_step(model, mesh, tcfg, constant_lr(0.05))
        closed = jax.make_jaxpr(step_fn)(state, batch, jax.random.key(1))
        eng = exchange_engines(model, mesh, tcfg)
        meta = expected_train_collectives(eng, mesh, k)
        meta["expect_donated"] = len(jax.tree_util.tree_leaves(state))
        meta["prng"] = {"random_bits": expected_train_draws(eng, mesh)}
        pallas = expected_train_pallas(eng, mesh, k)
        if pallas is not None:
            meta["expect_pallas_calls"] = pallas
        group_elems = max(g.size for g in eng.pex.layout.groups)
        leg = (mode, hier, policy)
        if k == 1:
            mat_baseline[leg] = stats.sized_outvar_count(
                closed, group_elems, "float32")
        elif leg in mat_baseline:
            meta["materialization"] = {"min_elems": group_elems,
                                       "dtype": "float32",
                                       "max_count": mat_baseline[leg]}
        out.append(TraceBundle(
            label=f"train/{mode}/{hier}/k{k}/{policy}", kind="train_step",
            closed=closed, meta=meta))
    return out


def sched_bundles(matrix: Optional[Sequence[tuple]] = None
                  ) -> List[TraceBundle]:
    """Trace the adaptive bit schedule's per-phase specialized steps.

    One by-rule engine SKELETON per leg (what ``ScheduledTrainStep``
    holds), re-specialized for every distinct phase of
    ``SCHED_SCHEDULE`` — each phase's trace gets its own collective/
    pallas/prng budget derived from the SPECIALIZED engines, extending
    the invariant matrix across schedule boundaries: a bits change must
    move the wire budgets and nothing else."""
    import dataclasses

    from repro.core.policy import BitSchedule
    from repro.optim.schedule import constant_lr
    from repro.train import TrainConfig, make_train_step
    from repro.train.step import (exchange_engines, init_state,
                                  specialize_engines)

    model, data = _smoke_setup()
    batch = data.batch(0)
    schedule = BitSchedule.parse(SCHED_SCHEDULE)
    out: List[TraceBundle] = []
    for mode, hier, shape, axes in (matrix or SCHED_MATRIX):
        mesh = jax.make_mesh(shape, axes)
        base = TrainConfig(
            policy=schedule.policy_at(schedule.ceil_assignment()),
            mode=mode, hierarchy=hier, group_by_rule=True)
        skeleton = exchange_engines(model, mesh, base)
        state = jax.eval_shape(
            lambda key: init_state(model, mesh, base, key),
            jax.random.key(0))
        for start, assignment in schedule.phases(SCHED_STEPS,
                                                 SCHED_RESOLVE):
            policy = schedule.policy_at(assignment)
            eng = specialize_engines(skeleton, policy)
            step_fn, _ = make_train_step(
                model, mesh, dataclasses.replace(base, policy=policy),
                constant_lr(0.05), engines=eng)
            closed = jax.make_jaxpr(step_fn)(state, batch,
                                             jax.random.key(1))
            meta = expected_train_collectives(eng, mesh, 1)
            meta["expect_donated"] = len(jax.tree_util.tree_leaves(state))
            meta["prng"] = {"random_bits": expected_train_draws(eng, mesh)}
            pallas = expected_train_pallas(eng, mesh, 1)
            if pallas is not None:
                meta["expect_pallas_calls"] = pallas
            bits = ",".join("fp" if b is None else str(b)
                            for b in assignment)
            out.append(TraceBundle(
                label=f"train/sched/{mode}/{hier}/phase{start}/b{bits}",
                kind="train_step", closed=closed, meta=meta))
    return out


def async_bundles(matrix: Optional[Sequence[tuple]] = None
                  ) -> List[TraceBundle]:
    """Trace BOTH programs of the two_level_async step pair.

    The dispatcher (``AsyncTrainStep``) is host-side, so the time
    hierarchy's central claim lives in two separate jaxprs:

      * ``inner_fn`` — H-1 of every H steps.  May touch NO wire
        primitive at all (``all_to_all``/``all_gather``/
        ``reduce_scatter``/``psum_scatter`` forbidden outright), draws
        no rounding bits, launches no kernels; its only collectives are
        ``psum`` means on the dp axes (grad pmean over intra, metric
        pmean over full dp).
      * ``sync_fn``  — the window's last step.  Must carry EXACTLY the
        two_level outer-exchange budget derived from the engines as
        built (fp scatter/gather on intra, quantized a2a + gather on
        inter only), pinned with the same collective/pallas/prng/
        materialization rules as the synchronous train legs.
    """
    from repro.analysis import stats
    from repro.optim.schedule import constant_lr
    from repro.train import TrainConfig, make_train_step
    from repro.train.step import exchange_engines, init_state

    model, data = _smoke_setup()
    batch = data.batch(0)
    shape, axes, policy = (2, 4), ("pod", "data"), MIXED_POLICY
    out: List[TraceBundle] = []
    mat_baseline: Dict[int, int] = {}
    for local_steps, k in (matrix or ASYNC_MATRIX):
        mesh = jax.make_mesh(shape, axes)
        tcfg = TrainConfig(policy=QuantPolicy.parse(policy),
                           mode="replicated", hierarchy="two_level_async",
                           local_steps=local_steps, error_feedback=True,
                           pipeline_chunks=k)
        state = jax.eval_shape(
            lambda key: init_state(model, mesh, tcfg, key),
            jax.random.key(0))
        step_fn, _ = make_train_step(model, mesh, tcfg, constant_lr(0.05))
        eng = exchange_engines(model, mesh, tcfg)
        intra = tuple(eng.intra_axes)
        inter = tuple(eng.inter_axes)
        full_dp = inter + intra
        donated = len(jax.tree_util.tree_leaves(state))
        tag = f"train/async/h{local_steps}/k{k}/{policy}"

        closed = jax.make_jaxpr(step_fn.inner_fn)(state, batch,
                                                  jax.random.key(1))
        out.append(TraceBundle(
            label=f"{tag}/inner", kind="train_step", closed=closed,
            meta={
                "expected_collectives": {},
                # empty allowed-axes list = the primitive may not appear
                # anywhere: inner steps are wire-silent by construction
                "exclusive_prims": {
                    "all_to_all": [],
                    "all_gather": [],
                    "reduce_scatter": [],
                    "psum_scatter": [],
                    "psum": [ax for ax in (full_dp, intra) if ax],
                },
                "expect_pallas_calls": 0,
                "prng": {"random_bits": 0},
                "expect_donated": donated,
            }))

        closed = jax.make_jaxpr(step_fn.sync_fn)(state, batch,
                                                 jax.random.key(1))
        meta = expected_train_collectives(eng, mesh, k)
        meta["expect_donated"] = donated
        meta["prng"] = {"random_bits":
                        expected_train_draws(eng, mesh, ef=True)}
        pallas = expected_train_pallas(eng, mesh, k, ef=True)
        if pallas is not None:
            meta["expect_pallas_calls"] = pallas
        group_elems = max(g.size for g in eng.pex.layout.groups)
        if k == 1:
            mat_baseline[local_steps] = stats.sized_outvar_count(
                closed, group_elems, "float32")
        elif local_steps in mat_baseline:
            meta["materialization"] = {"min_elems": group_elems,
                                       "dtype": "float32",
                                       "max_count":
                                           mat_baseline[local_steps]}
        out.append(TraceBundle(label=f"{tag}/sync", kind="train_step",
                               closed=closed, meta=meta))
    return out


# ---------------------------------------------------------------------------
# serve bundles (Engine._fwd at the decode shape)
# ---------------------------------------------------------------------------

def serve_bundles(schemes: Sequence[str] = SERVE_SCHEMES
                  ) -> List[TraceBundle]:
    from repro.serve import Engine, ServeConfig

    model, _ = _smoke_setup()
    params = jax.eval_shape(model.init, jax.random.key(0))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if jnp.issubdtype(a.dtype, jnp.floating)
            else a.dtype), params)
    n_attn = sum(1 for s in model.specs if s.kind in ("attn", "attn_local"))
    # attention layers of the scanned group units: each unit's body is
    # traced once, however many times its group repeats
    n_bodies = sum(1 for g in model.groups for s in g.unit
                   if s.kind in ("attn", "attn_local"))
    out: List[TraceBundle] = []
    for scheme in schemes:
        cfg = ServeConfig(kv_quant=scheme, page_size=4, max_batch=2,
                          max_pages_per_seq=4, prefill_chunk=4)
        eng = Engine(model, params, cfg)
        B = cfg.max_batch
        table = jnp.zeros((B, cfg.max_pages_per_seq), jnp.int32)
        pos = jnp.zeros((B,), jnp.int32)
        seeds = jnp.zeros((B,), jnp.int32)
        toks = jnp.zeros((B, 1), jnp.int32)
        closed = jax.make_jaxpr(eng._fwd)(
            eng.params, eng.pools, table, pos, seeds, toks)
        quantized = eng.qz is not None
        meta = {
            # per attention body at the decode shape: the fused append
            # (after ORQ's level fit) and the fused dequant-attend; the
            # bf16 escape hatch launches none
            "expect_pallas_calls":
                n_bodies * (2 + fit_launches(eng.qz))
                if quantized and kernels_enabled() else 0,
            # the paged pools are donated (updated in place)
            "expect_donated": len(jax.tree_util.tree_leaves(eng.pools)),
            "prng": {"random_bits": n_attn if eng._rr else 0},
        }
        out.append(TraceBundle(label=f"serve/{scheme}/_fwd",
                               kind="serve_fwd", closed=closed, meta=meta))
    return out


# ---------------------------------------------------------------------------
# the full matrix
# ---------------------------------------------------------------------------

def build_bundles(*, wire_ops: bool = True, train: bool = True,
                  serve: bool = True) -> List[TraceBundle]:
    bundles: List[TraceBundle] = []
    if wire_ops:
        bundles += wire_bundles()
    if train:
        bundles += train_bundles()
        bundles += sched_bundles()
        bundles += async_bundles()
    if serve:
        bundles += serve_bundles()
    return bundles
