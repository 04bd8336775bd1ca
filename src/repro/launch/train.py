"""Training launcher.

Real execution runs on the host's devices (``--mesh host``); the production
mesh is exercised via launch/dryrun.py. Examples:

    PYTHONPATH=src python -m repro.launch.train --arch lm-100m --smoke \
        --steps 100 --quant orq-9 --mode replicated --batch 8 --seq 128

    # mixed per-parameter-group policy: fp norms/biases, ORQ-9 elsewhere
    PYTHONPATH=src python -m repro.launch.train --arch lm-100m --smoke \
        --quant "norm|bias=fp,default=orq-9" --mode replicated

    # adaptive bit budget: per-group wire bits follow a schedule (and,
    # with --bit-budget, a bytes/step water-filling solve fed by the
    # fused encode's runtime statistics); see EXPERIMENTS.md
    PYTHONPATH=src python -m repro.launch.train --arch lm-100m --smoke \
        --bit-schedule "norm|bias=fp,default=orq@5..2" \
        --bit-budget 2e5 --resolve-every 25 --mode replicated

    # profile steps 2-4 (after the compile) into a TensorBoard/xprof log
    PYTHONPATH=src python -m repro.launch.train --arch lm-100m --smoke \
        --steps 8 --quant orq-9 --trace-dir /tmp/profile

Each step is a ``jax.profiler.StepTraceAnnotation`` ("train") holding the
host spans ``train:batch``, ``train:step``, ``train:metrics`` and
``train:checkpoint``; the step's device ops carry the program's named
scopes (README, "Splitting a step").
"""
from __future__ import annotations

import argparse
import hashlib
import json
import time

import jax
import numpy as np

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.configs.base import get_config, get_smoke_config, list_archs
from repro.core import (BitBudgetController, BitSchedule, QuantPolicy,
                        all_methods, comm)
from repro.data import SyntheticLM
from repro.launch.mesh import device_label, make_host_mesh
from repro.models import LM
from repro.optim.schedule import step_decay
from repro.train import TrainConfig, make_train_step
from repro.train.step import (ScheduledTrainStep, init_state,
                              specialize_engines)
from repro.utils.env import use_compile_cache


def _params_digest(params) -> str:
    """sha256 over the raw bytes of every parameter leaf (canonical tree
    order) — a bit-level run fingerprint."""
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(params):
        h.update(np.asarray(jax.device_get(leaf)).tobytes())
    return h.hexdigest()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm-100m", choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    # help text and validation are derived from the scheme registry, so a
    # newly registered scheme is accepted (and advertised) automatically
    ap.add_argument(
        "--quant", default="fp", metavar="SCHEME|POLICY",
        help="quantization scheme or per-parameter-group policy string. "
             f"Schemes: {', '.join(all_methods())}. Policy grammar: "
             "'pattern=scheme[,pattern=scheme...][,default=scheme]' with "
             "regex patterns matched against parameter paths (first match "
             'wins), e.g. "norm|bias=fp,embed=bingrad-b,default=orq-9".')
    ap.add_argument(
        "--bit-schedule", default=None, metavar="SCHEDULE",
        help="adaptive bit schedule: the --quant policy grammar extended "
             "with bit-ramp tokens 'family@HI..LO', HI <= 5 (e.g. "
             "\"embed=orq@5..3,norm|bias=fp,default=orq@4..1\"); per-group "
             "wire bits follow the ramp over --steps, re-resolved every "
             "--resolve-every steps (recompile on phase boundary — bits "
             "are never traced). Mutually exclusive with --quant.")
    ap.add_argument(
        "--bit-budget", type=float, default=None, metavar="BYTES",
        help="quantized-DCN bytes/step budget: each phase water-fills "
             "bits from the ramps' LO toward the deterministic ramp "
             "value, largest marginal MSE-reduction per byte first, fed "
             "by the fused encode's runtime statistics (needs "
             "--bit-schedule)")
    ap.add_argument("--resolve-every", type=int, default=50,
                    help="bit-schedule phase length in steps")
    ap.add_argument("--bucket", type=int, default=2048)
    ap.add_argument("--clip-c", type=float, default=None)
    ap.add_argument("--mode", default="replicated",
                    choices=["replicated", "fsdp"])
    ap.add_argument("--hierarchy", default="auto",
                    choices=list(comm.HIERARCHIES),
                    help="two_level runs the quantized exchange only over "
                         "the slow inter-pod (DCN) axis after a full-"
                         "precision intra-pod mean; auto picks two_level "
                         "whenever the dp mesh has >= 2 axes; "
                         "two_level_async additionally runs --local-steps "
                         "inner steps synced only over the fast intra "
                         "axis between quantized outer syncs of the "
                         "parameter delta (DiLoCo-style; needs "
                         "--pods >= 2 and replicated mode)")
    ap.add_argument("--local-steps", type=int, default=1,
                    help="two_level_async window H: inner steps per "
                         "quantized outer sync (H=1 is bit-identical to "
                         "two_level)")
    ap.add_argument("--outer-optimizer", default="nesterov",
                    choices=["nesterov", "sgd"],
                    help="outer optimizer applied to the window's "
                         "parameter delta at sync steps")
    ap.add_argument("--outer-lr", type=float, default=0.7)
    ap.add_argument("--outer-momentum", type=float, default=0.9)
    ap.add_argument("--pods", type=int, default=1,
                    help="leading pod axis size of the host mesh (>1 "
                         "builds the multi-pod ('pod','data','model') "
                         "topology the two-level exchange splits)")
    ap.add_argument("--per-leaf-exchange", action="store_true",
                    help="legacy one-collective-per-leaf exchange "
                         "(default: fused flat-buffer engine)")
    ap.add_argument("--error-feedback", action="store_true",
                    help="accumulate EF residuals (replicated mode and "
                         "fused fsdp; persisted in TrainState.ef)")
    ap.add_argument("--exchange-chunk", type=int, default=None,
                    help="cap fused-collective size (elements) for memory")
    ap.add_argument("--pipeline-chunks", type=int, default=1,
                    help="split each fused exchange into K bucket-row "
                         "chunks so chunk k's collective overlaps chunk "
                         "k+1's encode (bit-identical to K=1)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default=None,
                    help="save final PARAMS here (params-only snapshot)")
    ap.add_argument("--state-checkpoint", default=None,
                    help="save the FULL TrainState here (params + "
                         "optimizer + EF residuals + outer state — what "
                         "--resume restores bit-for-bit, including mid-"
                         "window two_level_async positions)")
    ap.add_argument("--checkpoint-at", type=int, default=None,
                    metavar="STEP",
                    help="write --state-checkpoint after this step instead "
                         "of at the end (the run continues): a later "
                         "--resume of it must reproduce the rest of THIS "
                         "run bit-for-bit — lr boundaries and data stream "
                         "key off the absolute step, so the comparison "
                         "run must use the same --steps")
    ap.add_argument("--resume", default=None, metavar="STATE_CKPT",
                    help="restore a --state-checkpoint and continue from "
                         "its step counter (strict load: the tree must "
                         "match the configured run exactly)")
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="profile steps start+2 .. start+4 (after the "
                         "compile) with jax.profiler and write the trace "
                         "under DIR")
    args = ap.parse_args(argv)
    if args.checkpoint_at is not None and not args.state_checkpoint:
        ap.error("--checkpoint-at needs --state-checkpoint")
    use_compile_cache()

    schedule = None
    if args.bit_schedule is not None:
        if args.quant != "fp":
            ap.error("--bit-schedule and --quant are mutually exclusive "
                     "(the schedule IS the policy; put static entries in "
                     "the schedule string)")
        if args.bit_budget is not None and args.per_leaf_exchange:
            ap.error("--bit-budget needs the fused exchange (its "
                     "statistics feed) — drop --per-leaf-exchange")
        try:
            schedule = BitSchedule.parse(args.bit_schedule,
                                         bucket_size=args.bucket,
                                         clip_c=args.clip_c)
        except ValueError as e:
            ap.error(str(e))
    try:
        policy = (None if schedule is not None else
                  QuantPolicy.parse(args.quant, bucket_size=args.bucket,
                                    clip_c=args.clip_c))
    except ValueError as e:
        ap.error(str(e))

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    model = LM(cfg)
    try:
        mesh = make_host_mesh(model=args.model_parallel, pods=args.pods)
    except ValueError as e:
        ap.error(str(e))
    try:
        tcfg = TrainConfig(
            policy=policy,
            mode=args.mode,
            hierarchy=args.hierarchy,
            local_steps=args.local_steps,
            outer_optimizer=args.outer_optimizer,
            outer_lr=args.outer_lr,
            outer_momentum=args.outer_momentum,
            fused_exchange=not args.per_leaf_exchange,
            error_feedback=args.error_feedback,
            exchange_chunk_elems=args.exchange_chunk,
            pipeline_chunks=args.pipeline_chunks,
            # the water-filling solve is statistics-driven; the pure ramp
            # needs no feed, so skip the per-step stats fetch without it
            collect_stats=(schedule is not None
                           and args.bit_budget is not None))
    except ValueError as e:
        ap.error(str(e))
    lr_fn = step_decay(args.lr, [args.steps // 2, 3 * args.steps // 4])
    controller = None
    if schedule is not None:
        controller = BitBudgetController(
            schedule, total_steps=args.steps,
            resolve_every=args.resolve_every,
            dcn_budget_bytes=args.bit_budget)
        step_fn = ScheduledTrainStep(model, mesh, tcfg, controller, lr_fn)
        # price assignments with the SAME per-link accounting the
        # benchmarks report, from the engines AS BUILT (shared path)
        n_intra = max(1, step_fn.skeleton.n_intra)
        n_inter = max(1, step_fn.plan.n_dp // n_intra)
        # two_level_async amortizes the outer exchange over the H-step
        # window — the controller budgets the same per-step DCN spend the
        # benchmarks report
        sync_every = (args.local_steps if comm.resolve_hierarchy(
            args.hierarchy, step_fn.plan.dp_axes,
            args.local_steps) == "two_level_async" else 1)

        def cost_fn(phase_policy):
            eng = specialize_engines(step_fn.skeleton, phase_policy)
            total, _ = comm.observed_link_stats(
                eng.pex, n_intra=n_intra, n_inter=n_inter,
                sync_every=sync_every)
            return total["dcn_q_bytes"]

        controller.cost_fn = cost_fn
        init_tcfg = step_fn.init_config
    else:
        init_tcfg = tcfg
    state = init_state(model, mesh, init_tcfg, jax.random.key(args.seed))
    if schedule is None:
        step_fn, _ = make_train_step(model, mesh, tcfg, lr_fn)
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       batch_size=args.batch, seed=args.seed)

    start = 0
    if args.resume:
        # strict full-state load against the freshly built state's tree:
        # params + optimizer + EF residuals + outer anchor/momentum all
        # round-trip, so a mid-window two_level_async run reproduces its
        # next outer sync bit-for-bit
        state, _ = load_checkpoint(args.resume, like=state)
        start = int(state.step)
        print(f"resumed {args.resume} at step {start}")
    history = []
    print(f"device: {device_label()}, mesh {dict(mesh.shape)}")
    span = jax.profiler.TraceAnnotation
    # steps start+2 .. start+4: after the compile, one traced window
    traced = (range(start + 2, min(start + 5, args.steps))
              if args.trace_dir else range(0))
    t0 = time.perf_counter()
    for i in range(start, args.steps):
        if traced and i == traced.start:
            jax.block_until_ready(state)
            jax.profiler.start_trace(args.trace_dir)
        with jax.profiler.StepTraceAnnotation("train", step_num=i):
            with span("train:batch"):
                batch = data.batch(i)
            with span("train:step"):
                state, metrics = step_fn(state, batch,
                                         jax.random.key(args.seed))
            if i == start:
                # the first step compiles; the rate counts from its end
                jax.block_until_ready(state)
                t1 = time.perf_counter()
            if args.state_checkpoint and args.checkpoint_at == i + 1:
                with span("train:checkpoint"):
                    save_checkpoint(args.state_checkpoint, state,
                                    step=int(state.step))
                print(f"state checkpoint -> {args.state_checkpoint} "
                      f"at step {i + 1}")
            if i % args.log_every == 0 or i == args.steps - 1:
                with span("train:metrics"):
                    loss = float(metrics["loss"])
                    row = {"step": i, "loss": loss,
                           "nll": float(metrics["nll"]),
                           "lr": float(metrics["lr"])}
                bits = ""
                if controller is not None:
                    row["bits"] = list(step_fn.last_assignment)
                    bits = " bits " + ",".join(
                        "fp" if b is None else str(b) for b in row["bits"])
                history.append(row)
                rate = (f"{(time.perf_counter() - t1) / (i - start):.2f}"
                        f"s/step" if i > start else
                        f"first step {t1 - t0:.2f}s, compile included")
                print(f"step {i:5d} loss {loss:.4f}{bits} ({rate})")
        if traced and i == traced.stop - 1:
            jax.block_until_ready(state)
            jax.profiler.stop_trace()
            print(f"trace of steps {traced.start}-{i} -> {args.trace_dir}")
    # bit-level fingerprint of the final parameters: two runs of an
    # exchange schedule that is supposed to be bit-identical (e.g.
    # --pipeline-chunks K vs 1) must print the same digest
    digest = _params_digest(state.params)
    print("params sha256", digest)
    if args.checkpoint:
        with span("train:checkpoint"):
            save_checkpoint(args.checkpoint, state.params,
                            step=int(state.step))
        print("checkpoint ->", args.checkpoint)
    if args.state_checkpoint and args.checkpoint_at is None:
        with span("train:checkpoint"):
            save_checkpoint(args.state_checkpoint, state,
                            step=int(state.step))
        print("state checkpoint ->", args.state_checkpoint)
    if args.metrics_out:
        out = {"history": history, "params_sha256": digest}
        if controller is not None:
            out["bit_decisions"] = controller.decisions
        with open(args.metrics_out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
