"""The general training runner: one compiled ``make_train_step``, fed
seeded batches, timed over a window, and checked against the plain
reference.

A traffic file of ``kind`` ``train`` gives the job: ``seq``,
``batch_per_chip``, the mesh's ``data`` size, the exchange (``quant``,
``bucket``, ``mode``, ``error_feedback``), SGD's ``lr`` and ``momentum``,
and ``batches``, the number of distinct batches made in set-up (the
window cycles through them).

Set-up builds the state from the benchmark's own weights, compiles the
step, and drives that same compiled step through steps 1-3 on batches
0-2. What the optimizer got at step 1 (SGD's momentum buffer, which after
one step is the exchanged gradient) and the parameters' change after
step 3 are reduced to one norm per leaf on the device. The window then
runs steps 4, 5, ... each ending in ``block_until_ready``. Once it has
closed and the program's state is freed, the reference trains three
steps from the same weights and batches, and the numbers that the cell's
``limits/<cell>.json`` names are compared (``numbers`` lists them).
"""
from __future__ import annotations

import gc
import time
from types import SimpleNamespace
from typing import Any, Dict, List

import numpy as np

from harness import common
from harness.common import log

SETUP_STEPS = 3


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def moved_leaves(ref_grad_norms: np.ndarray, share: float = 1e-3
                 ) -> np.ndarray:
    """Leaves whose reference gradient is not nought to rounding: at
    least ``share`` of the median leaf's."""
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= share * np.median(g)


def leaf_gaps(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Each leaf's gap between the program's norm and the reference's,
    against the larger of that leaf's reference norm and the median
    leaf's."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return np.abs(prog - ref) / np.maximum(ref, np.median(ref))


def numbers(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """What the program's first steps read against the reference's: the
    first step's loss gap and the largest over the steps; the worst and
    the median leaf's gap of the first gradient's norm and of the change's
    norm (the change over leaves that move)."""
    keep = moved_leaves(ref["grad_norms"])
    g = leaf_gaps(prog["grad_norms"], ref["grad_norms"])
    d = leaf_gaps(np.asarray(prog["delta_norms"])[keep],
                  np.asarray(ref["delta_norms"])[keep])
    dl = np.abs(np.asarray(prog["losses"], np.float64)
                - np.asarray(ref["losses"], np.float64))
    return {"loss1_gap": float(dl[0]), "loss_gap": float(np.max(dl)),
            "grad_norm_gap": float(np.max(g)),
            "update_norm_gap": float(np.max(d)),
            "median_grad_norm_gap": float(np.median(g)),
            "median_update_norm_gap": float(np.median(d))}


def compare(prog: Dict[str, Any], ref: Dict[str, Any],
            limits: Dict[str, float]) -> List[Dict[str, Any]]:
    """The numbers that the cell's limits name, each with its limit; the
    others are logged."""
    n = numbers(prog, ref)
    log("not compared: " + ", ".join(f"{k} {v!r}" for k, v in n.items()
                                     if k not in limits))
    return [common.check(k, n[k], v) for k, v in limits.items()]


class TrainCell:
    """The program's compiled step and its state, built once per process
    (``setup``) and driven from a seed (``start``)."""

    def __init__(self, cell: common.Cell, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from harness.data import batch_program
        from harness.weights import make_params
        from repro.core import QuantPolicy
        from repro.launch.mesh import make_host_mesh
        from repro.models import LM
        from repro.optim.schedule import constant_lr
        from repro.train import TrainConfig, make_train_step
        from repro.train.step import init_state

        t = cell.traffic
        if t["mode"] != "replicated":
            raise common.BenchError(f"mode {t['mode']!r}: the runner builds "
                                    "replicated states only")
        self.cell, self.t = cell, t
        self.devices = devices
        self.mcfg = common.model_config(cell.config)
        self.model = LM(self.mcfg)
        self.mesh = make_host_mesh(data=t["data"], model=1,
                                   devices=len(devices))
        self.tcfg = TrainConfig(
            policy=QuantPolicy.parse(t["quant"], bucket_size=t["bucket"]),
            mode=t["mode"], error_feedback=t["error_feedback"],
            momentum=t["momentum"])
        self.global_batch = t["batch_per_chip"] * t["data"]
        self.tokens_per_step = self.global_batch * t["seq"]
        key0 = jax.random.key(0)
        self.aparams = jax.eval_shape(self.model.init, key0)
        self.astate = jax.eval_shape(
            lambda k: init_state(self.model, self.mesh, self.tcfg, k), key0)
        self.step_fn, _ = make_train_step(self.model, self.mesh, self.tcfg,
                                          constant_lr(t["lr"]))
        self.rep = NamedSharding(self.mesh, P())
        self.bsh = NamedSharding(self.mesh, P("data"))
        self.compiled = None
        self.norms = jax.jit(_leaf_norms)
        build = make_params(self.aparams, jnp.float32)
        self.init_params = jax.jit(build, out_shardings=self.rep)
        self.batch_prog = jax.jit(batch_program(
            self.mcfg.vocab_size, t["seq"], self.global_batch),
            out_shardings=self.bsh)
        self.delta = jax.jit(lambda p, key: _leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, p, build(key))))

    def make_state(self, seed: int):
        """The program's TrainState from the benchmark's weights: SGD's
        momentum and the error-feedback residuals start at zero."""
        import jax
        import jax.numpy as jnp

        from harness.weights import seed_key

        astate = self.astate
        params = self.init_params(seed_key(seed))

        def build(params):
            zeros = lambda t: jax.tree_util.tree_map(      # noqa: E731
                lambda a: jnp.zeros(a.shape, a.dtype), t)
            return astate._replace(params=params, opt=zeros(astate.opt),
                                   step=jnp.int32(0), ef=zeros(astate.ef))

        state = jax.jit(build, out_shardings=self.rep,
                        donate_argnums=0)(params)
        got = jax.tree_util.tree_structure(state)
        want = jax.tree_util.tree_structure(astate)
        if got != want:
            raise common.BenchError(f"state structure {got} != program's "
                                    f"{want}")
        return state

    def make_batches(self, seed: int):
        import jax.numpy as jnp

        from harness.data import chain_table
        from harness.weights import seed_key

        table = jnp.asarray(chain_table(seed, self.mcfg.vocab_size))
        return [self.batch_prog(seed_key(seed), table, i)
                for i in range(self.t["batches"])]

    def start(self, seed: int, *salt) -> Dict[str, Any]:
        """State and batches from ``seed``, the step compiled (once per
        process), and steps 1-3 driven through it. ``salt`` changes only
        the step's rounding key."""
        from harness.weights import fold, seed_key

        state = self.make_state(seed)
        batches = self.make_batches(seed)
        key = fold(seed_key(seed), "step", *salt)
        if self.compiled is None:
            t0 = time.perf_counter()
            self.compiled = self.step_fn.lower(state, batches[0],
                                               key).compile()
            log(f"train step compiled or loaded in "
                f"{time.perf_counter() - t0:.1f}s")
        losses, gnorm = [], None
        for i in range(SETUP_STEPS):
            state, m = self.compiled(state, batches[i], key)
            losses.append(float(m["loss"]))
            if i == 0:
                gnorm = np.asarray(self.norms(state.opt))
        # each leaf's ||params - params_0||, params_0 made again from the
        # seed inside the reduction
        dnorm = np.asarray(self.delta(state.params, seed_key(seed)))
        return {"state": state, "batches": batches, "key": key,
                "losses": np.asarray(losses), "grad_norms": gnorm,
                "delta_norms": dnorm}

    def window(self, run: Dict[str, Any], seconds: float
               ) -> Dict[str, Any]:
        """Steps until ``seconds`` have passed, each ending in
        ``block_until_ready``."""
        import jax

        state, batches, key = run["state"], run["batches"], run["key"]
        n, losses, step_s = SETUP_STEPS, [], []
        ann = jax.profiler.TraceAnnotation
        t0 = time.perf_counter()
        with ann("bench:window"):
            while time.perf_counter() - t0 < seconds:
                ts = time.perf_counter()
                with ann("bench:step"):
                    state, m = self.compiled(state, batches[n % len(batches)],
                                             key)
                    loss = m["loss"].block_until_ready()
                step_s.append(time.perf_counter() - ts)
                losses.append(loss)
                n += 1
            jax.block_until_ready(state)
        elapsed = time.perf_counter() - t0
        run["state"] = state
        losses = [float(x) for x in losses]
        return {"elapsed": elapsed, "steps": len(step_s), "step_s": step_s,
                "failed": int(sum(not np.isfinite(x) for x in losses))}

    def reference(self, seed: int, matmul: str = "f32",
                  half_batch: bool = False) -> Dict[str, Any]:
        """The plain reference's three steps from the same weights and
        batches (made again from the seed)."""
        from harness.weights import seed_key

        ref = common.reference_module(self.cell.config)
        params = self.init_params(seed_key(seed))
        batches = [b["tokens"] for b in self.make_batches(seed)[:SETUP_STEPS]]
        return ref.train(params, batches, self.cell.config, self.t["lr"],
                         self.t["momentum"], steps=SETUP_STEPS,
                         matmul=matmul, half_batch=half_batch)


def run(cell: common.Cell, args, devices, counter, t_start: float) -> None:
    from harness.cost import peaks

    pk = peaks(devices[0].device_kind)
    tc = TrainCell(cell, devices)
    prog = tc.start(args.seed)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s (compile cache hits {counter.hits}, misses "
        f"{counter.misses}); setup losses {prog['losses']}")
    if args.trace:
        common.start_trace()
    counter.mark()
    w = tc.window(prog, args.seconds)
    in_window = counter.window()
    trace = common.stop_trace() if args.trace else None
    device = common.device_info(devices)
    tokens_per_s = w["steps"] * tc.tokens_per_step / w["elapsed"]
    log(f"window: {w['steps']} steps in {w['elapsed']:.3f}s, "
        f"{tokens_per_s:.1f} tokens/s, step s {w['step_s']}, programs "
        f"compiled or loaded inside it {in_window}")
    del prog["state"], prog["batches"]
    gc.collect()
    t_ref = time.perf_counter()
    ref = tc.reference(args.seed)
    log(f"reference {time.perf_counter() - t_ref:.1f}s: losses "
        f"{ref['losses']}")
    limits = common.load_limits(cell.name)
    checks = compare(prog, ref, limits)
    checks.append(common.check("window_compiles", in_window, 0))
    if args.trace:
        ns = SimpleNamespace(
            trace=trace, peaks=pk, chips=len(devices), config=cell.config,
            traffic=cell.traffic, steps=w["steps"],
            tokens_per_s=tokens_per_s)
        metrics = common.read_per_layer(cell, ns)
        device["busy_s"] = trace.mean_busy_ns() * 1e-9
        device["window_s"] = trace.window_ns * 1e-9
        breakdown = {"device_ops": trace.top_ops(),
                     "idle_gaps": trace.idle_gaps()}
    else:
        metrics = {"train_tokens_per_s": {"value": tokens_per_s,
                                          "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        breakdown = None
    common.emit(correct=all(c["ok"] for c in checks), attempted=w["steps"],
                failed=w["failed"], metrics=metrics, device=device,
                checks=checks, breakdown=breakdown)
