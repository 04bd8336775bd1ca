#!/usr/bin/env python3
"""Proof that quantized training and quantized-KV serving run on a TPU.

    python chip_smoke.py             # one chip: kernels vs oracle, train, serve
    python chip_smoke.py --chips 4   # only the 4-chip data-parallel exchange

Everything runs in this one process, at the full width of ``lm-100m``
(12 layers, d_model 768, vocab 32768) with random weights from ``--seed``:

* kernels vs oracle: the fused Pallas kernels (``use_kernels=True``)
  against the jnp oracle (``use_kernels=False``) on the same inputs, for
  the gradient wire ops (orq-9, bingrad-b) and the KV-cache append and
  dequant-attention;
* training: the ``launch/train.py`` path (``make_host_mesh`` ->
  ``TrainConfig`` -> ``init_state`` -> ``make_train_step``), replicated,
  orq-9 with error feedback and fp;
* serving: the ``launch/serve.py`` paged engine with ``--kv-quant orq-9``
  and ``bf16``;
* ``--chips 4``: a (data=4, model=1) mesh, orq-9 with error feedback
  against fp, in replicated and in fsdp mode.

Exits non-zero when JAX finds no TPU, when a Pallas kernel would run in
interpret mode or be swapped for its oracle, and when any check fails.
The last line of stdout is ``{"ok": true, "device": {...}}``. Times and
memory printed on the way are this run's chip numbers. JAX's compile
cache lives in ``JAX_COMPILATION_CACHE_DIR`` where that is set, else in
``.jax_cache/`` here.
"""
import argparse
import hashlib
import importlib.metadata
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "lm-100m"
BUCKET = 2048                 # --bucket default of launch/train.py
GRAD_ROWS = 4096              # gradient buckets in the kernel check
KV_BATCH, KV_CONTEXT = 8, 1024
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, MESH_STEPS = 8, 512, 5, 3
#: kernel vs oracle: float outputs may differ by rounding noise
#: (|diff| <= FLOAT_NOISE * max|oracle|) anywhere, and by more than that
#: (a flipped rounding decision) in at most MISMATCH_SHARE of elements;
#: wire indices may differ in at most MISMATCH_SHARE of elements
FLOAT_NOISE = 1e-6
MISMATCH_SHARE = 1e-4
#: ORQ fits: the ``orq_fit`` kernel sums a bucket's interval in another
#: order than XLA, so a midpoint solve on a half-way tie may take the
#: neighbouring value; at most this share of buckets may hold another
#: level table (1 in 4,096 against the sort-based solve on the CPU), and
#: the other checks run on the rest
TIE_SHARE = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"FAILED: {what}")
    log(f"  ok: {what}")


# ---------------------------------------------------------------- device

def device_check(n_chips: int):
    import jax
    import jaxlib

    devs = jax.devices()
    d0 = devs[0]
    log(f"platform {d0.platform}, device_kind {d0.device_kind}, "
        f"devices visible {len(devs)}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}")
    if d0.platform != "tpu":
        raise SystemExit(f"no TPU found: JAX runs on {d0.platform!r}; "
                         "this smoke test never falls back to it")
    if len(devs) < n_chips:
        raise SystemExit(f"--chips {n_chips} needs {n_chips} devices, "
                         f"{len(devs)} visible")
    from repro.utils.env import kernels_enabled, pallas_interpret

    if pallas_interpret():
        raise SystemExit("Pallas interpret mode is on "
                         "(REPRO_PALLAS_INTERPRET): kernels would not run "
                         "compiled")
    if not kernels_enabled():
        raise SystemExit("REPRO_USE_KERNELS=0 swaps every kernel for its "
                         "oracle")
    return d0


class CompileCounter:
    """Persistent compile-cache hits and misses, from JAX's own events."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# -------------------------------------------------------- kernels vs oracle

def _compare_ints(what, got, want):
    share = float(np.mean(np.asarray(got) != np.asarray(want)))
    check(share <= MISMATCH_SHARE,
          f"{what}: differing share {share:.3e} (bound {MISMATCH_SHARE:g})")


def _compare_floats(what, got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = np.abs(got - want)
    beyond = float(np.mean(err > FLOAT_NOISE * np.max(np.abs(want))))
    check(beyond <= MISMATCH_SHARE,
          f"{what}: exactly equal {float(np.mean(err == 0)):.6f}, beyond "
          f"rounding noise {beyond:.3e} (bound {MISMATCH_SHARE:g}), max abs "
          f"err {float(err.max()):.3e}")


def _same_fit_rows(what, got, want) -> np.ndarray:
    """Rows whose ORQ level tables agree; the others may be no more than
    TIE_SHARE of the rows."""
    same = (np.asarray(got) == np.asarray(want)).all(axis=-1)
    share = float(np.mean(~same))
    check(share <= TIE_SHARE, f"{what}: buckets with another level table "
          f"{share:.3e} (bound {TIE_SHARE:g})")
    return same


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.core import encode, make_quantizer, rounding
    from repro.core.comm import wire
    from repro.kernels import ops
    from repro.kernels.fused_kv import append_kv
    from repro.serve.kv_cache import KVQuantSpec

    log(f"== kernels vs oracle ({GRAD_ROWS}x{BUCKET} gradient buckets, "
        f"{ARCH} KV rows {KV_BATCH}x{KV_CONTEXT})")
    k = jax.random.split(jax.random.key(seed), 6)
    n_valid = GRAD_ROWS * BUCKET - 777                   # ragged last bucket
    g = jax.random.normal(k[0], (GRAD_ROWS, BUCKET)) * 1e-3
    mask = (jnp.arange(GRAD_ROWS * BUCKET) < n_valid).reshape(g.shape)
    workers = g[None] + 1e-4 * jax.random.normal(k[1], (4,) + g.shape)
    # every fused wire width (1-5 bits): the pack has to place fields at
    # lane offsets that are not multiples of 128
    for name in ("terngrad", "orq-5", "orq-17", "orq-9", "bingrad-b"):
        qz = make_quantizer(name, bucket_size=BUCKET)
        bits = qz.wire_bits_per_element
        (wk, lk), (wo, lo) = (wire.encode(qz, g, mask, k[2], use_kernels=u)
                              for u in (True, False))
        rows = (_same_fit_rows(f"{name} fit", lk, lo) if qz.method == "orq"
                else np.ones(GRAD_ROWS, bool))
        _compare_ints(f"{name} encode indices ({bits}-bit words)",
                      np.asarray(encode.unpack(wk, bits, BUCKET))[rows],
                      np.asarray(encode.unpack(wo, bits, BUCKET))[rows])
        _compare_floats(f"{name} encode levels", np.asarray(lk)[rows],
                        np.asarray(lo)[rows])
        if name not in ("orq-9", "bingrad-b"):
            continue
        _compare_floats(f"{name} qdq", *(
            np.asarray(wire.qdq(qz, g, mask, k[2], use_kernels=u))[rows]
            for u in (True, False)))
        units = [wire.encode(qz, w, mask, k[3]) for w in workers]
        words = jnp.stack([u[0] for u in units])
        levels = jnp.stack([u[1] for u in units])
        _compare_floats(f"{name} decode_mean (4 workers)", *(
            wire.decode_mean(qz, words, levels, BUCKET, use_kernels=u)
            for u in (True, False)))

    cfg = get_config(ARCH)
    spec = KVQuantSpec("orq-9", cfg.num_kv_heads, cfg.resolved_head_dim)
    qz = spec.quantizer()
    R, d, hd = KV_BATCH * KV_CONTEXT, spec.d, cfg.resolved_head_dim
    kr, vr = jax.random.normal(k[4], (2, R, d))
    rbits = rounding.random_bits(k[5], (2 * R, d))
    kern, orac = (append_kv(qz, kr, vr, rbits, use_kernels=u)
                  for u in (True, False))           # (kw, klv, vw, vlv)
    for i, part in enumerate(("K", "V")):
        rows = _same_fit_rows(f"append_kv {part} fit", kern[2 * i + 1],
                              orac[2 * i + 1])
        _compare_ints(f"append_kv {part} indices", *(
            np.asarray(encode.unpack(x[2 * i], spec.bits, d))[rows]
            for x in (kern, orac)))
        _compare_floats(f"append_kv {part} levels",
                        np.asarray(kern[2 * i + 1])[rows],
                        np.asarray(orac[2 * i + 1])[rows])
    kw, klv, vw, vlv = (x.reshape(KV_BATCH, KV_CONTEXT, -1) for x in kern)
    q = jax.random.normal(k[0], (KV_BATCH, 1, cfg.num_heads, hd))
    fill = jax.random.randint(k[1], (KV_BATCH, 1, 1), 1, KV_CONTEXT + 1)
    amask = jnp.arange(KV_CONTEXT)[None, None, :] < fill
    _compare_floats("decode_attend", *(
        ops.decode_attend(q, kw, klv, vw, vlv, amask, bits=spec.bits,
                          kv_heads=cfg.num_kv_heads, scale=hd ** -0.5,
                          use_kernels=u) for u in (True, False)))


# --------------------------------------------------------------- training

def _peak_bytes(devices) -> str:
    """Largest ``peak_bytes_in_use`` over ``devices`` (process lifetime)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return "not reported"
    return str(max(s["peak_bytes_in_use"] for s in stats))


def _prepare(mesh, mode: str, quant: str, *, steps: int, batch: int,
             seed: int) -> dict:
    """State, batches and the lowered train step, built the way
    ``launch/train.py`` builds them."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import get_config
    from repro.core import QuantPolicy
    from repro.data import SyntheticLM
    from repro.models import LM
    from repro.optim.schedule import step_decay
    from repro.train import TrainConfig, make_train_step
    from repro.train.step import init_state

    cfg = get_config(ARCH)
    model = LM(cfg)
    tcfg = TrainConfig(policy=QuantPolicy.parse(quant, bucket_size=BUCKET),
                       mode=mode, error_feedback=quant != "fp")
    lr_fn = step_decay(0.05, [steps // 2, 3 * steps // 4])
    state = init_state(model, mesh, tcfg, jax.random.key(seed))
    step_fn, _ = make_train_step(model, mesh, tcfg, lr_fn)
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
                       batch_size=batch, seed=seed)
    make_batch = jax.jit(data.batch)
    bsh = NamedSharding(mesh, P("data"))
    batches = [jax.device_put(make_batch(i), bsh) for i in range(steps)]
    key = jax.random.key(seed)
    return {"state": state, "batches": batches, "key": key,
            "lowered": step_fn.lower(state, batches[0], key),
            "tag": f"{mode} {quant}{' +EF' if tcfg.error_feedback else ''}"}


def _run(mesh, prep: dict, compiled) -> dict:
    import jax

    state, key = prep["state"], prep["key"]
    losses, times = [], []
    for batch in prep["batches"]:
        t = time.perf_counter()
        state, metrics = compiled(state, batch, key)
        jax.block_until_ready((state, metrics))
        times.append(time.perf_counter() - t)
        losses.append(float(metrics["loss"]))
    # one params digest per device: replicas must agree bit for bit
    per_dev = {}
    for leaf in jax.tree_util.tree_leaves(state.params):
        for sh in leaf.addressable_shards:
            per_dev.setdefault(sh.device.id, hashlib.sha256()).update(
                np.asarray(sh.data).tobytes())
    tag, steady = prep["tag"], times[1:]
    log(f"  {tag}: losses {' '.join(f'{x:.6f}' for x in losses)}")
    log(f"  {tag}: this run's chip numbers: steady step "
        f"{np.mean(steady) * 1e3:.1f} ms (mean of steps 1-{len(times) - 1}: "
        f"{' '.join(f'{x * 1e3:.1f}' for x in steady)} ms), "
        f"peak_bytes_in_use {_peak_bytes(mesh.devices.flat)}")
    return {"losses": losses, "hlo": compiled.as_text(),
            "lowered": prep["lowered"].as_text(),
            "digests": [per_dev[d].hexdigest() for d in sorted(per_dev)]}


def train_runs(mesh, cases, *, steps: int, batch: int, seed: int):
    """Train ``steps`` steps for each (mode, quant) case. The steps are
    built one by one, compiled concurrently (XLA releases the GIL while it
    compiles) and then run one after another, each timed with
    ``block_until_ready``. Returns (results by case, set-up seconds)."""
    t0 = time.perf_counter()
    preps = {c: _prepare(mesh, *c, steps=steps, batch=batch, seed=seed)
             for c in cases}
    with ThreadPoolExecutor(len(cases)) as ex:
        compiled = dict(zip(cases, ex.map(
            lambda c: preps[c]["lowered"].compile(), cases)))
    setup = time.perf_counter() - t0
    log(f"  set-up (init + trace + compile of {len(cases)} steps): "
        f"{setup:.1f}s")
    return {c: _run(mesh, preps.pop(c), compiled.pop(c)) for c in cases}, \
        setup


def phase_train(seed: int) -> float:
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(devices=1)
    log(f"== training {ARCH}, 1 chip, replicated, batch {TRAIN_BATCH}, "
        f"seq {TRAIN_SEQ}, {TRAIN_STEPS} steps")
    runs, setup = train_runs(
        mesh, [("replicated", "orq-9"), ("replicated", "fp")],
        steps=TRAIN_STEPS, batch=TRAIN_BATCH, seed=seed)
    q, fp = runs[("replicated", "orq-9")], runs[("replicated", "fp")]
    for name, r in (("orq-9", q), ("fp", fp)):
        check(bool(np.all(np.isfinite(r["losses"]))), f"{name}: losses finite")
    check(q["losses"][0] == fp["losses"][0],
          "step-0 loss of orq-9 equals fp (same params, same batch)")
    check("tpu_custom_call" in q["hlo"],
          "orq-9 train step HLO holds tpu_custom_call")
    return setup


def phase_mesh(seed: int) -> float:
    """Four chips: the quantized exchange against fp on one mesh."""
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=4, model=1, devices=4)
    batch = 4 * TRAIN_BATCH
    log(f"== training {ARCH}, mesh {dict(mesh.shape)}, batch {batch}, "
        f"seq {TRAIN_SEQ}, {MESH_STEPS} steps")
    modes = ("replicated", "fsdp")
    runs, setup = train_runs(
        mesh, [(m, q) for m in modes for q in ("orq-9", "fp")],
        steps=MESH_STEPS, batch=batch, seed=seed)
    for mode in modes:
        q, fp = runs[(mode, "orq-9")], runs[(mode, "fp")]
        for name, r in (("orq-9", q), ("fp", fp)):
            check(bool(np.all(np.isfinite(r["losses"]))),
                  f"{mode} {name}: losses finite")
            if mode == "replicated":
                check(len(set(r["digests"])) == 1 and len(r["digests"]) == 4,
                      f"{mode} {name}: params identical on all 4 devices "
                      f"after {MESH_STEPS} steps")
        check(q["losses"][0] == fp["losses"][0],
              f"{mode}: step-0 loss of orq-9 equals fp")
        hlo = q["hlo"]
        # fsdp's parameter all-gather comes out of the TPU compiler as an
        # all-reduce of a zero-padded buffer; the lowered program holds it
        ops = ("all-to-all", "tpu_custom_call") + (
            ("all-gather",) if mode == "replicated" else ())
        for op in ops:
            check(op in hlo, f"{mode} orq-9 compiled step HLO holds {op}")
        check("all_gather" in q["lowered"],
              f"{mode} orq-9 lowered step holds all_gather")
        log(f"  {mode} orq-9 compiled step: " + ", ".join(
            f"{op} x{hlo.count(op + '(')}" for op in
            ("all-to-all", "all-gather", "all-gather-start", "all-reduce",
             "all-reduce-start", "reduce-scatter")))
    return setup


# ---------------------------------------------------------------- serving

def phase_serve(seed: int) -> float:
    from repro.launch import serve

    log(f"== serving {ARCH}: paged engine, 4 requests, prompt 64, gen 16, "
        f"max-len 256, page 16")
    toks, setup = {}, 0.0
    for kv in ("orq-9", "bf16"):
        t0 = time.perf_counter()
        toks[kv] = serve.serve([
            "--arch", ARCH, "--kv-quant", kv, "--batch", "4",
            "--prompt-len", "64", "--gen", "16", "--max-len", "256",
            "--page-size", "16", "--seed", str(seed)])
        setup += time.perf_counter() - t0
        check(toks[kv].shape == (4, 16),
              f"kv={kv}: every request returned 16 tokens")
    agree = float(np.mean(toks["orq-9"] == toks["bf16"]))
    log(f"  greedy agreement orq-9 vs bf16: {agree:.4f}")
    return setup


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the four-chip data-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    d0 = device_check(args.chips)
    from repro.utils.env import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    counter = CompileCounter()
    if args.chips == 4:
        setup = phase_mesh(args.seed)
    else:
        t0 = time.perf_counter()
        phase_kernels(args.seed)
        setup = time.perf_counter() - t0
        setup += phase_train(args.seed)
        setup += phase_serve(args.seed)
    log(f"set-up time (compile + init, all phases): {setup:.1f}s; compile "
        f"cache hits {counter.hits}, misses {counter.misses}; wall "
        f"{time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
