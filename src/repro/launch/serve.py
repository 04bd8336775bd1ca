"""Serving launcher: batched greedy decoding on the host mesh.

Two paths:

  dense (default)      ring-buffer bf16 cache via make_serve_step; prompt
                       prefill runs chunked through the cache-filling
                       prefill step (``--prefill-chunk N``) or token-by-
                       token through the decode path (``--prefill-chunk
                       0``, the reference loop).
  paged (--kv-quant)   the continuous-batching engine over the paged
                       quantized KV cache (``--kv-quant orq-9`` etc.;
                       ``--kv-quant bf16`` is the unquantized escape
                       hatch, greedy-identical to the dense path at equal
                       context).

Timing starts AFTER a warm-up step on a throwaway cache, and prefill /
decode throughput are reported separately. A sha256 digest of the
generated tokens is printed for scheme-equivalence smokes.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --smoke \
        --batch 4 --prompt-len 16 --gen 32 --prefill-chunk 8
    PYTHONPATH=src python -m repro.launch.serve --smoke --kv-quant orq-9
"""
from __future__ import annotations

import argparse
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, get_smoke_config, list_archs
from repro.launch.mesh import device_label, make_host_mesh
from repro.models import LM
from repro.serve import Engine, ServeConfig
from repro.serve.step import (make_chunked_prefill_step, make_serve_step,
                              plan_serve_sharding)
from repro.utils.env import use_compile_cache


def _digest(toks: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(
        np.asarray(toks, np.int32)).tobytes()).hexdigest()


def _serve_dense(args, cfg, model, params, prompt):
    mesh = make_host_mesh()
    cache = model.init_cache(args.batch, args.max_len)
    acache = jax.eval_shape(lambda: cache)
    aparams = jax.eval_shape(lambda: params)
    plan = plan_serve_sharding(model, aparams, acache, mesh)
    step = make_serve_step(model, mesh, plan)

    if cfg.encoder:
        key = jax.random.key(args.seed + 2)
        enc = jax.random.normal(key, (args.batch, cfg.encoder.num_frames,
                                      cfg.d_model)) * 0.02
        cache = model.warm_cache(params, cache, enc.astype(jnp.bfloat16))

    chunk = args.prefill_chunk
    if chunk and not model.supports_chunked_prefill():
        print("note: arch has no chunked-prefill path (stateful/MLA "
              "layers); falling back to the token-by-token loop")
        chunk = 0
    if chunk:
        # chunked prefill writes at absolute slots (no ring wrap), so the
        # prompt must fit the smallest layer cache (window for attn_local)
        min_c = min((cfg.window if s.kind == "attn_local" else args.max_len)
                    for s in model.specs)
        if args.prompt_len > min_c:
            print(f"note: prompt {args.prompt_len} exceeds the smallest "
                  f"layer cache ({min_c}); falling back to the "
                  f"token-by-token loop")
            chunk = 0
    pstep = make_chunked_prefill_step(model, mesh, plan) if chunk else None

    # warm up (compile) on a THROWAWAY cache — the real cache is donated
    # through the step functions, so warm-up must not consume it
    warm = model.init_cache(args.batch, args.max_len)
    _, warm = step(params, warm, prompt[:, :1], jnp.int32(0))
    if pstep is not None:
        warm = model.init_cache(args.batch, args.max_len)
        _, warm = pstep(params, warm, prompt[:, :min(chunk, args.prompt_len)],
                        jnp.int32(0))
    del warm

    t0 = time.time()
    if pstep is not None:
        for off in range(0, args.prompt_len, chunk):
            logits, cache = pstep(params, cache,
                                  prompt[:, off:off + chunk],
                                  jnp.int32(off))
        logits = logits[:, -1:]
    else:
        for i in range(args.prompt_len):
            logits, cache = step(params, cache, prompt[:, i][:, None],
                                 jnp.int32(i))
    jax.block_until_ready(logits)
    t1 = time.time()
    out = [jnp.argmax(logits[:, -1], axis=-1)]
    for i in range(args.gen - 1):
        logits, cache = step(params, cache, out[-1][:, None],
                             jnp.int32(args.prompt_len + i))
        out.append(jnp.argmax(logits[:, -1], axis=-1))
    jax.block_until_ready(out[-1])
    t2 = time.time()

    toks = np.asarray(jnp.stack(out, axis=1))
    pre_tok = args.batch * args.prompt_len
    dec_tok = args.batch * (args.gen - 1)
    print("generated:", toks[:, :16])
    print(f"prefill: {pre_tok} tokens in {t1-t0:.2f}s = "
          f"{pre_tok/max(t1-t0, 1e-9):.1f} tok/s "
          f"({'chunk ' + str(chunk) if chunk else 'decode loop'})")
    print(f"decode:  {dec_tok} tokens in {t2-t1:.2f}s = "
          f"{dec_tok/max(t2-t1, 1e-9):.1f} tok/s "
          f"({device_label()}, batch {args.batch})")
    print("tokens sha256:", _digest(toks))
    return toks


def _serve_paged(args, cfg, model, params, prompt):
    page = args.page_size
    if args.max_len % page:
        raise SystemExit(f"--max-len {args.max_len} must be a multiple of "
                         f"--page-size {page}")
    scfg = ServeConfig(kv_quant=args.kv_quant, page_size=page,
                       max_batch=args.batch,
                       max_pages_per_seq=args.max_len // page,
                       prefill_chunk=args.prefill_chunk or 16)
    try:
        eng = Engine(model, params, scfg)
    except ValueError as e:
        raise SystemExit(f"--kv-quant: {e}")

    # warm-up request compiles the prefill/decode traces before timing
    eng.submit(prompt[0, :scfg.prefill_chunk + 1], max_new=2)
    eng.run()
    eng.prefill_time, eng.prefill_tokens = 0.0, 0
    eng.decode_times, eng.decode_tokens = [], 0

    rids = [eng.submit(prompt[b], max_new=args.gen)
            for b in range(args.batch)]
    res = eng.run()
    toks = np.stack([np.asarray(res[r].generated, np.int32) for r in rids])

    pre_s, dec_s = eng.prefill_time, sum(eng.decode_times)
    lat = np.asarray(eng.decode_times) * 1e3
    print("generated:", toks[:, :16])
    print(f"prefill: {eng.prefill_tokens} tokens in {pre_s:.2f}s = "
          f"{eng.prefill_tokens/max(pre_s, 1e-9):.1f} tok/s "
          f"(chunk {scfg.prefill_chunk})")
    print(f"decode:  {eng.decode_tokens} tokens in {dec_s:.2f}s = "
          f"{eng.decode_tokens/max(dec_s, 1e-9):.1f} tok/s "
          f"(kv={args.kv_quant}, {device_label()}, batch {args.batch})")
    if len(lat):
        print(f"step latency p50 {np.percentile(lat, 50):.1f}ms "
              f"p99 {np.percentile(lat, 99):.1f}ms")
    print(f"cache bytes: {eng.cache_bytes()} "
          f"({eng.kvq.token_bytes()} per token-layer)")
    print("tokens sha256:", _digest(toks))
    return toks


def serve(argv=None) -> np.ndarray:
    """Parse ``argv``, serve the batch and print the report; returns the
    generated tokens, (batch, gen) int32."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm-100m", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prefill chunk size (0 = token-by-token loop)")
    ap.add_argument("--kv-quant", default="",
                    help="paged-engine KV scheme (e.g. orq-9, bingrad-b; "
                         "bf16 = unquantized pages; empty = dense path)")
    ap.add_argument("--page-size", type=int, default=16)
    args = ap.parse_args(argv)

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    model = LM(cfg)
    params = jax.jit(model.init)(jax.random.key(args.seed))
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    key = jax.random.key(args.seed + 1)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    if args.kv_quant:
        return _serve_paged(args, cfg, model, params, np.asarray(prompt))
    return _serve_dense(args, cfg, model, params, prompt)


def main(argv=None):
    use_compile_cache()
    serve(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
