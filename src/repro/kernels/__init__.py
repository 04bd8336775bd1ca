# Pallas kernel package for the wire-format hot path.
#
#   ops.py            env-flag resolution + jit'd public wrappers
#   ref.py            pure-jnp oracles (bit-exact semantics for every kernel)
#   fused_encode.py   ONE-pass clip->round->pack (encode_fused) and
#                     clip->round->decode (qdq_fused, the EF residual path)
#   fused_bingrad.py  fully-fused BinGrad-b (b0 search + levels + 1-bit pack)
#   fused_decode.py   ONE-pass unpack->dequant->average / per-worker decode
#   orq_fit.py        the ORQ level fit (Alg. 1) with no sort, one pass per tile
#   quant_rr.py, bitpack.py, dequant_avg.py, bingrad.py
#                     the multi-pass kernels (PR 1-4 pipeline) — kept as the
#                     parity baseline and for callers that need bare stages
#
# Perf is tracked by benchmarks/kernel_bench.py (BENCH_kernels.json); CI
# gates regressions against benchmarks/BENCH_kernels_baseline.json.
