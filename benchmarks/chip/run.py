#!/usr/bin/env python3
"""One run of one benchmark cell, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the checkout's root. The run
makes its weights and inputs from ``--seed``, warms every shape the cell
uses (set-up), measures for ``--seconds``, checks what the timed path
produced against the cell's plain reference, and prints one JSON line as
the last line of stdout: the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics (read from a profiler trace of the window) with
``--trace 1``. The numbers compared with the reference, each beside its
limit, are the last lines of stderr and the last key of that line.

It exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell asks for, when Pallas kernels would run in interpret
mode or be swapped for their oracles, and when the program cannot be
imported. JAX's persistent compilation cache is kept in
``benchmarks/chip/.cache/jax`` of the checkout, so only a cell's first run
there compiles.
"""
import argparse
import os
import sys
import time

T_START = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def configure_jax() -> None:
    """The persistent compilation cache at its fixed path in the
    checkout, every program cached."""
    from harness.common import CACHE_DIR

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse(argv)
    configure_jax()
    from harness import common

    cell = common.load_cell(args.workload)
    devices = common.device_check(cell.chips)
    counter = common.CompileCounter()
    if cell.kind != "train":
        raise common.BenchError(f"traffic kind {cell.kind!r}")
    from harness import train as runner
    runner.run(cell, args, devices, counter, T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
