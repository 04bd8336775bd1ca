#!/usr/bin/env python3
"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--diagnose]

For a training cell, in one process and for each seed, every number that
``harness.train.numbers`` gives against the plain reference, for: the
program (sound runs: the lower reading); the control (the reference with
fp8 matmuls in the program's place); the planted fault that takes the mean
over half the batch (the reference so computed). A state left unchanged
reads 1 on both norm gaps and needs no run. ``--diagnose`` adds the
program with another rounding key for the exchange (same weights and
batches) and the program with the exchange in full precision, to tell the
exchange's rounding from the model's own arithmetic. One JSON line per
reading goes to stdout, with each leaf's norms. The benchmark's own runs
never run this.
"""
import argparse
import dataclasses
import gc
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.join(os.path.dirname(
    os.path.dirname(BENCH_DIR)), "src")]


def train_readings(cell, devices, seeds, diagnose, out):
    import jax

    from harness import train

    tc = train.TrainCell(cell, devices)
    programs = [("program", tc, ())]
    if diagnose:
        fp = dataclasses.replace(cell, traffic=dict(
            cell.traffic, quant="fp", error_feedback=False))
        programs += [("program_rekeyed", tc, ("rekeyed",)),
                     ("program_fp", train.TrainCell(fp, devices), ())]
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(tc.aparams)[0]]
    for seed in seeds:
        t0 = time.perf_counter()
        runs = {}
        for what, cell_of, salt in programs:
            got = cell_of.start(seed, *salt)
            del got["state"], got["batches"]
            gc.collect()
            runs[what] = got
        ref = tc.reference(seed)
        runs["control_fp8"] = tc.reference(seed, matmul="fp8")
        runs["fault_half_batch"] = tc.reference(seed, half_batch=True)
        for what, got in [("reference", ref)] + list(runs.items()):
            rec = {"cell": cell.name, "seed": seed, "what": what}
            if what != "reference":
                rec.update(train.numbers(got, ref))
            out({**rec, **{k: [float(x) for x in got[k]] for k in
                           ("losses", "grad_norms", "delta_norms")}})
        out({"cell": cell.name, "seed": seed, "what": "leaf_paths",
             "paths": paths})
        print(f"seed {seed}: {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--diagnose", action="store_true",
                    help="also the program rekeyed and with an fp exchange")
    args = ap.parse_args(argv)
    from run import configure_jax

    configure_jax()
    from harness import common

    cell = common.load_cell(args.workload)
    devices = common.device_check(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]

    def out(rec):
        print(json.dumps(rec), flush=True)

    if cell.kind != "train":
        raise common.BenchError(f"no readings for traffic kind {cell.kind!r}")
    train_readings(cell, devices, seeds, args.diagnose, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
