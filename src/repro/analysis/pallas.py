"""Pallas-kernel cost extraction (jaxpr-based).

The HLO text parser in ``launch/hlo_cost.py`` never sees the fused
quantization kernels: in interpret mode a pallas_call lowers to ordinary
HLO ops with no custom-call marker. The jaxpr, however, carries every
pallas_call eqn with its full grid mapping — block shapes, array shapes,
dtypes — which is exactly what a VMEM/roofline report (and the
``vmem-tile-budget`` rule) needs, identically between interpret and
compiled lowering. ``launch/hlo_cost.py`` re-exports these for callers.
"""
from __future__ import annotations

from typing import List

from repro.analysis.traversal import aval_elems, walk_eqns

#: elementwise / reduce primitives counted as one op per element for the
#: arithmetic-intensity estimate (bit-twiddling in the pack stage included:
#: on TPU those are real VPU lanes, not free address arithmetic)
_ARITH_PRIMS = {
    "add", "sub", "mul", "div", "rem", "max", "min", "neg", "abs", "sign",
    "floor", "ceil", "round", "exp", "log", "sqrt", "rsqrt", "integer_pow",
    "pow", "select_n", "and", "or", "xor", "not", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "ge", "gt", "le", "lt",
    "eq", "ne", "reduce_sum", "reduce_max", "reduce_min", "reduce_and",
    "reduce_or", "argmax", "argmin", "cumsum", "dot_general",
}


def _block_elems(block_shape) -> int:
    n = 1
    for d in block_shape:
        if d is None:               # squeezed / unblocked dim
            continue
        try:
            n *= int(d)
        except TypeError:           # BlockDim wrapper in newer jax
            n *= int(getattr(d, "block_size", 1))
    return n


def kernel_flops(jaxpr) -> float:
    """Per-grid-step op estimate: one op per element of the widest operand
    of every elementwise/reduce eqn, recursing into sub-jaxprs (via the
    shared ``repro.analysis.traversal`` walk)."""
    flops = 0.0
    for eqn, _path in walk_eqns(jaxpr):
        if eqn.primitive.name in _ARITH_PRIMS:
            flops += max([aval_elems(v) for v in
                          list(eqn.invars) + list(eqn.outvars)] or [1])
    return flops


def pallas_eqn_stats(eqn) -> dict:
    """Footprint of ONE ``pallas_call`` eqn (see ``pallas_call_stats``)."""
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    steps = 1
    for g in grid:
        steps *= g
    vmem = hbm = 0
    for bm in gm.block_mappings:
        sds = bm.array_aval
        isz = sds.dtype.itemsize
        vmem += _block_elems(bm.block_shape) * isz
        full = 1
        for d in sds.shape:
            full *= int(d)
        hbm += full * isz
    kj = eqn.params.get("jaxpr")
    body = getattr(kj, "jaxpr", kj)
    flops = (kernel_flops(body) * steps
             if hasattr(body, "eqns") else 0.0)
    # an explicit ``pallas_call(name=...)`` wins; else the kernel function
    name = eqn.params.get("name") or getattr(
        getattr(kj, "debug_info", None), "func_name", None)
    return {
        "kernel": name or "pallas_call",
        "grid": grid, "grid_steps": steps,
        "vmem_bytes": vmem, "hbm_bytes": hbm, "flops": flops,
        "arithmetic_intensity": round(flops / hbm, 3) if hbm else 0.0,
    }


def pallas_call_stats(closed) -> List[dict]:
    """Per-``pallas_call`` VMEM footprint and arithmetic intensity.

    ``closed`` is what ``jax.make_jaxpr(fn)(*args)`` returns. For every
    pallas_call eqn (nested sub-jaxprs included) reports:

      * ``kernel``       — kernel function name
      * ``grid``         — grid tuple; ``grid_steps`` its product
      * ``vmem_bytes``   — resident bytes per grid step: sum of
                           block_shape x dtype over every operand/output
                           BlockSpec (the quantity the kernels' row_block
                           sizing holds under VMEM_TILE_BYTES)
      * ``hbm_bytes``    — full operand + output array bytes (a one-pass
                           kernel touches each exactly once)
      * ``flops``        — elementwise-op estimate over the whole grid
      * ``arithmetic_intensity`` — flops / hbm_bytes
    """
    return [pallas_eqn_stats(eqn)
            for eqn, path in walk_eqns(closed)
            if eqn.primitive.name == "pallas_call"
            and "pallas_call" not in path]
