"""Seeded-violation corpus: one bundle per rule that MUST fire.

A rule that silently stops matching is worse than no rule (the matrix
audit would go green while the invariant rots), so CI runs
``python -m repro.analysis --selftest`` next to the real audit:
every registered rule is applied to a bundle constructed to violate it
and must produce at least one finding. ``tests/test_analysis.py``
asserts the same corpus rule by rule (true-positive tests), and
``--inject-violation RULE`` appends one of these bundles to the real
matrix to demonstrate the nonzero ``--check`` exit end to end.

Trace seeds are tiny standalone programs (no mesh needed except for the
collective seed, which uses however many fake devices the process was
started with); source seeds are synthetic files violating each lint.
"""
from __future__ import annotations

import ast
from typing import Dict, List

from repro.analysis.engine import (CHECKS, SourceBundle, SourceFile,
                                   TraceBundle, run_checks)
from repro.analysis.findings import Finding

#: synthetic sources violating each lint rule (paths matter: the pallas
#: seed must live under repro/kernels/ for the rule to scope it)
_BAD_SOURCES: Dict[str, SourceFile] = {}


def _bad_source(rule: str, path: str, text: str) -> None:
    _BAD_SOURCES[rule] = SourceFile(path=path, text=text,
                                    tree=ast.parse(text, filename=path))


_bad_source("env-read", "repro/core/_seeded_env_read.py", (
    "import os\n"
    "USE_KERNELS = os.environ.get('REPRO_USE_KERNELS', '1')\n"
    "INTERPRET = os.getenv('REPRO_PALLAS_INTERPRET')\n"))

_bad_source("set-axis-names", "repro/core/_seeded_set_axes.py", (
    "def exchange(x, reduce):\n"
    "    dp_axis_names = set(('pod', 'data'))\n"
    "    return reduce(x, axis_names={'data'})\n"))

_bad_source("pallas-body-discipline", "repro/kernels/_seeded_body.py", (
    "import jax\n"
    "from jax.experimental import pallas as pl\n"
    "\n"
    "def _kernel(x_ref, o_ref):\n"
    "    noise = jax.random.uniform(jax.random.key(0), x_ref.shape)\n"
    "    o_ref[...] = (x_ref[...] + noise).astype('float64')\n"
    "\n"
    "def op(x):\n"
    "    return pl.pallas_call(_kernel, out_shape=x)(x)\n"))

_bad_source("registry-bypass", "repro/train/_seeded_bypass.py", (
    "from repro.core.quantizers import Quantizer\n"
    "\n"
    "def make(d):\n"
    "    return Quantizer(bucket_size=d, method='orq', num_levels=9)\n"))


def _seeded_collective_trace() -> TraceBundle:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro.utils.compat import shard_map

    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    f = shard_map(lambda x: lax.pmean(x, "data"), mesh=mesh,
                  in_specs=P(), out_specs=P())
    closed = jax.make_jaxpr(f)(jnp.ones(8))
    return TraceBundle(
        label="seeded/collective-budget", kind="exchange", closed=closed,
        meta={
            # exact-count path: the budget promises an all_gather that
            # the trace never launches
            "expected_collectives": {("all_gather", ("data",)): 1},
            # exclusivity path: psum is banned from every axis, yet the
            # pmean traced one
            "exclusive_prims": {"psum": []},
        })


def _seeded_multipass_trace() -> TraceBundle:
    """The real multi-pass encoder claiming to be one-pass."""
    import jax
    import jax.numpy as jnp
    from repro.core import make_quantizer
    from repro.core.comm import wire

    qz = make_quantizer("orq-9", bucket_size=37)
    bkt = jnp.ones((5, 37))
    mask = jnp.ones((5, 37), bool)
    closed = jax.make_jaxpr(
        lambda b, m, k: wire.encode_multipass(qz, b, m, k))(
            bkt, mask, jax.random.key(0))
    return TraceBundle(label="seeded/one-pallas-call", kind="wire_op",
                       closed=closed, meta={"expect_pallas_calls": 1})


def _seeded_vmem_trace() -> TraceBundle:
    """A copy kernel whose single block is 4 MiB — double the tile
    budget."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def _copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    x = jnp.ones((1024, 1024), jnp.float32)
    closed = jax.make_jaxpr(
        lambda v: pl.pallas_call(
            _copy, out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype))(v))(x)
    return TraceBundle(label="seeded/vmem-tile-budget", kind="wire_op",
                       closed=closed, meta={"expect_pallas_calls": 1})


def _seeded_materialization_trace() -> TraceBundle:
    import jax
    import jax.numpy as jnp

    n = 1 << 17
    closed = jax.make_jaxpr(
        lambda x: (x + 1.0) * (x - 2.0))(jnp.ones((n,), jnp.float32))
    return TraceBundle(
        label="seeded/no-materialization", kind="exchange", closed=closed,
        meta={"materialization": {"min_elems": n, "dtype": "float32",
                                  "max_count": 0}})


def _seeded_donation_trace() -> TraceBundle:
    """A jitted state update that copies instead of donating."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda s: s + 1.0)     # no donate_argnums
    closed = jax.make_jaxpr(step)(jnp.ones((8,)))
    return TraceBundle(label="seeded/donation", kind="train_step",
                       closed=closed, meta={"expect_donated": 1})


def _seeded_widening_trace() -> TraceBundle:
    import jax
    import jax.numpy as jnp

    n = 1 << 17
    closed = jax.make_jaxpr(
        lambda w: w.astype(jnp.float32) / 2.0)(
            jnp.ones((n,), jnp.uint32))
    return TraceBundle(label="seeded/no-fp32-widening", kind="wire_op",
                       closed=closed, meta={"wire_min_elems": n})


def _seeded_prng_trace() -> TraceBundle:
    """The per-chunk re-draw bug the pipelined exchange must never have."""
    import jax

    def redraw(key):
        a = jax.random.bits(key, (4, 64))
        b = jax.random.bits(jax.random.fold_in(key, 1), (4, 64))
        return a ^ b

    closed = jax.make_jaxpr(redraw)(jax.random.key(0))
    return TraceBundle(label="seeded/prng-single-draw", kind="wire_op",
                       closed=closed,
                       meta={"prng": {"random_bits": 1, "fold_ins": 0}})


_TRACE_SEEDS = {
    "collective-budget": _seeded_collective_trace,
    "one-pallas-call": _seeded_multipass_trace,
    "vmem-tile-budget": _seeded_vmem_trace,
    "no-materialization": _seeded_materialization_trace,
    "donation": _seeded_donation_trace,
    "no-fp32-widening": _seeded_widening_trace,
    "prng-single-draw": _seeded_prng_trace,
}


def seeded_bundle(rule: str):
    """The bundle constructed to violate ``rule``."""
    if rule in _TRACE_SEEDS:
        return _TRACE_SEEDS[rule]()
    if rule in _BAD_SOURCES:
        return SourceBundle(label=f"seeded/{rule}",
                            files=(_BAD_SOURCES[rule],))
    raise KeyError(f"no seeded violation for rule {rule!r}; "
                   f"seeds: {sorted(_TRACE_SEEDS) + sorted(_BAD_SOURCES)}")


def run_selftest() -> Dict[str, List[Finding]]:
    """rule id -> findings its seeded bundle produced (must be non-empty
    for every registered rule)."""
    out: Dict[str, List[Finding]] = {}
    for rule in CHECKS:
        found = run_checks([seeded_bundle(rule)], rules=[rule])
        out[rule] = [f for f in found if f.rule == rule]
    return out
