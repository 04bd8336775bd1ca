"""The repo's one ``shard_map`` entry point: ``jax.shard_map`` with
``check_vma`` off by default and an ORDERED ``axis_names`` tuple.

``axis_names`` is the set of MANUAL axes; axes of the mesh not listed stay
automatic (the SPMD partitioner handles them, e.g. tensor parallelism over
``model``), and a ``shard_map`` over one axis may nest inside a manual
region over others.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh=None, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map`` over ``mesh`` (the enclosing one when None)."""
    kw = dict(in_specs=in_specs, out_specs=out_specs, check_vma=check_vma)
    if mesh is not None:
        kw["mesh"] = mesh
    if axis_names is not None:
        # the set conversion happens ONLY here, at the jax boundary,
        # where axis_names is genuinely membership-semantic (which axes
        # are manual). Everything order-sensitive — the collectives in
        # repro.core.comm — receives the caller's ordered tuple, never
        # this set.
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)
