"""The shared sub-jaxpr traversal.

Every jaxpr consumer in the repo (``utils/jaxpr.py`` collective/outvar
counting, ``launch/hlo_cost.py`` pallas stats, the ``analysis`` rules)
walks eqns through this module, so "which sub-jaxpr kinds do we descend
into" is answered in exactly one place. Handled kinds:

  * ``ClosedJaxpr``-valued params        — pjit, scan (``jaxpr``), while
    (``body_jaxpr``/``cond_jaxpr``), custom_vjp (``fun_jaxpr``),
    custom_jvp (``call_jaxpr``), closed_call, remat
  * raw ``Jaxpr``-valued params          — shard_map, pallas_call
  * tuple/list params of either          — cond ``branches``
  * ``custom_vjp_call_jaxpr``'s **fwd rule** via ``fwd_jaxpr_thunk``
    (opt-in: the fwd body duplicates the primal ``fun_jaxpr`` content,
    so counting rules must not traverse both) — the kind the three
    pre-``analysis`` ad-hoc walkers silently skipped

No jax import: the walk is pure duck-typing over eqn/params objects, so
the analysis CLI can configure ``XLA_FLAGS`` before jax ever loads.
"""
from __future__ import annotations

from typing import Any, Iterator, List, Tuple

#: path component marking eqns reached through a custom_vjp fwd rule
CUSTOM_VJP_FWD = "custom_vjp_fwd"


def _as_jaxprs(v, seen: set) -> List[Any]:
    """Raw ``Jaxpr`` objects reachable from one eqn param value."""
    sub = getattr(v, "jaxpr", None)
    if sub is not None and hasattr(sub, "eqns"):      # ClosedJaxpr
        v = sub
    if hasattr(v, "eqns"):                            # raw Jaxpr
        if id(v) in seen:
            return []
        seen.add(id(v))
        return [v]
    if isinstance(v, (tuple, list)):
        out: List[Any] = []
        for u in v:
            out.extend(_as_jaxprs(u, seen))
        return out
    return []


def custom_vjp_fwd_jaxprs(eqn) -> List[Any]:
    """Jaxprs of the custom_vjp FWD rule, if this eqn carries one.

    ``fwd_jaxpr_thunk`` traces the user's fwd function on demand; it
    takes one ``symbolic_zeros`` boolean per primal input and returns
    ``(jaxpr, consts)``. Returns ``[]`` for non-custom_vjp eqns and for
    thunks that fail to trace (nothing to audit there)."""
    thunk = eqn.params.get("fwd_jaxpr_thunk")
    if thunk is None:
        return []
    n_primal = len(eqn.invars) - int(eqn.params.get("num_consts", 0))
    try:
        res = thunk.call_wrapped(*([False] * max(n_primal, 0)))
    except Exception:  # noqa: BLE001 — un-traceable thunk: skip, don't fail
        return []
    jx = res[0] if isinstance(res, (tuple, list)) and res else res
    return _as_jaxprs(jx, set())


def eqn_sub_jaxprs(eqn, *, include_custom_vjp_fwd: bool = False
                   ) -> List[Tuple[str, Any]]:
    """``(tag, raw_jaxpr)`` pairs directly under one eqn. ``tag`` is the
    eqn's primitive name, or :data:`CUSTOM_VJP_FWD` for fwd-rule bodies."""
    seen: set = set()
    name = eqn.primitive.name
    subs = [(name, jx) for v in eqn.params.values()
            for jx in _as_jaxprs(v, seen)]
    if include_custom_vjp_fwd:
        subs += [(CUSTOM_VJP_FWD, jx) for jx in custom_vjp_fwd_jaxprs(eqn)]
    return subs


def walk_eqns(closed_or_jaxpr, *, include_custom_vjp_fwd: bool = False
              ) -> Iterator[Tuple[Any, Tuple[str, ...]]]:
    """Depth-first ``(eqn, path)`` over a jaxpr and every sub-jaxpr.

    ``path`` is the tuple of enclosing primitive names, outermost first
    (e.g. ``("pjit", "scan")``) — rules use it to scope counts, e.g.
    "not inside a pallas_call body". Accepts a ``ClosedJaxpr``, a raw
    ``Jaxpr``, or anything with a ``.jaxpr``.
    """
    root = getattr(closed_or_jaxpr, "jaxpr", closed_or_jaxpr)

    def rec(jx, path):
        for eqn in jx.eqns:
            yield eqn, path
            for tag, sub in eqn_sub_jaxprs(
                    eqn, include_custom_vjp_fwd=include_custom_vjp_fwd):
                yield from rec(sub, path + (tag,))

    yield from rec(root, ())


def aval_elems(v) -> int:
    """Element count of a var's abstract value (1 for scalars/unknown)."""
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    if not shape:
        return 1
    n = 1
    for d in shape:
        n *= int(d)
    return n


def aval_dtype(v) -> str:
    """Dtype name of a var's abstract value ("" when unknown)."""
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    return "" if dt is None else str(dt)
