"""Device time by the program's named scopes.

The program names each phase of its train step with ``jax.named_scope``
(``model``, ``exchange``, ``fit``, ``optimizer``, ...). A scope is HLO
``op_name`` metadata, and it survives into the compiled module: each op
carries its path, such as ``jit(local_step)/jvp(model)/while/body/...``
(the forward), ``jit(local_step)/transpose(jvp(model))/...`` (the
backward) or ``jit(local_step)/exchange/reduce/fit/jit(sort)/sort``.
The TPU's op events name the HLO instruction and carry no ``op_name``,
so each op's path comes from the compiled step's text
(:func:`hlo_instructions`, which also says how the compiler's own ops,
which have none, are placed); a fusion carries its root's.

Paths are matched on whole segments, never on substrings: a segment
matches a name when it is the name, or the name inside autodiff's
transformations (``jvp(model)``, ``transpose(jvp(exchange))``). So ``fit``
cannot match ``fitness`` and ``model`` cannot match ``model_axis``.

The device time of a scope is, per chip, the length of the union of the
window's op intervals whose path matches; averaged over the chips and
divided by the window's steps. A ``while`` and the ops of its body run
inside one interval and fall into the same scope, so the union counts
them once.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Tuple

from harness.trace import Trace, total

#: autodiff's transformations, which wrap the first scope inside the
#: function they transform: ``jvp(model)``, ``transpose(jvp(model))``
TRANSFORMS = ("jvp", "transpose", "vmap")

#: the step's top-level phases, disjoint; a device op in none of them is
#: "unscoped" (the step counter, key folding, the metrics' means)
LAYERS = ("model_fwd", "model_bwd", "exchange", "optimizer")

_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_UNESCAPE = re.compile(r"\\(.)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(r'\b(?:calls|to_apply)=(%?[\w.\-]+)')
_CALLED_SET = re.compile(r'\b(?:calls|called_computations)=\{([^}]*)\}')
#: computations run by control flow, as ops of their own
_RUNS = re.compile(r'\b(?:condition|body|true_computation|'
                   r'false_computation)=(%?[\w.\-]+)')
_RUNS_SET = re.compile(r'\bbranch_computations=\{([^}]*)\}')


def segments(path: str) -> List[str]:
    """``a/jvp(b)/c`` -> ``['a', 'jvp(b)', 'c']``; a ``/`` inside
    parentheses does not split."""
    out, depth, cur = [], 0, []
    for ch in path:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    out.append("".join(cur))
    return [s for s in out if s]


def unwrap(segment: str) -> Tuple[str, Tuple[str, ...]]:
    """``transpose(jvp(model))`` -> ``('model', ('transpose', 'jvp'))``:
    the scope name inside autodiff's transformations, and those
    transformations, outermost first."""
    wraps = []
    while segment.endswith(")"):
        head, sep, inner = segment.partition("(")
        if not sep or head not in TRANSFORMS:
            break
        wraps.append(head)
        segment = inner[:-1]
    return segment, tuple(wraps)


def has(path: str, name: str) -> bool:
    """Whether a segment of ``path`` is the scope ``name`` (bare or
    transformed)."""
    return any(unwrap(s)[0] == name for s in segments(path))


def layer(path: Optional[str]) -> Optional[str]:
    """The top-level phase of an op: ``exchange`` (the fsdp gather's
    exchange runs inside the model's autodiff, and counts here),
    ``model_bwd`` (under ``transpose(jvp(model))``), ``model_fwd`` (the
    rest of ``model``), ``optimizer``, or None."""
    if not path:
        return None
    segs = [unwrap(s) for s in segments(path)]
    names = [n for n, _ in segs]
    if "exchange" in names:
        return "exchange"
    for name, wraps in segs:
        if name == "model":
            return "model_bwd" if "transpose" in wraps else "model_fwd"
    if "optimizer" in names:
        return "optimizer"
    return None


def is_layer(name: str) -> Callable[[Optional[str]], bool]:
    return lambda path: layer(path) == name


def is_level_fit(path: Optional[str]) -> bool:
    """The exchange's level fit: ``fit`` under ``exchange``."""
    return bool(path) and layer(path) == "exchange" and has(path, "fit")


# ------------------------------------------------------- paths from the HLO

def _parse(rest: str) -> Tuple[str, List[str]]:
    """The opcode and the operands of an instruction, from its text after
    ``name = ``: the type (a shape, or a tuple of shapes in parentheses)
    comes first, then ``opcode(%operand, ...)``."""
    i = rest.find(" ")
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        i += 1
    m = re.match(r"\s*([\w\-]+)\(", rest[i:])
    if not m:
        return "", []
    depth, k = 0, i + m.end() - 1
    for k in range(k, len(rest)):
        depth += (rest[k] == "(") - (rest[k] == ")")
        if depth == 0:
            break
    return m.group(1), _OPERAND.findall(rest[i + m.end():k])


def hlo_instructions(hlo_text: str) -> List[Tuple[str, str, str]]:
    """``(name, opcode, path)`` of each instruction of a compiled module's
    text (``Compiled.as_text()``) that runs as an op of its own: those of
    the entry computation and of the computations it runs by control flow
    (a ``while``'s body and condition, a conditional's branches). The
    insides of fusions, reducers and comparators, which a trace never
    shows, are left out.

    An instruction's path is its ``op_name`` where that starts at the
    step's ``jit(...)``. The TPU compiler's own ops carry none, or a bare
    primitive name: a fusion then takes its root's path (the root of the
    computation it calls), an op in a loop body its loop's, and any other
    op the path of the nearest op that reads its result (a layout copy
    or a buffer write is there for its reader)."""
    comps: Dict[str, List[list]] = {}
    root: Dict[str, str] = {}
    cur = cur_name = None
    for line in hlo_text.splitlines():
        if not line.strip():
            continue
        if not line[0].isspace():
            cur = None
            if line.rstrip().endswith("{"):
                head = line.split()
                cur_name = (head[1] if head[0] == "ENTRY"
                            else head[0]).lstrip("%")
                cur = comps.setdefault(cur_name, [])
            continue
        if cur is None or " = " not in line:
            continue
        lhs, rest = line.strip().split(" = ", 1)
        m = _OP_NAME.search(rest)
        path = _UNESCAPE.sub(r"\1", m.group(1)) if m else ""
        path = path if path.startswith("jit(") else ""
        if lhs.startswith("ROOT"):
            root[cur_name] = path
        op, operands = _parse(rest)
        calls = _CALLED.findall(rest) + [
            c.strip() for g in _CALLED_SET.findall(rest)
            for c in g.split(",")]
        runs = _RUNS.findall(rest) + [
            c.strip() for g in _RUNS_SET.findall(rest)
            for c in g.split(",")]
        cur.append([lhs.split()[-1].lstrip("%"), op, path, operands,
                    [c.lstrip("%") for c in calls],
                    [c.lstrip("%") for c in runs]])
    called = {c for inss in comps.values() for ins in inss for c in ins[4]}
    ran = {c for inss in comps.values() for ins in inss for c in ins[5]}
    parent: Dict[str, str] = {}
    out: List[list] = []
    todo = [c for c in comps if c not in called and c not in ran]
    while todo:
        comp = todo.pop()
        inss = comps[comp]
        for ins in inss:
            ins[2] = (ins[2] or next((root[c] for c in ins[4]
                                      if root.get(c)), "")
                      or parent.get(comp, ""))
        # the rest read their path from their readers, last reader first
        by_name = {ins[0]: ins for ins in inss}
        for ins in reversed(inss):
            for a in ins[3]:
                src = by_name.get(a)
                if src is not None and not src[2] and ins[2]:
                    src[2] = ins[2]
        out += inss
        for ins in inss:
            for c in ins[5]:
                if c in comps and c not in parent:
                    parent[c] = ins[2]
                    todo.append(c)
    return [(n, op, path) for n, op, path, *_ in out]


def hlo_paths(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> op_name path, for the ops a trace can show."""
    return {n: p for n, _, p in hlo_instructions(hlo_text)}


# ------------------------------------------------------ time of the scopes

def scope_ns(trace: Trace, paths: Dict[str, str],
             match: Callable[[Optional[str]], bool]) -> float:
    """Per chip, the length of the union of the window's intervals of the
    ops whose path ``match`` accepts; the mean over the chips."""
    if not trace.ops:
        return 0.0
    acc = 0.0
    for dev in trace.ops:
        acc += total((s, s + d) for n, s, d in trace.device_ops(dev)
                     if match(paths.get(n)))
    return acc / len(trace.ops)


def scope_ms_per_step(trace: Trace, paths: Dict[str, str],
                      match: Callable[[Optional[str]], bool],
                      steps: int) -> Optional[float]:
    """:func:`scope_ns` in ms a step; None where no op matches (a program
    without the scope)."""
    ns = scope_ns(trace, paths, match)
    if ns <= 0 or not steps:
        return None
    return ns * 1e-6 / steps


def unscoped_ns(trace: Trace, paths: Dict[str, str]) -> float:
    """Busy time that no top-level phase covers: the union of every op
    less the union of the ops in one of :data:`LAYERS`, mean over the
    chips."""
    return trace.mean_busy_ns() - scope_ns(
        trace, paths, lambda p: layer(p) is not None)


def unscoped_ops(trace: Trace, paths: Dict[str, str], top: int = 10
                 ) -> List[Tuple[str, float]]:
    """The ``top`` op names outside every top-level phase by device time
    (seconds, mean over the chips)."""
    acc: Dict[str, float] = {}
    for dev in trace.ops:
        for n, _, d in trace.device_ops(dev):
            if layer(paths.get(n)) is None:
                acc[n] = acc.get(n, 0.0) + d / len(trace.ops)
    return [(n, d * 1e-9) for n, d in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


# ------------------------------------------------------------ a traced run

def compiled_step_text(run) -> str:
    """The text of the run's compiled train step: the cell's step lowered
    again from a state made the same way, and compiled, which loads the
    executable the run compiled from JAX's persistent cache (the same
    module gives the same instruction names in any case)."""
    import jax

    from harness import common, train
    from harness.weights import fold, seed_key

    cell = common.Cell(name="", chips=run.chips, config=run.config,
                       traffic=run.traffic, end_to_end=[], per_layer=[])
    tc = train.TrainCell(cell, jax.devices()[:run.chips])
    state = tc.make_state(0)
    batch = tc.make_batches(0)[0]
    text = tc.step_fn.lower(state, batch, fold(seed_key(0), "step")
                            ).compile().as_text()
    del state, batch
    return text


def run_paths(run) -> Dict[str, str]:
    """Instruction name -> path for a traced run, made once and kept on
    ``run`` (``run.scope_paths``); with it, to stderr, the busy time that
    no top-level phase covers. Empty where the step cannot be compiled
    again, so that the readers leave their metrics out."""
    from harness.common import BenchError, log

    if getattr(run, "scope_paths", None) is None:
        try:
            run.scope_paths = hlo_paths(compiled_step_text(run))
        except (Exception, BenchError) as e:    # noqa: BLE001
            log(f"scopes: no paths for the traced ops ({e!r})")
            run.scope_paths = {}
        busy = run.trace.mean_busy_ns()
        if run.scope_paths and busy > 0:
            un = unscoped_ns(run.trace, run.scope_paths)
            log(f"scopes: busy time outside model, exchange and optimizer "
                f"{un * 1e-9:.6f}s a window, {100 * un / busy:.4f} % of "
                f"busy; its longest ops (s) "
                f"{unscoped_ops(run.trace, run.scope_paths, 5)}")
    return run.scope_paths


def run_ms(run, match: Callable[[Optional[str]], bool]) -> Optional[float]:
    """:func:`scope_ms_per_step` of a traced run; None where no op of the
    run carries the scope (a program without it)."""
    paths = run_paths(run)
    if not paths:
        return None
    return scope_ms_per_step(run.trace, paths, match, run.steps)
