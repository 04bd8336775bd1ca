"""The program's named scopes and the reduction that reads them: path
matching and the union rule on hand-built events, the scopes of the
compiled train step at CPU size on 1 and 4 devices, and the five readers
on a trace recorded on a TPU v5e."""
import gzip
import json
import os
import re
import subprocess
import sys
from types import SimpleNamespace

import pytest

from harness import common, scopes
from harness import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
#: one training step of ``rwkv6-3b.train.orq9-ef`` traced on a TPU v5e
#: (``run.py --seconds 0.5 --trace 1``): the device ops and host spans
#: that ``Trace.from_file`` read from its ``.xplane.pb``, and the text of
#: the compiled step, whose ``op_name`` metadata gives each op its scope
#: path, as gzipped JSON
SCOPED = os.path.join(HERE, "data", "train_step_scoped.trace.json.gz")

FWD = "jit(local_step)/jvp(model)/while/body/closed_call/dot_general"
BWD = "jit(local_step)/transpose(jvp(model))/while/body/checkpoint/mul"
FIT = "jit(local_step)/exchange/reduce/fit/jit(sort)/sort"


# ---------------------------------------------------------- path matching

def test_segments_split_outside_parentheses():
    assert scopes.segments("jit(f)/transpose(jvp(model))/while/body") == [
        "jit(f)", "transpose(jvp(model))", "while", "body"]
    assert scopes.unwrap("transpose(jvp(model))") == (
        "model", ("transpose", "jvp"))
    assert scopes.unwrap("jit(sort)") == ("jit(sort)", ())


@pytest.mark.parametrize("path,want", [
    (FWD, "model_fwd"),
    (BWD, "model_bwd"),
    ("jit(local_step)/model/add", "model_fwd"),
    (FIT, "exchange"),
    # the fsdp gather's exchange runs inside the model's autodiff
    ("jit(s)/transpose(jvp(model))/exchange/reduce/collective/all_to_all",
     "exchange"),
    ("jit(s)/transpose(jvp(exchange))/reduce/fit/sort", "exchange"),
    ("jit(local_step)/optimizer/mul", "optimizer"),
    ("jit(local_step)/jit(_threefry_fold_in)/xor", None),
    ("jit(local_step)/psum", None),
    ("", None),
    # whole segments only
    ("jit(local_step)/model_axis/add", None),
    ("jit(local_step)/jvp(modelx)/add", None),
    ("jit(local_step)/exchanges/fit", None),
    ("jit(local_step)/optimizers/mul", None),
])
def test_layer_of_a_path(path, want):
    assert scopes.layer(path) == want


@pytest.mark.parametrize("path,want", [
    (FIT, True),
    ("jit(s)/exchange/requantize/fit/jit(sort)/sort", True),
    ("jit(s)/exchange/fitness/sort", False),
    ("jit(s)/exchange/jit(fit)/sort", False),
    ("jit(s)/fit/sort", False),                 # a fit outside the exchange
    ("jit(s)/exchange/encode/encode_fused", False),
])
def test_level_fit_matches_whole_segments(path, want):
    assert scopes.is_level_fit(path) is want


# ------------------------------------------------------------- union rule

def _trace(ops, window=(0, 1000)):
    return T.Trace({"/device:TPU:0": ops}, [], window=window)


def test_a_loop_and_its_body_count_once():
    # a backward loop [0, 100) with two body ops inside it
    t = _trace([("while.1", 0, 100), ("fusion.2", 10, 20),
                ("fusion.3", 50, 30), ("sort.4", 120, 40)])
    paths = {"while.1": BWD, "fusion.2": BWD + "/x", "fusion.3": BWD + "/y",
             "sort.4": FIT}
    assert scopes.scope_ns(t, paths, scopes.is_layer("model_bwd")) == 100
    assert scopes.scope_ns(t, paths, scopes.is_level_fit) == 40
    assert scopes.scope_ms_per_step(
        t, paths, scopes.is_layer("model_bwd"), 2) == pytest.approx(5e-5)
    # nothing under the optimizer: no reading
    assert scopes.scope_ms_per_step(
        t, paths, scopes.is_layer("optimizer"), 2) is None


def test_forward_backward_split_and_unscoped_time():
    t = _trace([("a", 0, 10), ("b", 10, 30), ("c", 40, 5), ("d", 45, 5),
                ("e", 60, 10)], window=(0, 100))
    paths = {"a": FWD, "b": BWD, "c": "jit(s)/optimizer/mul",
             "d": "jit(s)/psum", "e": "jit(s)/model_axis/add"}
    assert scopes.scope_ns(t, paths, scopes.is_layer("model_fwd")) == 10
    assert scopes.scope_ns(t, paths, scopes.is_layer("model_bwd")) == 30
    assert scopes.unscoped_ns(t, paths) == 15
    assert [n for n, _ in scopes.unscoped_ops(t, paths)] == ["e", "d"]


def test_scopes_average_over_chips_and_clip_to_the_window():
    t = T.Trace({"/device:TPU:0": [("x", -10, 30)],
                 "/device:TPU:1": [("x", 0, 10), ("y", 5, 10)]}, [],
                window=(0, 100))
    paths = {"x": FWD, "y": FWD}
    assert scopes.scope_ns(t, paths, scopes.is_layer("model_fwd")) == 17.5


# ---------------------------------------------------------- HLO text maps

HLO = """HloModule jit_step, entry_computation_layout={()->f32[]}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %sort.3 = f32[8]{0} sort(%param_0), dimensions={0}, to_apply=%lt, metadata={op_name="jit(step)/exchange/fit/jit(sort)/sort"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %copy.7 = f32[8]{0} copy(%arg)
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%arg, %copy.7), metadata={op_name="jit(step)/transpose(jvp(model))/while/body/tuple"}
}

%cond (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.4 = pred[] compare(%arg.1, %arg.1), direction=LT, metadata={op_name="jit(step)/transpose(jvp(model))/while/cond/lt"}
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="grads[\\'w\\']"}
  %copy.8 = f32[8]{0:T(128)} copy(%p)
  %sort_fusion = f32[8]{0} fusion(%copy.8), kind=kLoop, calls=%fused_computation
  %while.5 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(step)/transpose(jvp(model))/while"}
  ROOT %add.6 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/optimizer/add"}
}
"""


def test_hlo_map_of_the_ops_a_trace_shows():
    ins = {n: (op, p) for n, op, p in scopes.hlo_instructions(HLO)}
    # a fusion's insides never show; the fusion takes its root's path
    assert "sort.3" not in ins
    assert ins["sort_fusion"] == (
        "fusion", "jit(step)/exchange/fit/jit(sort)/sort")
    # a copy XLA added to a loop body takes the loop's path
    assert ins["copy.7"] == ("copy", "jit(step)/transpose(jvp(model))/while")
    assert ins["lt.4"][0] == "compare" and ins["tuple.2"][0] == "tuple"
    assert ins["while.5"][0] == "while"
    # an op the compiler made with no op_name takes its reader's path; an
    # op_name that is not a path (a parameter's) counts as none
    assert ins["copy.8"] == ("copy", "jit(step)/exchange/fit/jit(sort)/sort")
    assert ins["p"][0] == "parameter" and ins["p"][1].startswith("jit(step)/")
    assert scopes.hlo_paths(HLO)["add.6"] == "jit(step)/optimizer/add"


# -------------------------------------------- the compiled step's scopes

_COMPILE = """
import json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import jax
from harness import common, train
from harness.weights import seed_key
config, traffic = json.loads(sys.argv[1]), json.loads(sys.argv[2])
cell = common.Cell(name="small", chips=traffic["data"], config=config,
                   traffic=traffic, end_to_end=[], per_layer=[])
tc = train.TrainCell(cell, jax.devices()[:traffic["data"]])
st, b = tc.make_state(3), tc.make_batches(3)
print(tc.step_fn.lower(st, b[0], seed_key(3)).compile().as_text())
"""

#: ops that do real work; none may run outside the step's scopes
_WORK = ("custom-call", "sort", "dot", "convolution", "all-to-all",
         "all-gather", "reduce-scatter", "while")


def _compiled_step(config, traffic, devices):
    traffic = dict(traffic, data=devices)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    code = _COMPILE.format(bench=common.BENCH_DIR,
                           src=os.path.join(common.ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code, json.dumps(config),
                          json.dumps(traffic)], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def _operands(hlo):
    """name -> the instructions it reads (called computations left out)."""
    out = {}
    for line in hlo.splitlines():
        if not line[:1].isspace() or " = " not in line:
            continue
        lhs, rest = line.strip().split(" = ", 1)
        rest = re.split(r", (?:metadata|calls|to_apply|condition|body|"
                        r"backend_config|custom_call_target)=", rest)[0]
        out[lhs.split()[-1].lstrip("%")] = re.findall(r"%([\w.\-]+)", rest)
    return out


@pytest.mark.parametrize("devices", [1, 4])
def test_compiled_step_scopes(rwkv_small, train_traffic_small, devices):
    hlo = _compiled_step(rwkv_small, train_traffic_small, devices)
    ins = scopes.hlo_instructions(hlo)
    by_op = {}
    for name, op, path in ins:
        by_op.setdefault(op, []).append((name, path))
    # every bucket sort is a level fit of the exchange
    assert by_op["sort"]
    assert all(scopes.is_level_fit(p) for _, p in by_op["sort"])
    # the model's matmuls are its forward or backward
    dots = [scopes.layer(p) for _, p in by_op["dot"]]
    assert dots and set(dots) <= {"model_fwd", "model_bwd"}
    assert {"model_fwd", "model_bwd"} <= set(dots)
    layers = {scopes.layer(p) for _, _, p in ins}
    assert set(scopes.LAYERS) <= layers
    # the optimizer's update: elementwise fusions under ``optimizer``
    opt = [op for _, op, p in ins if scopes.layer(p) == "optimizer"]
    assert "fusion" in opt and not set(opt) & set(_WORK)
    if devices == 4:
        coll = by_op["all-to-all"] + by_op["all-gather"]
        assert len(coll) == 4
        assert all(scopes.layer(p) == "exchange"
                   and scopes.has(p, "collective") for _, p in coll)
    else:
        assert "all-to-all" not in by_op
    # outside the top-level scopes: parameters, constants, tuples, the
    # step counter, key folding, the metrics' reductions and constant
    # masks; and ops XLA made without an op_name, none of them real work
    kind = {n: op for n, op, _ in ins}
    reads = _operands(hlo)

    def constant(n, seen=()):
        return kind.get(n) in (None, "constant", "iota") or (
            n not in seen and reads.get(n) is not None and all(
                constant(a, seen + (n,)) for a in reads[n]))

    for name, op, path in ins:
        if scopes.layer(path) is not None or op in (
                "parameter", "constant", "tuple", "get-tuple-element",
                "bitcast", "copy"):
            continue
        segs = scopes.segments(path)
        if not path:
            assert op not in _WORK, name
        elif "jit(_threefry_fold_in)" in segs or "psum" in segs:
            continue
        elif segs[-1] in ("add", "div") and segs[-2] in (
                "jit(local_step)", "shard_map"):
            continue                     # the step counter, metrics' means
        else:
            assert constant(name), (name, op, path)


# ----------------------------------------------- a trace recorded on chip

def _scoped_run():
    with gzip.open(SCOPED) as f:
        doc = json.load(f)
    t = T.Trace({d: [tuple(e) for e in evs] for d, evs in doc["ops"].items()},
                [tuple(e) for e in doc["spans"]], window=tuple(doc["window"]))
    return SimpleNamespace(trace=t, steps=1,
                           scope_paths=scopes.hlo_paths(doc["hlo"]))


def _read(metric, run):
    mod = common.load_module(os.path.join(common.BENCH_DIR, "metrics",
                                          metric + ".py"))
    return mod.read(run)


@pytest.mark.parametrize("metric,want", [
    ("model_fwd_ms.train", 38.209),
    ("model_bwd_ms.train", 270.291),
    ("optimizer_ms.train", 12.654),
    ("exchange_ms.train", 973.756),
    ("level_fit_ms.train", 721.279),
])
def test_readers_on_the_recorded_trace(metric, want):
    assert _read(metric, _scoped_run()) == pytest.approx(want, abs=1e-3)


def test_recorded_step_adds_up_to_busy():
    run = _scoped_run()
    t, paths = run.trace, run.scope_paths
    # every traced op has a path in the compiled step's text
    assert {n for evs in t.ops.values() for n, _, _ in evs} <= set(paths)
    parts = sum(_read(m, run) for m in (
        "model_fwd_ms.train", "model_bwd_ms.train", "optimizer_ms.train",
        "exchange_ms.train"))
    busy_ms = t.mean_busy_ns() * 1e-6
    assert parts == pytest.approx(busy_ms, rel=1e-3)
    assert scopes.unscoped_ns(t, paths) < 1e-3 * t.mean_busy_ns()
    # the level fit's two bucket sorts, by their scope: phase 1's (the EF
    # residual's fit of the same buckets merged into it) and phase 2's;
    # the third sort is the compiler's, for the embedding's scatter-add
    traced = {n for evs in t.ops.values() for n, _, _ in evs}
    sorts = {n: paths[n] for n in traced if n.startswith("sort.")}
    assert sorted(n for n, p in sorts.items()
                  if scopes.is_level_fit(p)) == ["sort.10", "sort.7"]
    assert scopes.has(sorts["sort.7"], "reduce")
    assert scopes.has(sorts["sort.10"], "requantize")
    assert scopes.layer(sorts["sort.11"]) == "model_bwd"
    # the longest op, a loop, is the backward of the layer scan
    assert scopes.layer(paths["while.197"]) == "model_bwd"


def test_run_paths_compile_the_cells_step(rwkv_small, train_traffic_small,
                                          capsys):
    """A traced run's readers get their paths from the cell's step
    compiled again (here on the CPU at test size); a run whose step
    cannot be built leaves every metric out."""
    t = T.Trace({"/device:TPU:0": [("dot.1", 0, 10)]}, [], window=(0, 20))
    run = SimpleNamespace(trace=t, steps=1, chips=1, config=rwkv_small,
                          traffic=dict(train_traffic_small, data=1))
    paths = scopes.run_paths(run)
    assert run.scope_paths is paths
    assert {scopes.layer(p) for p in paths.values()} >= set(scopes.LAYERS)
    assert "busy time outside model" in capsys.readouterr().err
    broken = SimpleNamespace(trace=t, steps=1, chips=1, config=rwkv_small,
                             traffic=dict(train_traffic_small, mode="fsdp"))
    assert _read("exchange_ms.train", broken) is None
    assert broken.scope_paths == {}
