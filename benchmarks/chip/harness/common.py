"""What every cell shares: finding its files, the device check, compile
counting, the per-layer readers and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
configuration's entry names its file, ``configs/<name>.json``, and the
traffic is ``traffic/<traffic>.json``, whose ``kind`` picks the general
runner (``harness/<kind>.py``; ``train`` is the one there is). A per-layer metric
``<name>`` is read by ``metrics/<name>.py``'s ``read(run)``.
"""
from __future__ import annotations

import dataclasses
import importlib.metadata
import importlib.util
import json
import math
import os
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = os.path.join(BENCH_DIR, ".cache", "jax")
#: traces of ``--trace 1`` runs, replaced by each such run
TRACE_DIR = os.path.join(BENCH_DIR, ".cache", "trace")


class BenchError(SystemExit):
    """A run that cannot give a result: exit non-zero, print no line."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: Optional[str] = None):
    """Import the file at ``path`` (names may hold '.' and '-')."""
    name = name or "bench_" + os.path.basename(path)[:-3].replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]        # the configuration file's contents
    traffic: Dict[str, Any]       # the traffic file's contents
    end_to_end: List[dict]        # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def reports(metric: dict, cell: str, e2e_names) -> bool:
    """Whether ``cell`` reports ``metric`` (its ``workloads`` key, else
    every cell that reports the end-to-end metric that it moves)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def load_cell(name: str, bench_path: Optional[str] = None) -> Cell:
    bench = load_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, confs[w["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if reports(m, name, ())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def reference_module(config: Dict[str, Any]):
    """The plain reference beside the configuration file."""
    return load_module(os.path.join(BENCH_DIR, "configs",
                                    config["reference"]))


def model_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for this configuration: the
    program's registry entry with every size in the file applied, each
    nested group whole."""
    from repro.configs import base

    cfg = base.get_config(config["program_config"])
    nested = {"rwkv": base.RWKVParams}
    fields = {f.name for f in dataclasses.fields(cfg)}
    upd = {}
    for k, v in config["sizes"].items():
        if k not in fields:
            raise BenchError(f"{config['name']}: {k!r} is not a size of "
                             f"the program's configuration")
        if k in nested and isinstance(v, dict):
            v = nested[k](**v)
        elif isinstance(v, list):
            v = tuple(v)
        upd[k] = v
    return dataclasses.replace(cfg, **upd)


# ---------------------------------------------------------------- device

def device_check(chips: int):
    """The first ``chips`` TPU devices; raises :class:`BenchError` on any
    other platform, too few chips, Pallas interpret mode or kernels
    swapped for their oracles."""
    import jax
    import jaxlib

    devs = jax.devices()
    d0 = devs[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"platform {d0.platform}, device_kind {d0.device_kind}, devices "
        f"{len(devs)}; jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}")
    if d0.platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {d0.platform!r}; the "
                         "benchmark never falls back to it")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, {len(devs)} "
                         "visible")
    from repro.utils.env import kernels_enabled, pallas_interpret

    if pallas_interpret():
        raise BenchError("Pallas interpret mode is on "
                         "(REPRO_PALLAS_INTERPRET): kernels would not run "
                         "compiled")
    if not kernels_enabled():
        raise BenchError("REPRO_USE_KERNELS=0 swaps every kernel for its "
                         "oracle")
    return devs[:chips]


def device_info(devices) -> Dict[str, Any]:
    stats = [d.memory_stats() or {} for d in devices]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": int(peak)}


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, from JAX's
    own events; ``window()`` gives those since ``mark()``."""

    def __init__(self):
        import jax

        self.compiles = self.hits = self.misses = 0
        self._mark = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _count(self) -> int:
        return self.compiles + self.hits

    def mark(self) -> None:
        self._mark = self._count()

    def window(self) -> int:
        return self._count() - self._mark


# ------------------------------------------------------------ the result

def check(name: str, value: float, limit: float,
          at_least: bool = False) -> Dict[str, Any]:
    """One compared number: it passes while ``value <= limit`` (``value
    >= limit`` where ``at_least``)."""
    ok = value is not None and math.isfinite(value) and (
        value >= limit if at_least else value <= limit)
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


def load_limits(cell: str) -> Dict[str, float]:
    """The cell's limits, ``limits/<cell>.json`` (each set from the
    readings that PERF.md gives)."""
    return load_json(os.path.join(BENCH_DIR, "limits", cell + ".json"))


def start_trace() -> None:
    import shutil

    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(TRACE_DIR)


def stop_trace():
    import jax

    from harness.trace import Trace

    jax.profiler.stop_trace()
    return Trace.from_dir(TRACE_DIR)


def read_per_layer(cell: Cell, run: Any) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric of the cell, from its reader; a reader that
    finds nothing returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(os.path.join(BENCH_DIR, "metrics",
                                       m["name"] + ".py"))
        v = mod.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def emit(*, correct: bool, attempted: int, failed: int,
         metrics: Dict[str, Any], device: Dict[str, Any],
         checks: List[Dict[str, Any]], breakdown=None) -> None:
    """The compared numbers as the last lines of stderr, then the result
    as the last line of stdout (``checks`` last in it)."""
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
            f"{'ok' if c['ok'] else 'FAILED'}")
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
