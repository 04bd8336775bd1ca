"""The harness finds every cell's files by name, its generators are
seeded, and it refuses to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from harness import common
from harness.data import make_batch_fn

BENCH = os.path.join(common.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    with open(BENCH) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_cell_resolves_to_its_files(cell):
    c = common.load_cell(cell)
    assert c.kind == "train"
    assert os.path.exists(os.path.join(common.BENCH_DIR, "configs",
                                       c.config["reference"]))
    assert set(common.load_limits(cell))
    model_cfg = common.model_config(c.config)
    for k, v in c.config["sizes"].items():
        got = getattr(model_cfg, k)
        if isinstance(v, dict):
            got = {f: getattr(got, f) for f in v}
        assert got == (tuple(v) if isinstance(v, list) else v), k
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        mod = common.load_module(os.path.join(common.BENCH_DIR, "metrics",
                                              m["name"] + ".py"))
        assert callable(mod.read)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2


def test_benchmark_file_keeps_to_its_shape():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"][1].startswith(b["paths"][0] + "/")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(
        names)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
    for c in b["configs"]:
        assert os.path.exists(os.path.join(common.ROOT, c["file"]))
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)


def test_batches_are_seeded():
    fn = make_batch_fn(2**33 + 1, 512, 32, 2)
    other = make_batch_fn(5, 512, 32, 2)
    a, b = np.asarray(fn(0)["tokens"]), np.asarray(fn(0)["tokens"])
    assert a.shape == (2, 33) and a.max() < 512
    assert np.array_equal(a, b)
    assert not np.array_equal(a, np.asarray(fn(1)["tokens"]))
    assert not np.array_equal(a, np.asarray(other(0)["tokens"]))
    assert not np.array_equal(a[0], a[1])


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "rwkv6-3b.train.orq9-ef", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run(common.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(BENCH, tmp_path / "BENCHMARK.json")
    shutil.copytree(common.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
