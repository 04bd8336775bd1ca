"""The entry points' contract with the device: the compile-cache rule,
no CPU fallback on the chip path, and ``chip_smoke.py`` refusing to run
without a TPU."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.utils import env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **extra_env):
    e = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
             JAX_PLATFORMS="cpu", **extra_env)
    return subprocess.run([sys.executable, *args], env=e, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)


class TestCompileCache:
    def test_default_is_fixed_path_in_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        was = jax.config.jax_compilation_cache_dir
        try:
            assert env.use_compile_cache() == env.DEFAULT_COMPILE_CACHE_DIR
            assert (jax.config.jax_compilation_cache_dir
                    == os.path.join(ROOT, ".jax_cache"))
        finally:
            jax.config.update("jax_compilation_cache_dir", was)

    def test_env_dir_wins_and_nothing_is_set(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        was = jax.config.jax_compilation_cache_dir
        assert env.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was

    def test_cache_dir_is_gitignored(self):
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


def test_chip_path_imports_no_host_device_forcing():
    """dryrun and the analysis CLI set JAX_PLATFORMS=cpu (and 512 or 8
    fake devices) as they are imported; nothing on the chip path may
    import them."""
    out = _run(["-c", textwrap.dedent("""
        import os, sys
        sys.path.insert(0, ".")
        os.environ.pop("JAX_PLATFORMS")
        import chip_smoke
        import repro.core.comm.wire, repro.kernels.ops, repro.data
        import repro.kernels.fused_kv, repro.serve.kv_cache
        import repro.launch.train, repro.launch.serve, repro.launch.mesh
        import repro.train.step, repro.optim.schedule
        bad = [m for m in ("repro.launch.dryrun",
                           "repro.analysis.__main__") if m in sys.modules]
        assert not bad, bad
        assert "JAX_PLATFORMS" not in os.environ
        print("clean")
        """)])
    assert out.returncode == 0, out.stderr
    assert "clean" in out.stdout


def test_chip_smoke_refuses_the_cpu():
    out = _run(["chip_smoke.py"])
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_serve_returns_every_requests_tokens():
    from repro.launch import serve

    toks = serve.serve(["--smoke", "--kv-quant", "orq-9", "--batch", "2",
                        "--prompt-len", "8", "--gen", "4", "--max-len",
                        "32", "--page-size", "8"])
    assert toks.shape == (2, 4) and toks.dtype == np.int32


@pytest.mark.parametrize("var,value,why", [
    ("REPRO_PALLAS_INTERPRET", "1", "interpret mode"),
    ("REPRO_USE_KERNELS", "0", "oracle")])
def test_chip_smoke_rejects_kernel_overrides(var, value, why, monkeypatch):
    """On a TPU these overrides would swap in the interpreter or the
    oracle; the device check refuses them (steered here past the
    platform check)."""
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke

    class FakeTpu:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    monkeypatch.setenv(var, value)
    with pytest.raises(SystemExit, match=why):
        chip_smoke.device_check(1)


def test_train_trace_dir_writes_host_spans(tmp_path, capsys):
    """``--trace-dir`` profiles the steps after the compile: the written
    ``.xplane.pb`` holds the launcher's ``train:step`` host spans, one per
    traced step, and its ``s/step`` lines leave the compiling step out."""
    import glob

    from repro.launch import train

    train.main(["--arch", "lm-100m", "--smoke", "--steps", "4", "--batch",
                "2", "--seq", "16", "--quant", "orq-9", "--log-every", "1",
                "--trace-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert "first step" in out and "compile included" in out
    assert f"trace of steps 2-3 -> {tmp_path}" in out
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    names = [e.name for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events]
    assert names.count("train:step") == 2
    assert names.count("train:batch") == 2
    assert names.count("train:metrics") == 2
