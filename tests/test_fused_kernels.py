"""Fused one-pass kernel pipeline: parity, jaxpr shape, env overrides.

The contracts pinned here (deterministic — no hypothesis; the property
sweep lives in test_fused_property.py):

* ``wire.encode``/``wire.qdq``/``wire.decode*`` on the fused path are
  BIT-identical to the PR-1..4 multi-pass pipeline and to the pure-jnp
  reference oracle, for every scheme, on ragged buffers, given the same
  PRNG key.
* The fused path lowers to exactly ONE ``pallas_call`` per encode/decode
  (the acceptance criterion of PR 5); the multi-pass path keeps >= 2.
* ``REPRO_USE_KERNELS=0`` forces the reference oracle everywhere and is
  read at TRACE time (the CI reference-oracle matrix leg relies on it).
* The kernel_bench regression gate parses the stable schema and fails on
  speedup regressions / bit-identity loss.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.comm import wire
from repro.core.quantizers import Quantizer
from repro.kernels import ops

jax.config.update("jax_platform_name", "cpu")

KEY = jax.random.key(11)

SCHEMES = {
    "orq-9": dict(method="orq", num_levels=9),
    "orq-17": dict(method="orq", num_levels=17),
    "orq-5-clip": dict(method="orq", num_levels=5, clip_c=2.5),
    "terngrad-clip": dict(method="terngrad", clip_c=2.5),
    "qsgd-9": dict(method="qsgd", num_levels=9),
    "linear-5": dict(method="linear", num_levels=5),
    "minmax2": dict(method="minmax2"),
    "bingrad-pb": dict(method="bingrad_pb"),
    "bingrad-b": dict(method="bingrad_b"),
    "bingrad-b-lloyd-clip": dict(method="bingrad_b", clip_c=2.5,
                                 lloyd_iters=2),
    "signsgd": dict(method="signsgd"),
}


def _qz(name, d=64):
    return Quantizer(bucket_size=d, **SCHEMES[name])


def _buffers(nb, d, valid=None, seed=1):
    bkt = jax.random.laplace(jax.random.key(seed), (nb, d)) * 0.1
    n = nb * d if valid is None else valid
    mask = jnp.arange(nb * d).reshape(nb, d) < n
    return bkt, mask


def _pallas_calls(closed) -> int:
    from repro.analysis import stats

    return stats.pallas_call_count(closed)


def _expect_pallas(closed, want: int) -> None:
    """Pin the launch count through the SAME rule the CI matrix audit
    runs (``python -m repro.analysis --check``)."""
    from repro.analysis import TraceBundle, run_checks

    fs = run_checks(
        [TraceBundle(label="pin", kind="wire_op", closed=closed,
                     meta={"expect_pallas_calls": want})],
        rules=["one-pallas-call"])
    assert not fs, [str(f) for f in fs]


class TestEncodeParity:
    @pytest.mark.parametrize("name", sorted(SCHEMES))
    @pytest.mark.parametrize("nb,d,valid", [
        (5, 37, 172),      # ragged width, ragged tail, non-multiple rows
        (8, 64, 8 * 64),   # exact tile fit, fully valid
        (1, 129, 100),     # single odd-width bucket
    ])
    def test_fused_vs_multipass_vs_ref(self, name, nb, d, valid):
        qz = _qz(name, d)
        bkt, mask = _buffers(nb, d, valid)
        w_f, lv_f = wire.encode(qz, bkt, mask, KEY, use_kernels=True)
        w_m, lv_m = wire.encode_multipass(qz, bkt, mask, KEY,
                                          use_kernels=True)
        w_r, lv_r = wire.encode(qz, bkt, mask, KEY, use_kernels=False)
        np.testing.assert_array_equal(np.asarray(w_f), np.asarray(w_m))
        np.testing.assert_array_equal(np.asarray(w_f), np.asarray(w_r))
        np.testing.assert_array_equal(np.asarray(lv_f), np.asarray(lv_m))
        np.testing.assert_array_equal(np.asarray(lv_f), np.asarray(lv_r))

    def test_prng_bits_threaded_not_refreshed(self):
        """Same key -> identical words; different key -> different rounding
        (the fused path must consume the SAME threefry stream)."""
        qz = _qz("orq-9")
        bkt, mask = _buffers(6, 64)
        w1, _ = wire.encode(qz, bkt, mask, jax.random.key(0))
        w2, _ = wire.encode(qz, bkt, mask, jax.random.key(0))
        w3, _ = wire.encode(qz, bkt, mask, jax.random.key(1))
        np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
        assert not np.array_equal(np.asarray(w1), np.asarray(w3))


class TestDecodeParity:
    @pytest.mark.parametrize("name", ["orq-9", "terngrad-clip", "bingrad-b",
                                      "orq-17"])
    @pytest.mark.parametrize("L", [1, 3, 4])
    def test_mean_and_each(self, name, L):
        nb, d = 5, 37
        qz = _qz(name, d)
        bkt, mask = _buffers(nb, d, 172)
        units = [wire.encode(qz, bkt, mask, jax.random.key(i))
                 for i in range(L)]
        ws = jnp.stack([u[0] for u in units])
        lvs = jnp.stack([u[1] for u in units])
        m_f = wire.decode_mean(qz, ws, lvs, d, use_kernels=True)
        m_m = wire.decode_mean_multipass(qz, ws, lvs, d, use_kernels=True)
        np.testing.assert_array_equal(np.asarray(m_f), np.asarray(m_m))
        m_r = wire.decode_mean(qz, ws, lvs, d, use_kernels=False)
        np.testing.assert_allclose(np.asarray(m_f), np.asarray(m_r),
                                   rtol=1e-6, atol=1e-7)
        e_f = wire.decode_each(qz, ws, lvs, d, use_kernels=True)
        e_m = wire.decode_each_multipass(qz, ws, lvs, d, use_kernels=True)
        np.testing.assert_array_equal(np.asarray(e_f), np.asarray(e_m))
        e_r = wire.decode_each(qz, ws, lvs, d, use_kernels=False)
        np.testing.assert_array_equal(np.asarray(e_f), np.asarray(e_r))

    def test_decode_average_flag(self):
        qz = _qz("orq-9", 64)
        bkt, mask = _buffers(4, 64)
        w, lv = wire.encode(qz, bkt, mask, KEY)
        ws, lvs = w[None], lv[None]
        np.testing.assert_array_equal(
            np.asarray(wire.decode(qz, ws, lvs, 64, average=True)),
            np.asarray(wire.decode_mean(qz, ws, lvs, 64)))
        np.testing.assert_array_equal(
            np.asarray(wire.decode(qz, ws, lvs, 64, average=False)),
            np.asarray(wire.decode_each(qz, ws, lvs, 64)))


class TestQdqParity:
    """wire.qdq is the error-feedback hot path: must equal the legacy
    fit -> assign -> masked select -> decode composition bit for bit."""

    @pytest.mark.parametrize("name", sorted(SCHEMES))
    def test_fused_vs_legacy_vs_ref(self, name):
        nb, d = 5, 37
        qz = _qz(name, d)
        bkt, mask = _buffers(nb, d, 172)
        got = wire.qdq(qz, bkt, mask, KEY, use_kernels=True)
        lv = qz.fit(bkt, mask)
        idx = jnp.where(mask, wire.assign(qz, bkt, lv, KEY, True, mask=mask),
                        0)
        want = Quantizer.decode(idx, lv)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        ref = wire.qdq(qz, bkt, mask, KEY, use_kernels=False)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


class TestJaxprOnePallasCall:
    """PR-5 acceptance: the fused path lowers to exactly ONE pallas_call
    per encode/decode, after ORQ's level-fit kernel (``orq_fit``); the
    multi-pass path kept >= 2; the reference oracle has none."""

    @pytest.fixture(autouse=True)
    def _kernels_on(self, monkeypatch):
        # these assertions are about the KERNEL lowering; pin the env so
        # the CI reference-oracle leg (REPRO_USE_KERNELS=0) doesn't turn
        # them vacuous/false
        monkeypatch.setenv("REPRO_USE_KERNELS", "1")

    def _encode_jaxpr(self, qz, use_kernels):
        bkt, mask = _buffers(5, 37)
        return jax.make_jaxpr(
            lambda b, m, k: wire.encode(qz, b, m, k,
                                        use_kernels=use_kernels))(
            bkt, mask, KEY)

    @pytest.mark.parametrize("name", ["orq-9", "terngrad-clip", "bingrad-b",
                                      "signsgd"])
    def test_encode_single_pallas_call(self, name):
        qz = _qz(name, 37)
        _expect_pallas(self._encode_jaxpr(qz, True),
                       1 + (qz.method == "orq"))

    def test_encode_ref_has_none(self):
        _expect_pallas(self._encode_jaxpr(_qz("orq-9", 37), False), 0)

    def test_encode_multipass_has_more(self):
        qz = _qz("orq-9", 37)
        bkt, mask = _buffers(5, 37)
        closed = jax.make_jaxpr(
            lambda b, m, k: wire.encode_multipass(qz, b, m, k))(
            bkt, mask, KEY)
        assert _pallas_calls(closed) >= 2

    @pytest.mark.parametrize("average", [True, False])
    def test_decode_single_pallas_call(self, average):
        qz = _qz("orq-9", 37)
        ws = jnp.zeros((3, 5, 10), jnp.uint32)
        lvs = jnp.zeros((3, 5, 9))
        closed = jax.make_jaxpr(
            lambda w, l: wire.decode(qz, w, l, 37, average=average))(
            ws, lvs)
        _expect_pallas(closed, 1)

    def test_qdq_single_pallas_call(self):
        qz = _qz("orq-9", 37)
        bkt, mask = _buffers(5, 37)
        closed = jax.make_jaxpr(
            lambda b, m, k: wire.qdq(qz, b, m, k))(bkt, mask, KEY)
        _expect_pallas(closed, 2)                     # orq_fit + qdq_fused


class TestUseKernelsEnv:
    """REPRO_USE_KERNELS forces the reference oracle globally and is read
    at trace time (documented next to REPRO_PALLAS_INTERPRET)."""

    def test_enabled_values(self, monkeypatch):
        monkeypatch.setenv("REPRO_USE_KERNELS", "0")
        assert ops.kernels_enabled() is False
        monkeypatch.setenv("REPRO_USE_KERNELS", "off")
        assert ops.kernels_enabled() is False
        monkeypatch.setenv("REPRO_USE_KERNELS", "1")
        assert ops.kernels_enabled() is True
        monkeypatch.setenv("REPRO_USE_KERNELS", "bogus")
        with pytest.raises(ValueError, match="REPRO_USE_KERNELS"):
            ops.kernels_enabled()
        monkeypatch.delenv("REPRO_USE_KERNELS", raising=False)
        assert ops.kernels_enabled() is True

    def test_env_read_at_trace_time(self, monkeypatch):
        """Flipping the env between two FRESH traces flips the lowering —
        the override must NOT be baked in at import time. (A fresh
        closure per trace: jax caches traces on function identity, which
        is exactly why the docs say to set the env before the first jit
        of a step function.)"""
        qz = _qz("orq-9", 37)
        bkt, mask = _buffers(5, 37)

        def trace():
            fn = lambda b, m, k: wire.encode(  # noqa: E731 — fresh each time
                qz, b, m, k, use_kernels=True)
            return _pallas_calls(jax.make_jaxpr(fn)(bkt, mask, KEY))

        monkeypatch.setenv("REPRO_USE_KERNELS", "0")
        assert trace() == 0
        monkeypatch.setenv("REPRO_USE_KERNELS", "1")
        assert trace() == 2                           # orq_fit + encode
        monkeypatch.setenv("REPRO_USE_KERNELS", "bogus")
        with pytest.raises(ValueError, match="REPRO_USE_KERNELS"):
            trace()

    def test_forced_oracle_matches_kernel_numerics(self, monkeypatch):
        qz = _qz("orq-9", 64)
        bkt, mask = _buffers(6, 64)
        want_w, want_lv = wire.encode(qz, bkt, mask, KEY)
        monkeypatch.setenv("REPRO_USE_KERNELS", "0")
        got_w, got_lv = wire.encode(qz, bkt, mask, KEY)
        np.testing.assert_array_equal(np.asarray(want_w), np.asarray(got_w))
        np.testing.assert_array_equal(np.asarray(want_lv),
                                      np.asarray(got_lv))


class TestOpsJit:
    """Satellite: the ops wrappers dispatch to jit'd implementations —
    repeat calls with the same static shapes must not re-trace."""

    def test_ref_wrappers_are_jitted(self):
        idx = jnp.zeros((2, 64), jnp.int32)
        a = ops.pack(idx, 2, use_kernels=False)
        traces0 = ops._ref_pack._cache_size()
        for _ in range(3):
            ops.pack(idx, 2, use_kernels=False)
        assert ops._ref_pack._cache_size() == traces0
        np.testing.assert_array_equal(
            np.asarray(a),
            np.asarray(ops.pack(idx, 2, use_kernels=True)))

    def test_fused_wrappers_are_jitted(self, monkeypatch):
        monkeypatch.setenv("REPRO_USE_KERNELS", "1")
        v = jnp.zeros((2, 64))
        lv = jnp.tile(jnp.linspace(-1, 1, 9), (2, 1))
        rb = jnp.zeros((2, 64), jnp.uint32)
        m = jnp.ones((2, 64), bool)
        ops.encode_fused(v, lv, rb, m, bits=4)
        from repro.kernels import fused_encode
        n0 = fused_encode.encode_fused._cache_size()
        for _ in range(3):
            ops.encode_fused(v, lv, rb, m, bits=4)
        assert fused_encode.encode_fused._cache_size() == n0


class TestBenchGate:
    """The kernel_bench --check gate: schema, bit-identity, geomean
    regression detection (pure logic — no timing)."""

    def _mk(self, speedups, op="encode", bit_identical=True):
        return {
            "schema": 1, "quick": True, "modes": ["interpret"],
            "summary": {},
            "entries": [
                {"key": f"{op}/s{i}/d512/interpret", "op": op,
                 "scheme": f"s{i}", "wire_bits": 4, "bucket": 512,
                 "nb": 24, "mode": "interpret", "fused_us": 100.0,
                 "multipass_us": 100.0 * r, "ref_us": 120.0,
                 "speedup_vs_multipass": r, "melems_per_s": 1.0,
                 "bit_identical": bit_identical}
                for i, r in enumerate(speedups)],
        }

    def _bench(self):
        import benchmarks.kernel_bench as kb
        return kb

    def test_pass_within_tolerance(self):
        kb = self._bench()
        base = self._mk([2.0, 2.0, 2.0])
        new = self._mk([1.8, 1.9, 2.1])       # geomean well within 25%
        assert kb.check(new, base, 0.25) == []

    def test_fails_on_geomean_regression(self):
        kb = self._bench()
        base = self._mk([2.0, 2.0, 2.0])
        new = self._mk([1.2, 1.3, 1.2])       # ~38% drop
        fails = kb.check(new, base, 0.25)
        assert any("geomean regressed" in f for f in fails)

    def test_fails_on_bit_identity_loss(self):
        kb = self._bench()
        base = self._mk([2.0])
        new = self._mk([2.0], bit_identical=False)
        fails = kb.check(new, base, 0.25)
        assert any("bit-identity" in f for f in fails), fails

    def test_fails_on_schema_change(self):
        kb = self._bench()
        base = self._mk([2.0])
        new = self._mk([2.0])
        new["schema"] = 999
        assert any("schema" in f for f in kb.check(new, base, 0.25))

    def test_fails_on_disjoint_keys(self):
        kb = self._bench()
        base = self._mk([2.0])
        new = self._mk([2.0])
        new["entries"][0]["key"] = "encode/other/d1/interpret"
        assert any("no overlapping" in f for f in kb.check(new, base, 0.25))

    def test_committed_baseline_parses_and_matches_schema(self):
        import json
        import os
        kb = self._bench()
        path = kb.DEFAULT_BASELINE
        assert os.path.exists(path), "committed baseline JSON missing"
        with open(path) as fh:
            base = json.load(fh)
        assert base["schema"] == kb.SCHEMA
        assert base["entries"], "baseline has no entries"
        assert all(e.get("bit_identical") for e in base["entries"])
        # the gate passes a run against itself
        assert kb.check(base, base, 0.25) == []


class TestPallasCallStats:
    """Jaxpr-based per-pallas_call VMEM/AI extraction (launch.hlo_cost):
    the HLO text parser cannot see interpret-mode pallas_calls, so the
    roofline report reads the jaxpr grid mapping instead. These pin that
    the fused kernels' row_block sizing actually holds per-grid-step
    residency under VMEM_TILE_BYTES while HBM traffic scales with the
    problem."""

    @pytest.fixture(autouse=True)
    def _kernels_on(self, monkeypatch):
        # the stats are about the KERNEL lowering; pin the env so the CI
        # reference-oracle leg (REPRO_USE_KERNELS=0) doesn't void them
        monkeypatch.setenv("REPRO_USE_KERNELS", "1")

    def _encode_jaxpr(self, nb, d=512):
        qz = Quantizer(bucket_size=d, method="orq", num_levels=9)
        bkt = jnp.ones((nb, d), jnp.float32)
        mask = jnp.ones((nb, d), jnp.float32)
        return jax.make_jaxpr(
            lambda b, m, k: wire.encode(qz, b, m, k, use_kernels=True)
        )(bkt, mask, KEY)

    def test_fused_encode_within_vmem_tile(self):
        from repro.kernels.fused_encode import VMEM_TILE_BYTES
        from repro.launch.hlo_cost import pallas_call_stats

        stats = pallas_call_stats(self._encode_jaxpr(nb=4096))
        enc = [s for s in stats if "encode" in s["kernel"]]
        assert enc, f"no encode pallas_call found in {stats}"
        for s in enc:
            # the tiling fix: per-grid-step residency obeys the VMEM cap
            # even though the full problem is ~27 MiB
            assert s["vmem_bytes"] <= VMEM_TILE_BYTES
            assert s["hbm_bytes"] > VMEM_TILE_BYTES
            assert s["grid_steps"] > 1
            assert s["arithmetic_intensity"] > 0

    def test_small_problem_single_grid_step(self):
        from repro.launch.hlo_cost import pallas_call_stats

        stats = pallas_call_stats(self._encode_jaxpr(nb=8))
        enc = [s for s in stats if "encode" in s["kernel"]]
        assert enc and all(s["grid_steps"] == 1 for s in enc)

    def test_vmem_scales_with_row_block_not_problem(self):
        from repro.launch.hlo_cost import pallas_call_stats

        small = pallas_call_stats(self._encode_jaxpr(nb=4096))
        large = pallas_call_stats(self._encode_jaxpr(nb=8192))
        vs = max(s["vmem_bytes"] for s in small if "encode" in s["kernel"])
        vl = max(s["vmem_bytes"] for s in large if "encode" in s["kernel"])
        # doubling the rows grows HBM traffic, not the per-step footprint
        assert vl <= vs * 1.5

    def test_decode_mean_stats_present(self):
        from repro.launch.hlo_cost import pallas_call_stats

        qz = Quantizer(bucket_size=512, method="orq", num_levels=9)
        bkt = jnp.ones((32, 512), jnp.float32)
        mask = jnp.ones((32, 512), jnp.float32)
        words, levels = wire.encode(qz, bkt, mask, KEY, use_kernels=True)
        ws = jnp.stack([words] * 4)
        lvs = jnp.stack([levels] * 4)
        closed = jax.make_jaxpr(
            lambda w, l: wire.decode_mean(qz, w, l, 512, use_kernels=True)
        )(ws, lvs)
        stats = pallas_call_stats(closed)
        assert any("decode" in s["kernel"] for s in stats)
        assert all(s["vmem_bytes"] > 0 and s["hbm_bytes"] >= s["vmem_bytes"]
                   for s in stats)
