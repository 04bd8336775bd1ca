"""Fused quantized-KV serving kernels: parity, jaxpr pins, overrides.

Contracts pinned here (PR-7 acceptance):

* ``append_kv`` (one-pass quantize of new K/V rows to wire format) and
  ``decode_attend`` (fused dequant-attention over a packed context) are
  BIT-identical between the Pallas kernel path and the pure-jnp oracle,
  for schemes covering every wire width 1..5 bits plus BinGrad-b, across
  ragged page fills.
* Each lowers to exactly ONE ``pallas_call``; ``REPRO_USE_KERNELS=0``
  forces the oracle (zero pallas calls), read at trace time.
* ``append_kv``'s K/V stacking is a pure batching trick: each row's bits
  equal a standalone ``wire.encode`` of that tensor alone.
* ``decode_attend`` numerics match an independent numpy unpack ->
  level-decode -> masked-softmax GQA attention oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rounding as R
from repro.core.api import make_quantizer
from repro.core.comm import wire
from repro.kernels import ops
from repro.kernels.fused_kv import append_kv, decode_attend

jax.config.update("jax_platform_name", "cpu")

KV, HD = 2, 8
D = KV * HD           # one bucket per token spans all KV heads

#: scheme -> expected wire bits; covers widths 1..5 plus BinGrad-b
SCHEMES = {
    "signsgd": 1,
    "bingrad-b": 1,
    "orq-3": 2,
    "orq-5": 3,
    "orq-9": 4,
    "orq-17": 5,
}


def _qz(name):
    return make_quantizer(name, bucket_size=D)


def _rbits(qz, rows, seed=3):
    if wire._fused_mode(qz) != "rr":
        return None
    return R.random_bits(jax.random.key(seed), (rows, D))


def _context(name, B, C, seed=0):
    """Quantize B*C random tokens' K/V rows and shape them as per-sequence
    (B, C, ...) paged-context views."""
    qz = _qz(name)
    kk = jax.random.split(jax.random.key(seed), 2)
    k_rows = jax.random.normal(kk[0], (B * C, D)) * 0.3
    v_rows = jax.random.normal(kk[1], (B * C, D)) * 0.3
    parts = append_kv(qz, k_rows, v_rows, _rbits(qz, 2 * B * C))
    return qz, tuple(p.reshape(B, C, -1) for p in parts)


def _fill_mask(fills, T, C):
    """Ragged page fills: sequence b attends to its first fills[b] slots."""
    B = len(fills)
    m = jnp.arange(C)[None, None, :] < jnp.asarray(fills)[:, None, None]
    return jnp.broadcast_to(m, (B, T, C))


class TestAppendParity:
    @pytest.mark.parametrize("name,bits", sorted(SCHEMES.items()))
    @pytest.mark.parametrize("rows", [1, 7, 16])   # ragged + exact fills
    def test_kernel_vs_oracle_bit_identical(self, name, bits, rows):
        qz = _qz(name)
        assert qz.wire_bits_per_element == bits
        kk = jax.random.split(jax.random.key(5), 2)
        k_rows = jax.random.laplace(kk[0], (rows, D)) * 0.2
        v_rows = jax.random.laplace(kk[1], (rows, D)) * 0.2
        rb = _rbits(qz, 2 * rows)
        got = append_kv(qz, k_rows, v_rows, rb, use_kernels=True)
        want = append_kv(qz, k_rows, v_rows, rb, use_kernels=False)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("name", sorted(SCHEMES))
    def test_stacking_matches_standalone_encode(self, name):
        """K/V stacking is pure batching: every encode stage is
        independent per bucket row, so each tensor's bits equal a
        standalone wire.encode of that tensor alone."""
        qz = _qz(name)
        rows = 6
        kk = jax.random.split(jax.random.key(8), 2)
        k_rows = jax.random.normal(kk[0], (rows, D))
        v_rows = jax.random.normal(kk[1], (rows, D))
        rb = _rbits(qz, 2 * rows)
        kw, klv, vw, vlv = append_kv(qz, k_rows, v_rows, rb)
        ones = jnp.ones((rows, D), dtype=bool)
        rk = None if rb is None else rb[:rows]
        rv = None if rb is None else rb[rows:]
        kw2, klv2 = wire.encode(qz, k_rows, ones, None, rbits=rk)
        vw2, vlv2 = wire.encode(qz, v_rows, ones, None, rbits=rv)
        for g, w in zip((kw, klv, vw, vlv), (kw2, klv2, vw2, vlv2)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_rejects_schemes_without_fused_encode(self):
        with pytest.raises(ValueError, match="fused one-pass encode"):
            append_kv(make_quantizer("fp", bucket_size=D),
                      jnp.zeros((2, D)), jnp.zeros((2, D)), None)


class TestAttendParity:
    @pytest.mark.parametrize("name", sorted(SCHEMES))
    @pytest.mark.parametrize("T", [1, 3])          # decode + prefill chunk
    def test_kernel_vs_oracle_bit_identical(self, name, T):
        B, C, H = 3, 12, 4
        qz, (kw, klv, vw, vlv) = _context(name, B, C)
        q = jax.random.normal(jax.random.key(7), (B, T, H, HD),
                              jnp.float32)
        mask = _fill_mask([5, 12, 1], T, C)        # ragged page fills
        kw_ = dict(bits=qz.wire_bits_per_element, kv_heads=KV,
                   scale=HD ** -0.5)
        got = ops.decode_attend(q, kw, klv, vw, vlv, mask,
                                use_kernels=True, **kw_)
        want = ops.decode_attend(q, kw, klv, vw, vlv, mask,
                                 use_kernels=False, **kw_)
        assert got.shape == (B, T, H, HD)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_softcap_kernel_vs_oracle(self):
        B, T, C, H = 2, 2, 8, 4
        qz, (kw, klv, vw, vlv) = _context("orq-9", B, C)
        q = jax.random.normal(jax.random.key(9), (B, T, H, HD))
        mask = _fill_mask([8, 3], T, C)
        kw_ = dict(bits=qz.wire_bits_per_element, kv_heads=KV,
                   scale=HD ** -0.5, softcap=4.0)
        got = ops.decode_attend(q, kw, klv, vw, vlv, mask,
                                use_kernels=True, **kw_)
        want = ops.decode_attend(q, kw, klv, vw, vlv, mask,
                                 use_kernels=False, **kw_)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_matches_independent_numpy_oracle(self):
        """Unpack words + level-decode + masked-softmax GQA attention in
        plain numpy — independent of ref.kv_attend_block."""
        name, B, T, C, H = "orq-9", 2, 1, 8, 4
        qz, (kw, klv, vw, vlv) = _context(name, B, C)
        bits = qz.wire_bits_per_element
        q = jax.random.normal(jax.random.key(11), (B, T, H, HD),
                              jnp.float32)
        fills = [6, 8]
        mask = _fill_mask(fills, T, C)
        got = np.asarray(ops.decode_attend(
            q, kw, klv, vw, vlv, mask, bits=bits, kv_heads=KV,
            scale=HD ** -0.5))

        epw = 32 // bits
        m = (1 << bits) - 1

        def dec(w, lv):
            w = np.asarray(w)
            # slice layout: bit field j holds columns [j*nw, (j+1)*nw)
            idx = np.concatenate(
                [(w >> (bits * j)) & m for j in range(epw)], axis=-1)
            idx = idx[:, :, :D].astype(np.int64)
            vals = np.take_along_axis(np.asarray(lv, np.float32), idx,
                                      axis=-1)
            return vals.reshape(B, C, KV, HD)

        k = dec(kw, klv)
        v = dec(vw, vlv)
        g = H // KV
        qg = np.asarray(q, np.float32).reshape(B, T, KV, g, HD)
        sc = np.einsum("btkgh,bckh->bkgtc", qg, k,
                       dtype=np.float32) * (HD ** -0.5)
        mb = np.asarray(mask)[:, 0][:, None, None, None, :]  # (B,1,1,1,C)
        sc = np.where(mb, sc, -2.0e38)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        want = np.einsum("bkgtc,bckh->btkgh", p, v,
                         dtype=np.float32).reshape(B, T, H, HD)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


class TestJaxprPins:
    """PR-7 acceptance: one pallas_call per hot path; the oracle leg
    (REPRO_USE_KERNELS=0) lowers to zero."""

    @pytest.fixture(autouse=True)
    def _kernels_on(self, monkeypatch):
        # these assertions are about the KERNEL lowering; pin the env so
        # the CI reference-oracle leg (REPRO_USE_KERNELS=0) doesn't turn
        # them vacuous/false
        monkeypatch.setenv("REPRO_USE_KERNELS", "1")

    @staticmethod
    def _expect_pallas(closed, want: int) -> None:
        # the same rule the CI matrix audit runs (python -m repro.analysis)
        from repro.analysis import TraceBundle, run_checks

        fs = run_checks(
            [TraceBundle(label="pin", kind="serve_fwd", closed=closed,
                         meta={"expect_pallas_calls": want})],
            rules=["one-pallas-call"])
        assert not fs, [str(f) for f in fs]

    @pytest.mark.parametrize("name", sorted(SCHEMES))
    def test_append_single_pallas_call(self, name):
        qz = _qz(name)
        rb = _rbits(qz, 8)
        closed = jax.make_jaxpr(
            lambda k, v: append_kv(qz, k, v, rb))(
                jnp.zeros((4, D)), jnp.zeros((4, D)))
        self._expect_pallas(closed, 1)

    def test_attend_single_pallas_call(self):
        B, T, C, H = 2, 1, 8, 4
        qz, (kw, klv, vw, vlv) = _context("orq-9", B, C)
        mask = _fill_mask([8, 4], T, C)
        closed = jax.make_jaxpr(
            lambda q: decode_attend(q, kw, klv, vw, vlv, mask,
                                    bits=qz.wire_bits_per_element,
                                    kv_heads=KV, scale=0.25))(
                jnp.zeros((B, T, H, HD)))
        self._expect_pallas(closed, 1)

    def test_env_override_forces_oracle(self, monkeypatch):
        monkeypatch.setenv("REPRO_USE_KERNELS", "0")
        B, T, C, H = 2, 1, 8, 4
        qz, (kw, klv, vw, vlv) = _context("orq-9", B, C)
        mask = _fill_mask([8, 4], T, C)
        closed = jax.make_jaxpr(
            lambda q: ops.decode_attend(q, kw, klv, vw, vlv, mask,
                                        bits=qz.wire_bits_per_element,
                                        kv_heads=KV, scale=0.25))(
                jnp.zeros((B, T, H, HD)))
        self._expect_pallas(closed, 0)
