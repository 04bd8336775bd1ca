"""The plain float32 references against the program on the CPU, at a
small size, on the benchmark's own weights."""
import numpy as np
import pytest

from harness import common, weights
from harness.data import make_batch_fn


@pytest.mark.parametrize("seed", [1, 2**33 + 3])
def test_rwkv6_reference_loss_and_grads(rwkv_small, seed):
    import jax
    import jax.numpy as jnp

    from repro.models import LM

    ref = common.reference_module(rwkv_small)
    model = LM(common.model_config(rwkv_small))
    aparams = jax.eval_shape(model.init, jax.random.key(0))
    params = jax.jit(weights.make_params(aparams, jnp.float32))(
        weights.seed_key(seed))
    tokens = make_batch_fn(seed, 512, 96, 2)(0)["tokens"]
    lp, gp = jax.value_and_grad(
        lambda p: model.loss(p, {"tokens": tokens})[0])(params)
    with jax.default_matmul_precision("highest"):
        lr, gr = jax.value_and_grad(
            lambda p: ref.loss(p, tokens, rwkv_small))(params)
    # the program computes in bf16 (8 mantissa bits) where the reference
    # is float32: the loss (about 6.8) agreed within 3.2e-3 over three
    # seeds, gradients within 11 % of each leaf's norm (median 4-9 %)
    assert abs(float(lp) - float(lr)) < 2e-2
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert rel < 0.25


def test_rwkv6_reference_recurrence_matches_a_loop():
    import jax
    import jax.numpy as jnp

    ref = common.reference_module({"reference": "rwkv6_ref.py"})
    k = jax.random.split(jax.random.key(0), 5)
    B, S, H, hd = 1, 128, 2, 4
    r, kk, v = (jax.random.normal(k[i], (B, S, H, hd)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(k[3], (B, S, H, hd)))
    u = jax.random.normal(k[4], (H, hd))
    got = np.asarray(ref.wkv(r, kk, v, w, u))
    r, kk, v, w, u = (np.asarray(x, np.float64) for x in (r, kk, v, w, u))
    s = np.zeros((H, hd, hd))
    for t in range(S):
        kv = kk[0, t][:, :, None] * v[0, t][:, None, :]
        want = np.einsum("hij,hi->hj", s + u[:, :, None] * kv, r[0, t])
        np.testing.assert_allclose(got[0, t], want, rtol=2e-4, atol=2e-4)
        s = w[0, t][:, :, None] * s + kv


@pytest.mark.parametrize("scale", [1e-8, 1.0, 1e4])
def test_fp8_control_rounds_without_underflow(scale):
    import jax
    import jax.numpy as jnp

    ref = common.reference_module({"reference": "rwkv6_ref.py"})
    x = scale * jax.random.normal(jax.random.key(3), (4096,))
    for dtype, mantissa in ((jnp.float8_e4m3fn, 3), (jnp.float8_e5m2, 2)):
        y = ref._scaled_round(x, dtype)
        # per-tensor scaling puts every value of a normal sample inside
        # the format's normal range: each is off by at most half a unit
        # of its last mantissa bit, and none is flushed to zero
        rel = np.abs(np.asarray(y - x)) / np.abs(np.asarray(x))
        assert rel.max() <= 2.0 ** -(mantissa + 1) * 1.0001
        assert np.all(np.asarray(y) != 0)


def test_fp8_control_gradients_keep_every_leaf(rwkv_small):
    import jax
    import jax.numpy as jnp

    from repro.models import LM

    ref = common.reference_module(rwkv_small)
    model = LM(common.model_config(rwkv_small))
    aparams = jax.eval_shape(model.init, jax.random.key(0))
    params = jax.jit(weights.make_params(aparams, jnp.float32))(
        weights.seed_key(7))
    tokens = make_batch_fn(7, 512, 96, 2)(0)["tokens"]
    with jax.default_matmul_precision("highest"):
        l32, g32 = jax.value_and_grad(
            lambda p: ref.loss(p, tokens, rwkv_small))(params)
        l8, g8 = jax.value_and_grad(
            lambda p: ref.loss(p, tokens, rwkv_small, "fp8"))(params)
    # the loss within 1 % (read 0.17 % at this size), and no leaf's
    # gradient lost to underflow: each leaf's norm is finite and at least
    # half of float32's (read 0.67-1.04; fp8 weights move the small bonus
    # ``u``'s gradient most)
    assert abs(float(l8) - float(l32)) < 0.01 * float(l32)
    for a, b in zip(jax.tree_util.tree_leaves(g8),
                    jax.tree_util.tree_leaves(g32)):
        na, nb = float(jnp.linalg.norm(a)), float(jnp.linalg.norm(b))
        assert np.isfinite(na) and na >= 0.5 * nb, (na, nb)
