"""The sort-free ORQ level fit: the ``orq_fit`` kernel against its jnp
oracle (bit-identical in interpret mode), and the sort-free solve against
the sort-based one it replaced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import levels as L
from repro.core.quantizers import Quantizer

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------ kernel vs jnp oracle

def _awkward_buckets(nb: int, d: int, seed: int):
    """Gaussian buckets with ragged masks, an all-masked row, repeated
    values, exact zeros of both signs and a constant row."""
    rng = np.random.default_rng(seed)
    v = (rng.standard_normal((nb, d)) * 1e-3).astype(np.float32)
    v[:, ::7] = 0.0
    v[:, 3::11] = -0.0
    v[1] = np.round(v[1] * 2e3) / 2e3                  # few distinct values
    v[2] = 0.25                                        # constant bucket
    v[4, : d // 2] = -0.0                              # half the row -0.0
    mask = rng.random((nb, d)) < 0.9
    mask[0] = False                                    # all masked
    mask[2] = True
    mask[-1, d // 3:] = False                          # ragged last bucket
    return jnp.asarray(v), jnp.asarray(mask)


# (K, refine_iters, d, nb, clip_c): every K with and without refinement;
# nb spans two or three row blocks of the kernel, the last one ragged
# (120 rows of 2,048 lanes, 248 of 1,024, 1,976 of 128)
CASES = [
    (1, 0, 64, 2100, None),
    (1, 2, 2048, 250, 2.5),
    (2, 0, 1000, 300, None),
    (2, 2, 64, 2100, None),
    (3, 0, 2048, 250, None),
    (3, 2, 1000, 300, 2.5),
    (4, 0, 64, 2100, 1.7),
    (4, 2, 2048, 250, None),
]


@pytest.mark.parametrize("K,refine_iters,d,nb,clip_c", CASES)
def test_orq_fit_kernel_matches_oracle(K, refine_iters, d, nb, clip_c):
    qz = Quantizer("orq", 2 ** K + 1, bucket_size=d, clip_c=clip_c,
                   refine_iters=refine_iters)
    v, mask = _awkward_buckets(nb, d, seed=K * 10 + refine_iters)
    jaxpr = str(jax.make_jaxpr(
        lambda v, m: qz.fit(v, m, use_kernels=True))(v, mask))
    assert "pallas_call" in jaxpr and "sort" not in jaxpr
    kern = np.asarray(qz.fit(v, mask, use_kernels=True))
    orac = np.asarray(qz.fit(v, mask, use_kernels=False))
    assert kern.shape == (nb, 2 ** K + 1)
    np.testing.assert_array_equal(kern.view(np.uint32), orac.view(np.uint32))
    assert np.isfinite(kern).all()
    assert (np.diff(kern, axis=-1) >= 0).all()
    np.testing.assert_array_equal(kern[0], 0.0)        # all-masked bucket
    if clip_c is None:                                 # constant bucket
        np.testing.assert_array_equal(kern[2], 0.25)


# ----------------------------------- sort-free vs the sort-based solver

def _solve_sorted(sb, bl, br):
    """The sort-based Eq. (12) solve the fit used before: counts and the
    interval sum from the sorted bucket's prefix sums, the level a gather.
    Also returns x = idx_r - rhs, the value ``round`` decides on."""
    idx_l = L._count_lt(sb, bl)
    idx_r = L._count_le(sb, br)
    cnt_in = (idx_r - idx_l).astype(jnp.float32)
    sum_in = L._take(sb.psum, idx_r) - L._take(sb.psum, idx_l)
    width = br - bl
    safe_w = jnp.where(width > 0, width, 1.0)
    rhs = (sum_in - bl * cnt_in) / safe_w
    x = idx_r.astype(jnp.float32) - rhs
    j = jnp.clip(jnp.round(x).astype(jnp.int32), idx_l,
                 jnp.maximum(idx_r - 1, idx_l))
    b = L._take(sb.v, jnp.clip(j, 0, sb.v.shape[-1] - 1))
    b = jnp.where(jnp.isfinite(b), b, 0.0)
    b = jnp.where((cnt_in > 0) & (width > 0), b, 0.5 * (bl + br))
    return jnp.clip(b, jnp.minimum(bl, br), jnp.maximum(bl, br)), x


@jax.jit
def _orq9_sorted(bkt, mask):
    """Algorithm 1 for s = 9 on sorted buckets; levels (nb, 9) and the
    solves in their order, each as (level index, x)."""
    sb = L.sort_buckets(bkt, mask)
    lv = [None] * 9
    lv[0], lv[8] = L._bucket_min(sb), L._bucket_max(sb)
    xs = []
    for step in (8, 4, 2):
        for lo in range(0, 8, step):
            k = lo + step // 2
            lv[k], x = _solve_sorted(sb, lv[lo], lv[lo + step])
            xs.append(x)
    return jnp.stack(lv, axis=-1), jnp.stack(xs, axis=-1)


#: level index of each solve of ``_orq9_sorted``, in order
SOLVE_ORDER = (4, 2, 6, 1, 3, 5, 7)


def _integers(key, shape):
    """Small integers with many repeats and zeros of both signs: every
    sum is exact in f32, so the two solves meet no rounding to differ on."""
    v = jax.random.randint(key, shape, -6, 7).astype(jnp.float32)
    return jnp.where(jax.random.bernoulli(key, 0.3, shape), -0.0, v)


@pytest.mark.parametrize("dist", ["gaussian", "laplace", "student_t2",
                                  "integers"])
def test_sort_free_solve_matches_sorted(dist):
    """The same order statistics: levels equal in every bucket but those
    where ``round`` meets a half-way tie, which the two interval sums
    (masked sum here, difference of prefix sums there) may round to
    either side. Those are at most 1e-3 of buckets (none where the sums
    are exact), and in each the first level that differs was solved from
    x within 0.01 of a half."""
    nb, d = 4096, 2048
    key = jax.random.key(
        {"gaussian": 1, "laplace": 2, "student_t2": 3, "integers": 4}[dist])
    g = {"gaussian": lambda: jax.random.normal(key, (nb, d)) * 1e-3,
         "laplace": lambda: jax.random.laplace(key, (nb, d)) * 1e-3,
         "student_t2": lambda: jax.random.t(key, 2.0, (nb, d)) * 1e-3,
         "integers": lambda: _integers(key, (nb, d))}[dist]()
    mask = jnp.ones((nb, d), bool).at[-1, 1000:].set(False).at[-2].set(False)
    # an exact half-way tie: x = 4 - (0 + 0 + 2 + 4) / 4 = 2.5 rounds to
    # even, level 2 (up would give 4)
    g = g.at[-3, :4].set(jnp.array([0.0, 0.0, 2.0, 4.0]))
    mask = mask.at[-3, 4:].set(False)
    new = np.asarray(jax.jit(lambda g, m: L.orq_levels(g, m, 3))(g, mask))
    old, xs = (np.asarray(a) for a in _orq9_sorted(g, mask))
    rows = np.flatnonzero((new != old).any(axis=-1))
    assert len(rows) <= (0 if dist == "integers" else 1e-3 * nb), (
        dist, len(rows))
    np.testing.assert_array_equal(new[-3, [0, 4, 8]], [0.0, 2.0, 4.0])
    for r in rows:
        i = next(i for i, k in enumerate(SOLVE_ORDER)
                 if new[r, k] != old[r, k])
        frac = xs[r, i] - np.floor(xs[r, i])
        assert abs(frac - 0.5) < 0.01, (dist, r, SOLVE_ORDER[i], xs[r, i])
