"""Quantization-level solvers.

This file implements the paper's contribution:

* ``orq_levels``       — Algorithm 1: greedy recursive bisection solving the
  optimal unbiased random-rounding condition Eq. (11)/(12) on the *empirical*
  per-bucket gradient distribution, for s = 2^K + 1 levels. Endpoints are the
  bucket min/max (Corollary 1.1).
* ``bingrad_pb_b1``    — Eq. (15): optimal partially-biased binary level b₁
  (b₋₁ = −b₁ under the paper's zero-mean-symmetric assumption).
* ``bingrad_b_levels`` — Eq. (17): fully-biased binary levels; paper sets
  b₀ = mean(G) for ease of implementation, b±₁ = conditional means. Optional
  ``lloyd_iters`` iterates the Eq. (17) fixed point exactly (beyond-paper; the
  paper's conclusion flags the greedy solver as future work to improve).

Baseline level rules (paper §5 comparison set):

* ``terngrad_levels`` — {−max|v|, 0, +max|v|} (TernGrad).
* ``qsgd_levels``     — s levels evenly spaced over ±‖G‖ (paper §3.1: "evenly
  spaced from −‖G‖ to ‖G‖"; ‖·‖ = ℓ∞ per bucket by default — TernGrad's scale
  and the common practical QSGD choice; ``norm='l2'`` gives the literal QSGD
  scaling).
* ``linear_levels``   — s levels linearly dividing the empirical CDF
  (quantiles), the paper's "Linear-s" naive baseline.
* ``signsgd_scale``   — scaled SignSGD: ±‖G‖₁/dim (Eq. 13).

All solvers are vectorized over buckets: inputs are ``(nb, d)`` values with a
``(nb, d)`` validity mask; outputs are ascending ``(nb, s)`` level tables in
float32. Everything is jit-safe (static shapes, no data-dependent control
flow).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
from jax import lax


class SortedBuckets(NamedTuple):
    """Sorted per-bucket values with prefix sums; the 'empirical p(v)'."""

    v: jnp.ndarray      # (nb, d) ascending; padding sorted to the end as +inf
    psum: jnp.ndarray   # (nb, d+1) prefix sums of valid values (pads count 0)
    cnt: jnp.ndarray    # (nb,) int32 number of valid values


def sort_buckets(bkt: jnp.ndarray, mask: jnp.ndarray) -> SortedBuckets:
    bkt = bkt.astype(jnp.float32)
    v = jnp.sort(jnp.where(mask, bkt, jnp.inf), axis=-1)
    finite = jnp.isfinite(v)
    vz = jnp.where(finite, v, 0.0)
    psum = jnp.concatenate(
        [jnp.zeros_like(vz[:, :1]), jnp.cumsum(vz, axis=-1)], axis=-1
    )
    cnt = mask.sum(axis=-1).astype(jnp.int32)
    return SortedBuckets(v=v, psum=psum, cnt=cnt)


def _count_lt(sb: SortedBuckets, x: jnp.ndarray) -> jnp.ndarray:
    """Per bucket: #(v < x). x: (nb,) -> (nb,) int32."""
    return (jnp.where(jnp.isfinite(sb.v), sb.v, jnp.inf) < x[:, None]).sum(
        axis=-1
    ).astype(jnp.int32)


def _count_le(sb: SortedBuckets, x: jnp.ndarray) -> jnp.ndarray:
    return (jnp.where(jnp.isfinite(sb.v), sb.v, jnp.inf) <= x[:, None]).sum(
        axis=-1
    ).astype(jnp.int32)


def _take(a: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Per-bucket gather: a (nb, m), idx (nb,) -> (nb,)."""
    return jnp.take_along_axis(a, idx[:, None], axis=-1)[:, 0]


def _bucket_min(sb: SortedBuckets) -> jnp.ndarray:
    v0 = sb.v[:, 0]
    return jnp.where(sb.cnt > 0, jnp.where(jnp.isfinite(v0), v0, 0.0), 0.0)


def _bucket_max(sb: SortedBuckets) -> jnp.ndarray:
    idx = jnp.maximum(sb.cnt - 1, 0)
    vm = _take(sb.v, idx)
    return jnp.where(sb.cnt > 0, jnp.where(jnp.isfinite(vm), vm, 0.0), 0.0)


# ---------------------------------------------------------------------------
# ORQ: optimal unbiased multi-level condition (Theorem 1, Eqs. 11/12, Alg. 1)
# ---------------------------------------------------------------------------

#: lane tile: ``orq_levels`` pads bucket rows to a multiple of it, as the
#: ``orq_fit`` kernel does, so both reduce over the same widths
LANES = 128
#: bisection steps that pin down any int32 key (a range of at most 2^32)
KEY_STEPS = 32


def order_key(x: jnp.ndarray) -> jnp.ndarray:
    """The order-preserving int32 key of f32 values: ``a < b`` iff
    ``key(a) < key(b)`` (with -0.0 below +0.0). Its own inverse, read
    back through :func:`from_key`."""
    b = lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def from_key(k: jnp.ndarray) -> jnp.ndarray:
    return lax.bitcast_convert_type(k ^ ((k >> 31) & 0x7FFFFFFF),
                                    jnp.float32)


def _zero(x: jnp.ndarray) -> jnp.ndarray:
    """-0.0 -> +0.0, so that the keys order values exactly as ``<`` does."""
    return jnp.where(x == 0, 0.0, x)


def _count(pred: jnp.ndarray) -> jnp.ndarray:
    """Per row: the number of true entries, (R, d) -> (R, 1) f32 (exact
    below 2^24; f32 because the TPU's vector reductions are float)."""
    return jnp.sum(jnp.where(pred, 1.0, 0.0), axis=-1, keepdims=True)


class Block(NamedTuple):
    """An unsorted (R, d) block of buckets, ready for the sort-free solve."""

    v: jnp.ndarray      # (R, d) f32; invalid slots +inf, -0.0 made +0.0
    k: jnp.ndarray      # (R, d) int32 order keys of ``v``


def solve_midpoint(blk: Block, bls, brs) -> list:
    """Solve Eq. (12) for b_k given neighbours (b_{k-1}, b_{k+1}) = (bl, br),
    for each pair of ``bls``/``brs`` ((R, 1) columns) at once.

    Discrete optimal condition:
        |{b_k <= v <= br}|  =  Σ_{bl<=v<=br} (v - bl) / (br - bl).

    The LHS is a decreasing step function of b_k over the bucket's values
    in ascending order, so the solution is the j-th smallest value, with
    j = round(#(v <= br) - RHS): the O(d) rule the paper cites. Nothing is
    sorted: the counts and the sum of the values in [bl, br] are masked
    reductions, and the j-th smallest value is found by bisection on the
    int32 order key, bounded by the keys of bl and br. The pairs share the
    bisection loop, one count per pair per step."""
    k = blk.k
    bls = [_zero(bl) for bl in bls]
    brs = [_zero(br) for br in brs]
    kls = [order_key(bl) for bl in bls]
    krs = [order_key(br) for br in brs]
    stats = []
    for bl, br, kl, kr in zip(bls, brs, kls, krs):
        idx_l = _count(k < kl)                   # #(v < bl)
        idx_r = _count(k <= kr)                  # #(v <= br)
        cnt_in = idx_r - idx_l                   # #values in [bl, br]
        sum_in = jnp.sum(jnp.where((k >= kl) & (k <= kr), blk.v, 0.0),
                         axis=-1, keepdims=True)
        width = br - bl
        safe_w = jnp.where(width > 0, width, 1.0)
        rhs = (sum_in - bl * cnt_in) / safe_w    # target count in [b_k, br]
        # count{v in [b, br]} = idx_r - j  where j = #(v < b)
        j = jnp.round(idx_r - rhs)               # half to even
        j = jnp.clip(j, idx_l, jnp.maximum(idx_r - 1.0, idx_l))
        stats.append((cnt_in, width, j + 1.0))

    def step(_, bounds):
        # the smallest key x with #(key <= x) >= j + 1: the j-th smallest
        out = []
        for (lo, hi), (_, _, want) in zip(bounds, stats):
            mid = (lo & hi) + ((lo ^ hi) >> 1)   # floor((lo + hi) / 2)
            ok = _count(k <= mid) >= want
            out.append((jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)))
        return tuple(out)

    bounds = lax.fori_loop(0, KEY_STEPS, step, tuple(zip(kls, krs)))
    out = []
    for (_, hi), (cnt_in, width, _), bl, br in zip(bounds, stats, bls, brs):
        b = from_key(hi)
        b = jnp.where(jnp.isfinite(b), b, 0.0)
        mid = 0.5 * (bl + br)
        # Degenerate interval (no data inside, or zero width): bisect.
        b = jnp.where((cnt_in > 0) & (width > 0), b, mid)
        out.append(jnp.clip(b, jnp.minimum(bl, br), jnp.maximum(bl, br)))
    return out


def orq_levels_block(
    v: jnp.ndarray, valid: jnp.ndarray, K: int, *, refine_iters: int = 0
) -> jnp.ndarray:
    """Algorithm 1 on one (R, d) block of buckets: (R, d) values + (R, d)
    bool validity -> (R, 2^K + 1) ascending levels. The one definition of
    the fit: :func:`orq_levels` runs it on the whole bucket array, the
    ``orq_fit`` Pallas kernel on each VMEM tile."""
    s = 2 ** K + 1
    v = v.astype(jnp.float32)
    vm = _zero(jnp.where(valid, v, jnp.inf))
    blk = Block(v=vm, k=order_key(vm))
    has = _count(valid) > 0
    lo = jnp.min(vm, axis=-1, keepdims=True)
    hi = jnp.max(jnp.where(valid, v, -jnp.inf), axis=-1, keepdims=True)
    lv = [None] * s
    # Corollary 1.1: the endpoints are the bucket's min and max
    lv[0] = jnp.where(has & jnp.isfinite(lo), lo, 0.0)
    lv[s - 1] = jnp.where(has & jnp.isfinite(hi), hi, 0.0)
    step = s - 1
    while step > 1:  # static recursion depth K; a depth's solves share a loop
        half = step // 2
        los = list(range(0, s - 1, step))
        bs = solve_midpoint(blk, [lv[a] for a in los],
                            [lv[a + step] for a in los])
        for a, b in zip(los, bs):
            lv[a + half] = b
        step = half
    for _ in range(refine_iters):
        for k in range(1, s - 1):
            (lv[k],) = solve_midpoint(blk, [lv[k - 1]], [lv[k + 1]])
    return jnp.concatenate(lv, axis=-1)


def orq_levels(
    bkt: jnp.ndarray,
    mask: jnp.ndarray,
    K: int,
    *,
    refine_iters: int = 0,
) -> jnp.ndarray:
    """Algorithm 1: greedy recursive level selection. Returns (nb, 2^K + 1).

    ``refine_iters`` > 0 adds coordinate-descent sweeps re-solving every
    interior level against its converged neighbours (beyond-paper refinement
    of the greedy recursion; see EXPERIMENTS.md §Perf for its effect).

    The jnp oracle of the ``orq_fit`` kernel: :func:`orq_levels_block` on
    the whole array, its rows padded to whole lane tiles as the kernel
    pads them.
    """
    assert K >= 1
    d = bkt.shape[-1]
    pad = -(-d // LANES) * LANES - d
    bkt = jnp.pad(bkt.astype(jnp.float32), ((0, 0), (0, pad)))
    mask = jnp.pad(mask.astype(bool), ((0, 0), (0, pad)))
    return orq_levels_block(bkt, mask, K, refine_iters=refine_iters)


def optimality_residual(
    bkt: jnp.ndarray, mask: jnp.ndarray, levels: jnp.ndarray
) -> jnp.ndarray:
    """Eq. (8) residual at each interior level, normalized. ~0 at optimum.

    residual_k = b_{k-1}·P[b_{k-1},b_k] + b_{k+1}·P[b_k,b_{k+1}]
                 − E[v; b_{k-1} <= v <= b_{k+1}]       (per unit mass)
    Used by tests and benchmarks to check Theorem 1 holds at the solver's
    output (up to the discreteness of the empirical distribution).
    """
    sb = sort_buckets(bkt, mask)
    s = levels.shape[-1]
    res = []
    for k in range(1, s - 1):
        bl, bk, br = levels[:, k - 1], levels[:, k], levels[:, k + 1]
        i_l = _count_lt(sb, bl)
        i_k = _count_lt(sb, bk)
        i_r = _count_le(sb, br)
        n_lo = (i_k - i_l).astype(jnp.float32)
        n_hi = (i_r - i_k).astype(jnp.float32)
        sum_in = _take(sb.psum, i_r) - _take(sb.psum, i_l)
        total = jnp.maximum(n_lo + n_hi, 1.0)
        r = (bl * n_lo + br * n_hi - sum_in) / total
        scale = jnp.maximum(jnp.abs(br - bl), 1e-12)
        res.append(r / scale)
    return jnp.stack(res, axis=-1)  # (nb, s-2)


# ---------------------------------------------------------------------------
# BinGrad (binary quantization, §3.2)
# ---------------------------------------------------------------------------

def bingrad_pb_b1(bkt: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Eq. (15): b₁ with  b₁·∫₀^∞ p  =  ∫_{b₁}^∞ v·p(v)dv,  solved on the
    empirical distribution by minimizing |LHS − RHS| over candidate data
    values (paper §3.2). Returns (nb,) positive scale; levels are ±b₁.
    """
    sb = sort_buckets(bkt, mask)
    n = sb.v.shape[-1]
    total = _take(sb.psum, sb.cnt)
    cnt_pos = (
        jnp.where(jnp.isfinite(sb.v), sb.v, -jnp.inf) > 0
    ).sum(axis=-1).astype(jnp.float32)
    # suffix sum from index j: S[cnt] - S[j]
    suffix = total[:, None] - sb.psum[:, :n]
    vpos = jnp.where(jnp.isfinite(sb.v) & (sb.v > 0), sb.v, jnp.nan)
    f = jnp.abs(vpos * cnt_pos[:, None] - suffix)
    f = jnp.where(jnp.isnan(f), jnp.inf, f)
    j = jnp.argmin(f, axis=-1)
    b1 = _take(sb.v, j)
    b1 = jnp.where(jnp.isfinite(b1) & (cnt_pos > 0), b1, 0.0)
    # all-nonpositive bucket: fall back to mean |v| scale
    absmean = jnp.where(
        sb.cnt > 0,
        jnp.abs(jnp.where(mask, bkt, 0.0)).sum(-1) / jnp.maximum(sb.cnt, 1),
        0.0,
    )
    return jnp.where(b1 > 0, b1, absmean)


def bingrad_b_levels(
    bkt: jnp.ndarray, mask: jnp.ndarray, *, lloyd_iters: int = 0
) -> jnp.ndarray:
    """Eq. (17): fully-biased binary levels. Returns (nb, 2) = (b₋₁, b₁).

    Paper default: b₀ = mean(G); b₋₁/b₁ = conditional means below/above b₀.
    ``lloyd_iters`` > 0 iterates b₀ ← (b₋₁+b₁)/2 (the exact Eq. 17 fixed
    point, i.e. 1-D 2-means) — beyond-paper refinement.
    """
    bkt = bkt.astype(jnp.float32)
    m = mask.astype(jnp.float32)
    cnt = jnp.maximum(m.sum(-1, keepdims=True), 1.0)
    b0 = (bkt * m).sum(-1, keepdims=True) / cnt

    def cond_means(b0):
        lo = m * (bkt < b0)
        hi = m * (bkt >= b0)
        cl = lo.sum(-1, keepdims=True)
        ch = hi.sum(-1, keepdims=True)
        bm = (bkt * lo).sum(-1, keepdims=True) / jnp.maximum(cl, 1.0)
        bp = (bkt * hi).sum(-1, keepdims=True) / jnp.maximum(ch, 1.0)
        # empty side: collapse to the other side's mean (degenerate bucket)
        bm = jnp.where(cl > 0, bm, bp)
        bp = jnp.where(ch > 0, bp, bm)
        return bm, bp

    bm, bp = cond_means(b0)
    for _ in range(lloyd_iters):
        b0 = 0.5 * (bm + bp)
        bm, bp = cond_means(b0)
    return jnp.concatenate([bm, bp], axis=-1)


# ---------------------------------------------------------------------------
# Baselines (§5 comparison set)
# ---------------------------------------------------------------------------

def terngrad_levels(bkt: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """TernGrad: {−max|v|, 0, +max|v|}. Returns (nb, 3)."""
    a = jnp.where(mask, jnp.abs(bkt.astype(jnp.float32)), 0.0)
    mx = a.max(axis=-1)
    return jnp.stack([-mx, jnp.zeros_like(mx), mx], axis=-1)


def qsgd_levels(
    bkt: jnp.ndarray, mask: jnp.ndarray, s: int, *, norm: str = "linf"
) -> jnp.ndarray:
    """QSGD-s: s levels evenly spaced over ±‖G‖ per bucket. Returns (nb, s)."""
    b = bkt.astype(jnp.float32)
    if norm == "linf":
        r = jnp.where(mask, jnp.abs(b), 0.0).max(axis=-1)
    elif norm == "l2":
        r = jnp.sqrt(jnp.where(mask, b * b, 0.0).sum(axis=-1))
    else:
        raise ValueError(f"unknown norm {norm!r}")
    ticks = jnp.linspace(-1.0, 1.0, s, dtype=jnp.float32)
    return r[:, None] * ticks[None, :]


def linear_levels(bkt: jnp.ndarray, mask: jnp.ndarray, s: int) -> jnp.ndarray:
    """Linear-s: levels linearly dividing the empirical CDF (quantiles)."""
    sb = sort_buckets(bkt, mask)
    q = jnp.linspace(0.0, 1.0, s, dtype=jnp.float32)
    idx = jnp.round(q[None, :] * (sb.cnt[:, None] - 1).astype(jnp.float32))
    idx = jnp.clip(idx.astype(jnp.int32), 0, sb.v.shape[-1] - 1)
    lv = jnp.take_along_axis(sb.v, idx, axis=-1)
    lv = jnp.where(jnp.isfinite(lv), lv, 0.0)
    # enforce ascending (ties collapse fine for rounding)
    return jnp.where(sb.cnt[:, None] > 0, lv, jnp.zeros_like(lv))


def signsgd_scale(bkt: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Scaled SignSGD (Eq. 13): ±‖G‖₁/dim. Returns (nb, 2) = (−m, m)."""
    a = jnp.where(mask, jnp.abs(bkt.astype(jnp.float32)), 0.0)
    cnt = jnp.maximum(mask.sum(-1).astype(jnp.float32), 1.0)
    mmean = a.sum(-1) / cnt
    return jnp.stack([-mmean, mmean], axis=-1)


def minmax_levels(bkt: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Unbiased binary endpoints {min, max} (Corollary 1.1 for s=2) — the
    outlier-fragile scheme BinGrad-pb improves on. Returns (nb, 2)."""
    sb = sort_buckets(bkt, mask)
    return jnp.stack([_bucket_min(sb), _bucket_max(sb)], axis=-1)
