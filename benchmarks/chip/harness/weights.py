"""Weights and keys made from ``--seed``, by the benchmark itself.

Every leaf, and every layer of a stacked leaf, is drawn from its own key
(seed, leaf path, layer), so the reference can make any one layer again
without the rest and without anything the program made. The values follow
the usual scheme of the program's model family: normal / sqrt(fan-in)
matrices, 0.02-scaled embeddings, zero norm offsets and biases, and the
RWKV-6 constants (token-shift 0.5, decay base -2, LoRA matrices scaled by
0.1).
"""
from __future__ import annotations

import zlib
from typing import Any, Callable

import jax
import jax.numpy as jnp

ZEROS = ("scale", "final_norm", "q_norm", "k_norm", "bq", "bk", "bv",
         "bias", "bi", "bo", "conv_b")
CONSTANT = {"tm_mu": 0.5, "cm_mu_k": 0.5, "cm_mu_r": 0.5, "w0": -2.0,
            "ln_x": 1.0}
LORA = ("tm_w1", "tm_w2", "dec_w1", "dec_w2")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number, 64 bits and more
    included (the low 31 bits seed it, the rest fold in)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    k = jax.random.key(seed & 0x7FFFFFFF)
    rest = seed >> 31
    while rest:
        k = jax.random.fold_in(k, rest & 0x7FFFFFFF)
        rest >>= 31
    return k


def fold(key: jax.Array, *parts) -> jax.Array:
    for p in parts:
        if isinstance(p, str):
            p = zlib.crc32(p.encode()) & 0x7FFFFFFF
        key = jax.random.fold_in(key, p)
    return key


def leaf_name(path: str) -> str:
    return path.rstrip("]'").rsplit("'", 1)[-1]


def make_leaf(key: jax.Array, path: str, shape, dtype) -> jax.Array:
    """One layer's (or one unstacked) leaf of ``shape``."""
    name = leaf_name(path)
    if name in ZEROS:
        return jnp.zeros(shape, dtype)
    if name in CONSTANT:
        return jnp.full(shape, CONSTANT[name], dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        x = x * 0.02
    elif len(shape) >= 2:
        x = x / jnp.sqrt(jnp.float32(shape[-2]))
        if name in LORA:
            x = x * 0.1
    return x.astype(dtype)


def stacked(path: str) -> bool:
    """Leaves under ``groups`` carry a leading layer axis."""
    return path.startswith("['groups']")


def layer_leaf(key: jax.Array, path: str, shape, dtype, layer: int):
    """Layer ``layer`` of the stacked leaf at ``path`` (``shape`` without
    the layer axis)."""
    return make_leaf(fold(key, path, layer), path, shape, dtype)


def make_params(abstract, dtype) -> Callable[[jax.Array], Any]:
    """A function (to jit) that makes the whole tree ``abstract`` in
    ``dtype`` (integer leaves keep theirs) from a key (``seed_key``), one
    program for every seed."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(abstract)

    def build(key):
        leaves = []
        for p, a in flat:
            path = jax.tree_util.keystr(p)
            dt = dtype if jnp.issubdtype(a.dtype, jnp.floating) else a.dtype
            if stacked(path):
                leaves.append(jnp.stack([
                    layer_leaf(key, path, a.shape[1:], dt, i)
                    for i in range(a.shape[0])]))
            else:
                leaves.append(make_leaf(fold(key, path), path, a.shape, dt))
        return jax.tree_util.tree_unflatten(tdef, leaves)

    return build
