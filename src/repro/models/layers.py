"""Shared neural building blocks (pure JAX, no framework deps)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# sharding helper: constraint only when the axis exists in the current mesh
# ---------------------------------------------------------------------------

def shard(x: jnp.ndarray, *spec):
    """with_sharding_constraint that degrades to a no-op off-mesh.

    ``spec`` entries are axis names (or None / tuples). Axes absent from the
    ambient abstract mesh are dropped, so the same model code runs in smoke
    tests (1 device, no mesh), under jit+NamedSharding, and inside shard_map
    bodies with auto axes.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    # inside shard_map, manual axes cannot appear in constraints
    usable = set(mesh.axis_names) - set(mesh.manual_axes)
    sizes = dict(mesh.shape)

    def _axes_size(entry) -> int:
        if isinstance(entry, (tuple, list)):
            n = 1
            for a in entry:
                n *= sizes.get(a, 1)
            return n
        return sizes.get(entry, 1)

    def _filter(entry, dim_size):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in usable)
            entry = kept if kept else None
        elif entry not in usable:
            entry = None
        if entry is None:
            return None
        # dimension must divide evenly across the axis (e.g. whisper's 8
        # heads cannot shard over a 16-way model axis)
        if dim_size % _axes_size(entry) != 0:
            return None
        return entry

    spec = list(spec)
    if len(spec) < x.ndim:  # left-pad: spec aligns to trailing dims
        spec = [None] * (x.ndim - len(spec)) + spec
    filtered = [_filter(e, d) for e, d in zip(spec, x.shape)]
    if all(f is None for f in filtered):
        return x
    return jax.lax.with_sharding_constraint(x, P(*filtered))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6,
             *, offset: float = 1.0) -> jnp.ndarray:
    """RMSNorm with (1 + scale) parameterization (gemma/llama style)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (offset + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dt)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    """(head_dim/2,) inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 1e4) -> jnp.ndarray:
    """x (..., S, H, hd), positions (..., S) -> rotated x (split halves)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                    # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[name]


def gated_mlp(p, x, *, act: str = "silu"):
    """p: {wi_gate (D,F), wi_up (D,F), wo (F,D)}; x (..., D)."""
    g = x @ p["wi_gate"]
    u = x @ p["wi_up"]
    g = shard(g, None, None, "model")
    h = _act(act)(g) * u
    return h @ p["wo"]


def dense_mlp(p, x, *, act: str = "gelu"):
    """p: {wi (D,F), bi (F,), wo (F,D), bo (D,)} (whisper-style)."""
    h = _act(act)(x @ p["wi"] + p["bi"])
    h = shard(h, None, None, "model")
    return h @ p["wo"] + p["bo"]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    return (jax.random.normal(key, shape) / jnp.sqrt(fan_in)).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)
