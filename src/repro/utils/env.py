"""The central (and only) ``os.environ`` accessor for ``src/repro``.

Every runtime flag the package reads from the environment resolves
through here — the ``env-read`` lint rule in ``repro.analysis`` forbids
``os.environ``/``os.getenv`` anywhere else under ``src/repro``, so flag
semantics (accepted spellings, validation errors, trace-time resolution)
can't fork per call site.

Flags:

``REPRO_PALLAS_INTERPRET``
    Overrides the backend autodetection for Pallas interpret mode in
    either direction (default: interpret everywhere except on a real TPU
    backend). ``1``/``true``/``yes``/``on`` forces interpret mode — e.g.
    to debug kernel numerics ON a TPU — and ``0``/``false``/``no``/``off``
    forces compiled kernels.

``REPRO_USE_KERNELS``
    ``0`` forces the pure-jnp reference oracle for EVERY op regardless
    of the caller's ``use_kernels`` flag — the CI matrix runs the whole
    tier-1 suite this way to enforce kernel/ref parity. ``1``/unset
    keeps the caller's flag (kernels by default).

``JAX_COMPILATION_CACHE_DIR``
    Where the entry points keep JAX's persistent compilation cache
    (:func:`use_compile_cache`); unset, a fixed directory in the checkout.

No jax import at module scope: :func:`force_host_device_count` must be
callable BEFORE jax first initializes (device counts lock on first use).
"""
from __future__ import annotations

import os
from typing import Optional

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def env_flag(name: str, *, context: str = "") -> Optional[bool]:
    """Validated tri-state boolean env flag: True / False / None (unset).

    Any other spelling raises — a typo'd flag silently falling back to a
    default is how parity legs end up not testing what they claim."""
    env = os.environ.get(name, "").strip().lower()
    if env in _TRUE:
        return True
    if env in _FALSE:
        return False
    if env:
        raise ValueError(
            f"{name}={env!r}: expected one of {_TRUE + _FALSE} "
            f"(or unset{' ' + context if context else ''})")
    return None


def pallas_interpret() -> bool:
    """``REPRO_PALLAS_INTERPRET``, defaulting to backend autodetection
    (interpret everywhere except a real TPU). Resolved at trace time."""
    flag = env_flag("REPRO_PALLAS_INTERPRET",
                    context="for backend autodetection")
    if flag is not None:
        return flag
    import jax  # deferred: keep this module importable pre-jax-init
    return jax.default_backend() != "tpu"


def kernels_enabled() -> bool:
    """``REPRO_USE_KERNELS``: ``0`` forces the pure-jnp reference oracle
    everywhere (the CI parity matrix leg); ``1``/unset keeps each
    caller's ``use_kernels`` flag."""
    flag = env_flag("REPRO_USE_KERNELS",
                    context="to keep the caller's flag")
    return True if flag is None else flag


#: the compile cache's fixed home when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: inside the checkout (listed in ``.gitignore``), never a temp
#: name — the path is part of the cache key, so a moving one never hits
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. ``JAX_COMPILATION_CACHE_DIR``, where set, is
    read by JAX itself and no other path is set here; otherwise the cache
    lives at :data:`DEFAULT_COMPILE_CACHE_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if path:
        return path
    import jax  # deferred: keep this module importable pre-jax-init
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


def force_host_device_count(n: int, *, platform: str = "cpu") -> None:
    """Expose ``n`` fake host devices (and default to ``platform``).

    Must run before jax first initializes — jax locks the device count
    on first use. Prepends to any caller-provided ``XLA_FLAGS`` so an
    explicit outer setting still wins."""
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={int(n)} "
        + os.environ.get("XLA_FLAGS", ""))
    if platform:
        os.environ.setdefault("JAX_PLATFORMS", platform)
