"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state. The production target is TPU v5e, 256 chips per
pod as a (16, 16) (data, model) mesh; multi-pod adds a leading 2-way "pod"
axis (2 x 256 = 512 chips).
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # per chip, FLOP/s
HBM_BW = 819e9                  # per chip, B/s
ICI_BW = 50e9                   # per link, B/s (fast intra-pod)
DCN_BW = 12.5e9                 # per host, B/s (100 Gbps inter-pod NIC —
                                # the slow link the two-level hierarchical
                                # exchange reserves quantization for)


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis AUTO. Since jax 0.9 the axes
    default to Explicit, under which the model's ``shard`` hints (a
    ``with_sharding_constraint`` over ``model``) are refused inside the
    dp-manual train step."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _positive_int(name: str, value) -> int:
    if not isinstance(value, int) or isinstance(value, bool) or value < 1:
        raise ValueError(
            f"{name} must be a positive integer, got {value!r}")
    return value


def make_host_mesh(data: int | None = None, model: int = 1, *,
                   pods: int = 1, devices: int | None = None):
    """Mesh over the actually-available devices (for real runs/tests).

    ``devices`` takes the first that many visible devices instead of all
    of them (a one-chip run on a four-chip host). ``pods > 1`` adds a
    leading "pod" axis — the multi-pod topology the two-level
    hierarchical exchange splits into (inter=pod, intra=data). Every
    factor is validated up front so a bad launch dies with a clear
    message here instead of a downstream XLA shape failure.
    """
    visible = jax.devices()
    n = len(visible)
    if devices is not None:
        n = _positive_int("devices", devices)
        if n > len(visible):
            raise ValueError(
                f"devices={n} but only {len(visible)} are visible")
    model = _positive_int("model", model)
    pods = _positive_int("pods", pods)
    if n % (model * pods):
        raise ValueError(
            f"model*pods={model}*{pods} does not divide the device count "
            f"{n}; pick factors of {n}")
    if data is None:
        data = n // (model * pods)
    data = _positive_int("data", data)
    if pods * data * model != n:
        raise ValueError(
            f"mesh shape pods*data*model = {pods}*{data}*{model} = "
            f"{pods * data * model} must equal the device count {n}")
    if pods > 1:
        return _auto_mesh((pods, data, model), ("pod", "data", "model"),
                          visible[:n])
    return _auto_mesh((data, model), ("data", "model"), visible[:n])


def device_label() -> str:
    """What this process runs on, as JAX reports it: platform, device
    kind and count (e.g. ``tpu TPU v5 lite x1``)."""
    devs = jax.devices()
    return f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}"
