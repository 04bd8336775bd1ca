"""make_host_mesh validation (PR satellite) + the shared dp-axis helper.

The old ``data or n // model`` truthiness silently rewrote an explicit
``data=0``; an indivisible ``model`` surfaced as a downstream XLA shape
error. Both must now die here with a clear message. Runs on the default
single-device test view (every error path is reachable with n=1).
"""
import jax
import pytest

from repro.launch.mesh import make_host_mesh
from repro.utils.sharding import dp_axis_names

jax.config.update("jax_platform_name", "cpu")


class TestMakeHostMeshValidation:
    def test_default_ok(self):
        mesh = make_host_mesh()
        assert mesh.axis_names == ("data", "model")
        assert mesh.devices.size == len(jax.devices())

    def test_model_not_dividing_devices(self):
        n = len(jax.devices())
        with pytest.raises(ValueError, match="does not divide"):
            make_host_mesh(model=n + 1)

    def test_explicit_zero_data_rejected(self):
        # the old truthiness fallback silently replaced data=0
        with pytest.raises(ValueError, match="data must be a positive"):
            make_host_mesh(data=0)

    def test_bad_types_rejected(self):
        with pytest.raises(ValueError, match="model must be a positive"):
            make_host_mesh(model=0)
        with pytest.raises(ValueError, match="model must be a positive"):
            make_host_mesh(model=-2)
        with pytest.raises(ValueError, match="pods must be a positive"):
            make_host_mesh(pods=0)
        with pytest.raises(ValueError, match="data must be a positive"):
            make_host_mesh(data=2.0)  # type: ignore[arg-type]

    def test_product_mismatch(self):
        n = len(jax.devices())
        with pytest.raises(ValueError, match="must equal the device count"):
            make_host_mesh(data=n + 3)

    def test_pods_not_dividing(self):
        n = len(jax.devices())
        with pytest.raises(ValueError, match="does not divide"):
            make_host_mesh(pods=n + 1)


class TestMakeHostMeshDevices:
    """``devices=`` takes a prefix of the visible devices (a one-chip run
    on a four-chip host), and every axis is Auto so the model's sharding
    hints apply inside the train step."""

    def test_prefix(self):
        mesh = make_host_mesh(devices=1)
        assert mesh.devices.size == 1
        assert mesh.devices.flat[0] == jax.devices()[0]

    def test_more_than_visible_rejected(self):
        n = len(jax.devices())
        with pytest.raises(ValueError, match="visible"):
            make_host_mesh(devices=n + 1)
        with pytest.raises(ValueError, match="devices must be a positive"):
            make_host_mesh(devices=0)

    def test_factors_checked_against_the_prefix(self):
        with pytest.raises(ValueError, match="must equal the device count"):
            make_host_mesh(data=2, devices=1)

    def test_axes_are_auto(self):
        for mesh in (make_host_mesh(), make_host_mesh(pods=1, devices=1)):
            assert all(t == jax.sharding.AxisType.Auto
                       for t in mesh.axis_types)


class TestDpAxisNames:
    """The deduped dp-axis selection (utils/sharding.dp_axis_names): the
    single source the train step, dryrun, and the hierarchy split share."""

    def test_orders_pod_before_data(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        assert dp_axis_names(mesh) == ("data",)
        mesh3 = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
        assert dp_axis_names(mesh3) == ("pod", "data")

    def test_no_dp_axes(self):
        mesh = jax.make_mesh((1,), ("model",))
        assert dp_axis_names(mesh) == ()

    def test_train_step_uses_it(self):
        from repro.train.step import _dp_axes
        mesh3 = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
        assert _dp_axes(mesh3) == dp_axis_names(mesh3)
