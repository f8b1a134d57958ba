"""utils.device: interpret mode only where the CPU was asked for by name,
and a stated device (or a refusal) for every child process."""

import pytest

from hivemall_tpu.utils import device
from hivemall_tpu.utils.device import DevicePolicyError


def test_interpret_allowed_because_conftest_names_the_cpu():
    assert device.cpu_requested() and device.pallas_interpret() is True


def test_a_cpu_backend_nobody_asked_for_raises(monkeypatch):
    # what a chip machine whose TPU failed to initialise would look like
    monkeypatch.setattr(device, "cpu_requested", lambda: False)
    with pytest.raises(DevicePolicyError, match="need a TPU"):
        device.pallas_interpret()
    from hivemall_tpu.ops.pallas_hist import use_pallas_default
    with pytest.raises(DevicePolicyError):
        use_pallas_default()             # no slide to the XLA reference


def test_children_inherit_the_named_cpu():
    assert device.child_device_envs(3) == [{"JAX_PLATFORMS": "cpu"}] * 3


def test_children_get_one_chip_each_or_a_refusal(monkeypatch):
    monkeypatch.setattr(device, "cpu_requested", lambda: False)
    monkeypatch.setattr(device, "holds_accelerator", lambda: False)
    monkeypatch.setattr(device, "visible_chip_count", lambda: 2)
    envs = device.child_device_envs(2)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
    assert all(device.names_device(e) for e in envs)
    assert len({e["TPU_MESH_CONTROLLER_PORT"] for e in envs}) == 2
    with pytest.raises(DevicePolicyError, match="3 chips"):
        device.child_device_envs(3)
    monkeypatch.setattr(device, "holds_accelerator", lambda: True)
    with pytest.raises(DevicePolicyError, match="holds the chip"):
        device.child_device_envs(1)


def test_bulk_kernel_pool_refused_off_the_cpu(monkeypatch, tmp_path):
    from hivemall_tpu.io import bulk
    monkeypatch.setattr(device, "cpu_requested", lambda: False)
    monkeypatch.setattr(bulk, "resolve_model_bundle",
                        lambda *a, **k: ("b.npz", "explicit"))
    with pytest.raises(DevicePolicyError, match="--workers 1"):
        bulk.bulk_predict("train_classifier", str(tmp_path / "in.libsvm"),
                          backend="kernel", workers=2)
