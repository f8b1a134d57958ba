"""Test config: run JAX on CPU with 8 virtual devices.

Mirrors the reference's "distributed without a cluster" trick (SURVEY.md §5
item 3 — in-process localhost MixServer): mix/psum semantics are exercised on
an 8-device virtual CPU mesh, no TPU pod needed. Must run before jax imports.
"""

import os

import pytest

# CPU by name + 8 virtual devices, set before jax is imported: the suite
# checks arithmetic and control flow on a virtual mesh, and naming the CPU
# is what lets Pallas kernels run in interpret mode (utils/device.py); the
# chip is exercised by chip_smoke.py, never by pytest.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long soak variants (fault-injection soaks etc.); excluded "
        "from the tier-1 `-m 'not slow'` run")


@pytest.fixture
def tracer():
    """The process tracer, empty and on for one test."""
    from hivemall_tpu.obs.trace import get_tracer
    t = get_tracer()
    t.reset()
    t.enable()
    yield t
    t.disable()
    t.reset()


def tracer_spans(tracer, name=None):
    """The tracer's completed spans as Chrome events, all or one name's."""
    evs = [e for e in tracer.chrome_dict()["traceEvents"] if e["ph"] == "X"]
    return [e for e in evs if name is None or e["name"] == name]


def assert_batches_equal(a, b):
    """``a`` == ``b`` over EVERY dataclass field — tree structure and
    values. Introspects dataclasses.fields so staging/prep paths can never
    silently drop metadata the batch dataclass grows later. Handles host
    and device (staged) arrays alike, ``None`` fields included."""
    import dataclasses

    import numpy as np
    assert type(a) is type(b)
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if x is None or y is None:
            assert x is None and y is None, f.name
        elif isinstance(x, np.ndarray) or hasattr(x, "shape"):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f.name)
        else:
            assert x == y, f.name
