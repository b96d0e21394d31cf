"""Core utility conformance tests: stride_tricks, sanitation, constants,
devices, memory (reference: heat/core/tests/test_{stride_tricks,constants,
devices,sanitation,memory}.py scenarios)."""

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import stride_tricks


def test_broadcast_shape():
    # reference test_stride_tricks.py:6-23
    assert stride_tricks.broadcast_shape((5, 4), (4,)) == (5, 4)
    assert stride_tricks.broadcast_shape((1, 100, 1), (10, 1, 5)) == (10, 100, 5)
    assert stride_tricks.broadcast_shape((8, 1, 6, 1), (7, 1, 5)) == (8, 7, 6, 5)
    for bad in [((5, 4), (5,)), ((5, 4), (2, 3)), ((5, 2), (5, 2, 3)), ((2, 1), (8, 4, 3))]:
        with pytest.raises(ValueError):
            stride_tricks.broadcast_shape(*bad)


def test_sanitize_axis():
    # reference test_stride_tricks.py:25-47
    assert stride_tricks.sanitize_axis((5, 4, 4), 1) == 1
    assert stride_tricks.sanitize_axis((5, 4, 4), -1) == 2
    assert stride_tricks.sanitize_axis((5, 4, 4), 2) == 2
    assert stride_tricks.sanitize_axis((5, 4, 4), (0, 1)) == (0, 1)
    assert stride_tricks.sanitize_axis((5, 4, 4), (-2, -3)) == (1, 0)
    assert stride_tricks.sanitize_axis((5, 4), 0) == 0
    assert stride_tricks.sanitize_axis((5, 4), None) is None
    assert stride_tricks.sanitize_axis(tuple(), 0) is None
    with pytest.raises(TypeError):
        stride_tricks.sanitize_axis((5, 4), 1.0)
    with pytest.raises(TypeError):
        stride_tricks.sanitize_axis((5, 4), "axis")
    with pytest.raises(ValueError):
        stride_tricks.sanitize_axis((5, 4), 2)
    with pytest.raises(ValueError):
        stride_tricks.sanitize_axis((5, 4), -3)
    with pytest.raises(ValueError):
        stride_tricks.sanitize_axis((5, 4, 4), (-4, 1))


def test_sanitize_shape():
    # reference test_stride_tricks.py:49-66
    assert stride_tricks.sanitize_shape(1) == (1,)
    assert stride_tricks.sanitize_shape([1, 2]) == (1, 2)
    assert stride_tricks.sanitize_shape((1, 2)) == (1, 2)
    with pytest.raises(ValueError):
        stride_tricks.sanitize_shape(-1)
    with pytest.raises(ValueError):
        stride_tricks.sanitize_shape((2, -1))
    with pytest.raises(TypeError):
        stride_tricks.sanitize_shape("shape")
    with pytest.raises(TypeError):
        stride_tricks.sanitize_shape(1.0)
    with pytest.raises(TypeError):
        stride_tricks.sanitize_shape((1, 1.0))


def test_sanitize_slice():
    # reference test_stride_tricks.py:68-79
    s = stride_tricks.sanitize_slice(slice(None, None, None), 100)
    assert (s.start, s.stop, s.step) == (0, 100, 1)
    s = stride_tricks.sanitize_slice(slice(-50, -5, 2), 100)
    assert (s.start, s.stop, s.step) == (50, 95, 2)


def test_constants():
    # reference test_constants.py
    assert float("inf") == ht.Inf
    assert ht.inf == np.inf
    assert np.isnan(ht.nan)
    assert 3 < ht.inf
    assert np.isinf(ht.inf)
    assert ht.pi == np.pi
    assert ht.e == np.e


def test_devices_sanitize():
    # reference test_devices.py (cpu paths; 'fpu' and non-str inputs raise)
    dev = ht.get_device()
    assert ht.sanitize_device(None) is dev
    assert ht.sanitize_device(dev) is dev
    name = dev.device_type
    assert ht.sanitize_device(name) is dev
    assert ht.sanitize_device(f"  {name.upper()}  ") is dev
    with pytest.raises(ValueError):
        ht.sanitize_device("fpu")
    with pytest.raises(ValueError):
        ht.sanitize_device(1)


def test_use_device_roundtrip():
    dev = ht.get_device()
    ht.use_device(dev)
    assert ht.get_device() is dev


def test_memory_copy():
    # reference test_memory.py: copy() is deep w.r.t. subsequent mutation
    a = ht.ones((4, 4), split=0)
    b = ht.copy(a)
    assert b is not a
    np.testing.assert_array_equal(a.numpy(), b.numpy())
    with pytest.raises(TypeError):
        ht.copy("not an array")


def test_sanitize_memory_layout():
    from heat_tpu.core.memory import sanitize_memory_layout

    sanitize_memory_layout(None, "C")
    with pytest.raises(ValueError):
        sanitize_memory_layout(None, "K")


def test_constants_uppercase_aliases():
    # reference constants.py:6-16 module-level names
    from heat_tpu.core import constants

    assert constants.PI == np.pi
    assert constants.E == np.e
    assert constants.INF == float("inf")
    assert constants.NINF == -float("inf")
    assert np.isnan(constants.NAN)


def test_conditional_accelerator_singletons():
    """ht.tpu / ht.gpu are exported only when the platform exists, like the
    reference's conditional gpu singleton (reference devices.py:66-74).
    Tests run on the cpu platform, so neither may be exported."""
    from heat_tpu.core import devices

    assert devices.cpu is not None
    if devices.tpu is None:
        assert not hasattr(ht, "tpu")
    else:
        assert ht.tpu is devices.tpu
    if devices.gpu is None:
        assert not hasattr(ht, "gpu")
    else:
        assert ht.gpu is devices.gpu
