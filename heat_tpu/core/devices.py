"""Device abstraction for heat_tpu.

Reference: heat/core/devices.py:9-135 — there, a ``Device`` names a torch
device per MPI process, with GPUs assigned round-robin by rank
(devices.py:66-74).  Here a :class:`Device` names a **JAX platform** whose
entire device set forms the mesh; placement of individual shards is XLA's
job, so there is no per-rank device arithmetic.  ``ht.cpu`` always exists,
``ht.tpu`` exists when TPU hardware (or an emulated TPU platform) is
present, and ``ht.gpu`` when CUDA/ROCm devices are visible.
"""

from __future__ import annotations

from typing import Optional, Union

import jax

__all__ = ["Device", "cpu", "get_device", "sanitize_device", "use_device"]


class Device:
    """A logical compute platform binding arrays to a device mesh.

    Parameters
    ----------
    device_type : str
        Platform name understood by JAX: ``'cpu'``, ``'tpu'``, ``'gpu'``.

    Reference: heat/core/devices.py:9-56 (``Device`` with device_type/
    device_id/torch_device); the id is dropped because a single controller
    addresses every device of the platform through the mesh.
    """

    def __init__(self, device_type: str):
        self.__device_type = str(device_type).strip().lower()

    @property
    def device_type(self) -> str:
        return self.__device_type

    @property
    def platform(self) -> str:
        """JAX platform name (alias of :attr:`device_type`)."""
        return self.__device_type

    def jax_devices(self):
        """All JAX devices of this platform (the mesh population)."""
        return jax.devices(self.__device_type)

    def __str__(self) -> str:
        return self.__device_type

    def __repr__(self) -> str:
        return f"device({self.__device_type})"

    def __eq__(self, other) -> bool:
        if isinstance(other, Device):
            return self.device_type == other.device_type
        if isinstance(other, str):
            return self.device_type == other.strip().lower()
        return NotImplemented

    def __hash__(self) -> int:
        return hash(self.device_type)


# ---------------------------------------------------------------------- #
# platform singletons (reference devices.py:59-74)                        #
# ---------------------------------------------------------------------- #
cpu = Device("cpu")
"""The CPU device — always available (reference devices.py:59)."""

__registry = {"cpu": cpu}

# name -> Device | None, filled on first access.  Probing calls
# jax.devices(), which initializes the XLA backend — deferring it keeps
# `import heat_tpu` backend-free so jax.distributed / init_multihost can
# run first (jax requires distributed init before any backend touch).
_probe_cache: dict = {}


def __probe_platform(name: str) -> Optional[Device]:
    try:
        if jax.devices(name):
            dev = Device(name)
            __registry[name] = dev
            return dev
    except RuntimeError:
        pass
    return None


def _accelerator(name: str) -> Optional[Device]:
    """The 'tpu'/'gpu' singleton, probed lazily (None when absent)."""
    if name not in _probe_cache:
        _probe_cache[name] = __probe_platform(name)
    return _probe_cache[name]


def __getattr__(name: str):
    """PEP 562: ``devices.tpu`` / ``devices.gpu`` are probed on first
    access, mirroring the reference's conditional ``gpu`` singleton
    (devices.py:66-74) without touching the backend at import time.

    Trade-off: star-imports (``from heat_tpu import *``) do not consult
    this hook, so they bind only ``cpu``; use attribute access
    (``ht.tpu``) for accelerators — the lazy probe is what keeps
    ``import heat_tpu`` backend-free for :func:`ht.init_multihost`."""
    if name in ("tpu", "gpu"):
        return _accelerator(name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__default_device: Device = None


def _accelerator_or_cpu() -> Device:
    for name in ("tpu", "gpu"):
        dev = _accelerator(name)
        if dev is not None:
            return dev
    return cpu


def get_device() -> Device:
    """The process-global default device (reference devices.py:80-89).
    Defaults to the best available platform: tpu > gpu > cpu."""
    global __default_device
    if __default_device is None:
        __default_device = _accelerator_or_cpu()
    return __default_device


def use_device(device: Optional[Union[str, Device]] = None) -> None:
    """Set the process-global default device (reference devices.py:124-135)."""
    global __default_device
    __default_device = sanitize_device(device) if device is not None else _accelerator_or_cpu()


def sanitize_device(device: Optional[Union[str, Device]]) -> Device:
    """Normalize a device argument, substituting the default for None
    (reference devices.py:92-121)."""
    if device is None:
        return get_device()
    if isinstance(device, Device):
        return device
    name = str(device).strip().lower()
    if name in __registry:
        return __registry[name]
    # tpu/gpu go through the lazy singleton's probe cache
    dev = _accelerator(name) if name in ("tpu", "gpu") else __probe_platform(name)
    if dev is not None:
        return dev
    raise ValueError(f"Unknown device or platform not available: {device!r}")
