"""Test configuration: run the whole suite on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): the reference executes
one unittest suite under mpirun -np {1,2,4,7}; here the same effect comes
from XLA host-platform device multiplication — every test sees an 8-device
mesh, and split/replicated paths exercise real (CPU-emulated) collectives.
Set HEAT_TEST_DEVICES to change the mesh size (e.g. 1 or 7 for the
uneven-chunk edge cases the reference probes with -np 7).

The device count is set twice on purpose: ``jax_num_cpu_devices`` for this
process, and ``--xla_force_host_platform_device_count`` in ``XLA_FLAGS`` for
the children tests spawn (procfleet replicas inherit the environment and must
see the same emulated mesh their ``.aotx`` bundles were compiled for).

The persistent compilation cache stays off, here and in every child (the
entry points would otherwise place it in the checkout): the suite's compiles
are CPU programs nobody runs again.
"""

import os

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

_DEVICES = int(os.environ.get("HEAT_TEST_DEVICES", "8"))
_FLAG = f"--xla_force_host_platform_device_count={_DEVICES}"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

import jax  # noqa: E402  (after the XLA_FLAGS setup above, by design)

# must run before any jax computation
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", _DEVICES)
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture
def one_tpu(monkeypatch):
    """What the kernels' route predicates (``_symv.conforms``,
    ``_colvar.conforms``) ask of the process, answered as a one-chip machine
    would: a TPU backend with one device."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
