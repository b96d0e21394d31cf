"""heat_tpu — a TPU-native distributed tensor and data-analytics framework.

A ground-up rebuild of the capabilities of HeAT (the Helmholtz Analytics
Toolkit, reference mounted at /root/reference) designed for TPU: global
jax.Arrays sharded over a device mesh replace per-process torch tensors,
XLA collectives over ICI/DCN replace MPI, and GSPMD replaces hand-written
SPMD communication.  See SURVEY.md for the full architectural mapping.

The flat ``ht.*`` namespace mirrors the reference (heat/__init__.py:1-12).
"""

import time as _time

# the start-up record (``telemetry.startup()``): the clock after each import
# statement below, handed over once at the end of this file
_T0 = _time.monotonic()
_STAGES = []


def _done(what, _now=_time.monotonic, _add=_STAGES.append):
    _add((what, _now()))


import os as _os

# float64/int64 support requires x64 mode; heat's API exposes 64-bit dtypes,
# so enable it before any jax arrays exist.  Defaults everywhere remain
# 32-bit (TPU-friendly); set HEAT_TPU_DISABLE_X64=1 to hard-disable.
# The flip touches no backend: the import must stay backend-free so
# jax.distributed.initialize()/ht.init_multihost() can run after it.
if _os.environ.get("HEAT_TPU_DISABLE_X64", "0") != "1":
    import jax as _jax

    _done("jax")
    _jax.config.update("jax_enable_x64", True)

from .version import __version__
_done("version")
from . import core
_done("core")
from .core import *
_done("core")
from .core import linalg, random
_done("core")
from . import comm
_done("comm")
from . import cluster
_done("cluster")
from . import classification
_done("classification")
from . import parallel
_done("parallel")
from . import graph
_done("graph")
from . import naive_bayes
_done("naive_bayes")
from . import regression
_done("regression")
from . import resilience
_done("resilience")

# ht.io is the io PACKAGE (flat loaders re-exported + the streaming path).
# `from .core import *` above bound the name to the flat core.io module, so
# a `from . import io` would be a no-op (the attribute already exists);
# the absolute import forces the submodule load, which rebinds `io` here.
import heat_tpu.io  # noqa: F401
_done("io")
from . import spatial
_done("spatial")
from . import telemetry
_done("telemetry")
from . import obs
_done("obs")
from . import utils
_done("utils")
from . import datasets
_done("datasets")
from . import serve
_done("serve")

telemetry._core.record_imports(_T0, _STAGES, {"core": core._STAGES})
del _T0, _STAGES, _done


def __getattr__(name):
    """Lazy accelerator singletons: ``ht.tpu`` / ``ht.gpu`` exist iff the
    platform does (reference's conditional gpu, devices.py:66-74), probed
    on first access so importing heat_tpu never initializes a backend."""
    if name in ("tpu", "gpu"):
        dev = core.devices._accelerator(name)
        if dev is not None:
            return dev
    raise AttributeError(f"module 'heat_tpu' has no attribute {name!r}")
