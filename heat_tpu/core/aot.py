"""Ahead-of-time executable export/install for fused programs.

The zero-cold-start half of fleet serving (docs/design.md §22): a warm
serving process captures its compiled ``ht.fuse`` predict programs,
lowers them through the staged AOT path
(``jfn.lower(specs).compile()``) and
serializes the XLA executables via
:mod:`jax.experimental.serialize_executable`.  A fresh replica installs
the bundles straight into the fuse cache, so its first request is a
cache *replay* — zero traces, zero XLA compiles, verifiable on the
``fuse.cache.misses`` / ``compile.cache.misses`` counters.

Soundness is fingerprint-gated, never assumed:

- :func:`fingerprint` pins the format version, jax/jaxlib versions,
  backend platform, visible device count, and the policy key-context
  (:func:`heat_tpu.core._compile.context_token` — precision/threshold/
  redistribution/overlap/guard state).  A bundle whose fingerprint does
  not match the running process is *skipped*, not loaded.
- per-bundle, the capture comm's size and mesh shape must match the
  install comm — an executable compiled for one topology never replays
  on another.
- anything that cannot be exported soundly (unpicklable statics, mixed
  comms across operands, backends whose executables refuse
  serialization) is silently dropped from the bundle list; the replica
  then falls back to a fresh trace+compile for exactly those programs.

The fallback ladder is therefore: installed replay → (on any mismatch)
fresh compile — bit-identical results either way, only the cold-start
latency differs.
"""

from __future__ import annotations

import contextlib
import importlib
import pickle
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax

import sys as _sys

from ..telemetry import _core as _tel
from . import _compile
from . import fuse as _fuse_mod  # noqa: F401 - ensures the module is loaded

# the package rebinds the ``fuse`` attribute to the decorator function,
# so resolve the MODULE explicitly
_fuse = _sys.modules["heat_tpu.core.fuse"]

__all__ = [
    "capture_programs",
    "export_programs",
    "fingerprint",
    "install_programs",
]

#: bumped whenever the bundle layout changes — an old sidecar is a
#: fingerprint mismatch, not a parse error
_FORMAT_VERSION = 1

#: sentinel replacing live comm objects inside pickled key/meta parts
_COMM_SENTINEL = "__heat_tpu_comm__"


def fingerprint() -> Tuple:
    """The compatibility fingerprint an executable bundle is stamped
    with: equal fingerprints mean "this process can soundly replay that
    process's executables"."""
    import jaxlib

    return (
        _FORMAT_VERSION,
        jax.__version__,
        jaxlib.__version__,
        jax.default_backend(),
        jax.device_count(),
        tuple(_compile.context_token()),
    )


# --------------------------------------------------------------------- #
# capture
# --------------------------------------------------------------------- #
@contextlib.contextmanager
def capture_programs():
    """Record every cache-keyed fused-program call inside the block.

    Yields the capture dict (one entry per distinct fuse-cache key,
    recorded whether the call was a build or a replay); hand it to
    :func:`export_programs`.  Capture is observation only — the calls
    themselves run exactly as they would outside the block.
    """
    sink: Dict[Tuple, Dict[str, Any]] = {}
    _fuse._CAPTURE_SINKS.append(sink)
    try:
        yield sink
    finally:
        _fuse._CAPTURE_SINKS.remove(sink)


def _swap_comm(obj, comm, live):
    """Recursively replace ``comm``-equal objects with the sentinel
    (export, ``live=False``) or the sentinel with ``comm`` (install,
    ``live=True``) inside key/meta tuples."""
    if live:
        if isinstance(obj, str) and obj == _COMM_SENTINEL:
            return comm
    else:
        if isinstance(obj, type(comm)) and obj == comm:
            return _COMM_SENTINEL
    if isinstance(obj, tuple):
        return tuple(_swap_comm(o, comm, live) for o in obj)
    return obj


def _comms_in(obj, out: list) -> None:
    """Collect comm-like objects (anything with ``.size`` and
    ``.sharding``) from nested key/meta tuples."""
    if isinstance(obj, tuple):
        for o in obj:
            _comms_in(o, out)
    elif hasattr(obj, "size") and hasattr(obj, "sharding") and not isinstance(
        obj, (np.ndarray, jax.Array)
    ):
        out.append(obj)


def export_programs(capture: Dict[Tuple, Dict[str, Any]]) -> List[Dict[str, Any]]:
    """AOT-compile and serialize every captured program into picklable
    bundles.  Entries that cannot be exported soundly (see module docs)
    are dropped; the count of exported bundles is the caller's signal.
    """
    try:
        from jax.experimental import serialize_executable as _ser
    except ImportError:  # pragma: no cover - jax always ships it here
        return []
    bundles: List[Dict[str, Any]] = []
    for entry in capture.values():
        fn = entry["fn"]
        comm = entry["comm"]
        if comm is None:
            continue  # no DNDarray operand: nothing topology-bound to pin
        seen: list = []
        _comms_in(entry["keyparts"], seen)
        _comms_in(entry["program"].out_meta, seen)
        if any(c != comm for c in seen):
            continue  # mixed comms: one live substitute cannot rebuild the key
        try:
            jfn = entry["program"].jfn
            stashed = getattr(entry["program"], "aot_payload", None)
            if hasattr(jfn, "lower"):
                compiled = jfn.lower(entry["specs"]).compile()
                payload, in_tree, out_tree = _ser.serialize(compiled)
            elif stashed is not None:
                # an installed program: XLA cannot soundly re-serialize a
                # loaded executable (second-generation deserialization
                # fails symbol resolution), so re-export the original
                # payload the install stashed on the program
                payload, in_tree, out_tree = stashed
            else:
                continue
        except (ValueError, TypeError, AttributeError):
            continue  # backend refuses AOT serialization: fresh-compile rung
        bundle = {
            "fingerprint": fingerprint(),
            "fn": (fn.__module__, fn.__qualname__),
            "donate": entry["donate"],
            "plan_token": entry["plan_token"],
            "treedef": entry["treedef"],
            "keyparts": _swap_comm(entry["keyparts"], comm, live=False),
            "comm_size": int(comm.size),
            "mesh_shape": tuple(getattr(comm, "_mesh_shape", (comm.size,))),
            "out_treedef": entry["program"].out_treedef,
            "out_meta": _swap_comm(entry["program"].out_meta, comm, live=False),
            "guarded": entry["program"].guarded,
            "payload": payload,
            "in_tree": in_tree,
            "out_tree": out_tree,
        }
        try:
            pickle.dumps(bundle)
        except Exception:
            continue  # unpicklable static/meta leaf: fresh-compile rung
        bundles.append(bundle)
    if _tel.enabled and bundles:
        _tel.inc("aot.exported", len(bundles))
    return bundles


# --------------------------------------------------------------------- #
# install
# --------------------------------------------------------------------- #
def _resolve_fn(module: str, qualname: str):
    obj: Any = importlib.import_module(module)
    for part in qualname.split("."):
        obj = getattr(obj, part)
    if isinstance(obj, _fuse._FusedFunction):
        obj = obj._fn  # the raw fn is what fuse keys on
    return obj


def install_programs(bundles: List[Dict[str, Any]], *, comm) -> int:
    """Install serialized executables into the fuse cache for ``comm``.

    Returns how many bundles were installed; every skipped bundle (wrong
    fingerprint, topology mismatch, unresolvable function) simply leaves
    its program to the fresh-compile rung of the ladder.  After a
    successful install the next call of the captured pipeline with the
    captured operand layout is a pure cache replay: zero traces, zero
    compiles, one dispatch.
    """
    try:
        from jax.experimental import serialize_executable as _ser
    except ImportError:  # pragma: no cover
        return 0
    want = fingerprint()
    installed = 0
    for bundle in bundles:
        if bundle.get("fingerprint") != want:
            continue
        if int(bundle.get("comm_size", -1)) != int(comm.size):
            continue
        if tuple(bundle.get("mesh_shape", ())) != tuple(
            getattr(comm, "_mesh_shape", (comm.size,))
        ):
            continue
        try:
            fn = _resolve_fn(*bundle["fn"])
        except (ImportError, AttributeError):
            continue
        try:
            compiled = _ser.deserialize_and_load(
                bundle["payload"], bundle["in_tree"], bundle["out_tree"]
            )
        except Exception:
            # ValueError/TypeError on tree mismatch, XlaRuntimeError on
            # unresolvable symbols — every flavour lands on the
            # fresh-compile rung
            continue
        program = _fuse._Program(compiled)
        program.out_treedef = bundle["out_treedef"]
        program.out_meta = _swap_comm(bundle["out_meta"], comm, live=True)
        program.guarded = bool(bundle["guarded"])
        program.aot_payload = (
            bundle["payload"], bundle["in_tree"], bundle["out_tree"]
        )
        key = (
            fn,
            bundle["donate"],
            bundle["plan_token"],
            bundle["treedef"],
            _swap_comm(bundle["keyparts"], comm, live=True),
            comm,
            _compile.context_token(),
        )
        _fuse._FUSE_CACHE[key] = program
        installed += 1
    if _tel.enabled:
        if installed:
            _tel.inc("aot.installed", installed)
        _tel.gauge("fuse.cache.size", len(_fuse._FUSE_CACHE))
    return installed
