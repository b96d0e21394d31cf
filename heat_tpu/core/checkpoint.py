"""Fitted-estimator checkpointing.

An extension the reference lacks: its estimators expose ``get_params``
(reference base.py:30-55) but have no save/restore of FITTED state —
persistence there is data-level only (``ht.save``/``ht.load``, reference
io.py:622-921; SURVEY §5.4 calls this out).  Training an estimator on a
large mesh and re-fitting it in every consumer process is exactly the
workflow a TPU deployment cannot afford, so this module closes the gap
on top of the existing parallel IO layer:

- one HDF5 file per estimator;
- a typed JSON manifest (file attribute) describing constructor params
  and fitted attributes: scalars inline, small host numpy arrays inline,
  large host numpy arrays spilled to datasets, nested fitted estimators
  recursively, DNDarrays by dataset key;
- all datasets + the manifest written in ONE file open with ONE
  cross-process failure barrier (io._save_hdf5_many — multihost-safe:
  process 0 writes, every process joins the slab collectives);
- split layouts recorded per dataset and restored exactly on load;
- DNDarrays shared between a parent and a nested estimator (Spectral's
  ``_labels`` IS its KMeans's ``labels_``) are written once and re-linked
  on load.

What gets captured: constructor parameters (``get_params``) plus the
attributes named by ``BaseEstimator._checkpoint_attrs()`` — by default
every public ``*_`` instance attribute (the sklearn fitted convention);
estimators whose fitted state lives in private storage override it
(``_KCluster``, ``Spectral``, ``Lasso``).
"""

from __future__ import annotations

import importlib
import json
from typing import Any, Dict, Tuple

import numpy as np

from ..telemetry import _core as _tel
from . import io as _io
from . import types
from .base import BaseEstimator
from .dndarray import DNDarray

__all__ = ["list_checkpoints", "load_estimator", "save_estimator"]

_MANIFEST_ATTR = "heat_tpu_estimator"
#: manifest schema version this build WRITES (as ``format_version``);
#: v1 manifests (which carried the version under the legacy ``format``
#: key) remain readable — the entry kinds are a superset-compatible set
_FORMAT_VERSION = 2
_READABLE_VERSIONS = (1, 2)
#: inline-manifest budget for host numpy arrays; anything bigger spills
#: to an HDF5 dataset instead of the JSON attribute
_NPARRAY_INLINE_MAX = 16384


class _SaveContext:
    """Dataset accumulator with identity dedup: the same DNDarray (or the
    same host array object) reachable twice — e.g. Spectral._labels is
    its nested KMeans's labels_ — is written once."""

    def __init__(self):
        self.datasets: Dict[str, DNDarray] = {}
        self._by_id: Dict[int, str] = {}
        # id() keys are only valid while the object lives — retain every
        # identity object so a freed temporary's recycled address can
        # never produce a false dedup hit
        self._keepalive: list = []

    def add(self, value: DNDarray, key: str, ident=None) -> str:
        """Register ``value`` under ``key`` unless the identity object
        (``ident``, default the value itself — pass the ORIGINAL host
        array when spilling a numpy attribute) was registered before."""
        obj = value if ident is None else ident
        existing = self._by_id.get(id(obj))
        if existing is not None:
            return existing
        self._by_id[id(obj)] = key
        self._keepalive.append(obj)
        self.datasets[key] = value
        return key


def _encode(value, key: str, ctx: _SaveContext) -> Dict[str, Any]:
    """One manifest entry for ``value``; DNDarrays (and spilled host
    arrays) land in ``ctx`` under ``key`` (or an earlier key if dedup
    hits)."""
    if isinstance(value, DNDarray):
        return {
            "kind": "dndarray",
            "key": ctx.add(value, key),
            "split": value.split,
            "dtype": value.dtype.__name__,
        }
    if isinstance(value, BaseEstimator):
        return {"kind": "estimator", "manifest": _manifest(value, key + "/", ctx)}
    import jax

    ident = None
    if isinstance(value, jax.Array):
        # dedup keys on the ORIGINAL device array: np.asarray makes a
        # fresh host copy per attribute, so two attributes aliasing one
        # jax.Array would otherwise write two datasets
        ident = value
        value = np.asarray(value)
        if value.ndim == 0:
            value = value.item()
    if isinstance(value, np.generic):
        value = value.item()
    is_bf16 = isinstance(value, np.ndarray) and value.dtype == np.dtype("bfloat16")
    if isinstance(value, np.ndarray) and (value.dtype.kind in "biuf" or is_bf16):
        # non-numeric dtypes (datetime64, structured, object) fall
        # through to the descriptive TypeError below: neither json
        # inlining nor the heat dataset spill can round-trip them.
        # bfloat16 (numpy kind 'V' via ml_dtypes) IS numeric: its dtype
        # is recorded by NAME (its .str is a lossy '<V2') and its HDF5
        # spill widens exactly to f32 (h5py has no bf16)
        obj = ident if ident is not None else value
        if value.size > _NPARRAY_INLINE_MAX:
            # library-managed host state (e.g. GaussianNB theta_ on many
            # features) must not fail the save — spill it to a dataset.
            # Dedup keys on the original object: two attributes aliasing
            # one array write one dataset
            existing = ctx._by_id.get(id(obj))
            if existing is not None:
                arr = ctx.datasets[existing]
                used = existing
            else:
                from . import factories

                host = np.ascontiguousarray(value)
                if is_bf16:
                    host = host.astype(np.float32)  # exact widening
                arr = factories.array(host)
                used = ctx.add(arr, key, ident=obj)
            return {
                "kind": "nparray_dataset",
                "key": used,
                "dtype": value.dtype.name,
                "heat_dtype": arr.dtype.__name__,
            }
        return {
            "kind": "nparray",
            "dtype": value.dtype.name,
            "shape": list(value.shape),
            # bf16 tolist() yields exact python floats — json-safe
            "data": value.ravel().tolist(),
        }
    if value is None or isinstance(value, (bool, int, float, str)):
        return {"kind": "scalar", "value": value}
    if isinstance(value, (list, tuple)):
        if all(v is None or isinstance(v, (bool, int, float, str)) for v in value):
            # JSON collapses tuples into lists; record which it was so the
            # restored param compares equal to the original
            return {
                "kind": "scalar",
                "value": list(value),
                "tuple": isinstance(value, tuple),
            }
    raise TypeError(
        f"cannot checkpoint {key!r} of type {type(value).__name__}: {value!r} "
        "(supported: DNDarray, estimators, scalars, strings, numeric "
        "bool/int/uint/float host numpy arrays, flat scalar lists)"
    )


def _is_heat_tpu_module(mod_name: str) -> bool:
    """One allowlist predicate for BOTH the save-time guard (_manifest)
    and the load-time import guard (_resolve_class), so the two can
    never drift apart."""
    return mod_name == "heat_tpu" or mod_name.startswith("heat_tpu.")


def _manifest(est: BaseEstimator, prefix: str, ctx: _SaveContext):
    cls = type(est)
    mod = cls.__module__
    if not _is_heat_tpu_module(mod):
        # _resolve_class refuses non-heat_tpu imports on load; failing
        # only there would let the save "succeed" and error much later
        # with a confusing message — reject at save time instead
        raise TypeError(
            f"cannot checkpoint {mod}.{cls.__qualname__}: only heat_tpu "
            "estimator classes are re-importable at load time"
        )
    out: Dict[str, Any] = {
        "class": f"{cls.__module__}:{cls.__qualname__}",
        "params": {},
        "fitted": {},
    }
    params = est.get_params(deep=False)
    for name, value in params.items():
        out["params"][name] = _encode(value, f"{prefix}params/{name}", ctx)
    for name in est._checkpoint_attrs():
        if name in params or not hasattr(est, name):
            continue
        out["fitted"][name] = _encode(
            getattr(est, name), f"{prefix}fitted/{name}", ctx
        )
    return out


def save_estimator(est: BaseEstimator, path: str) -> None:
    """Write ``est`` — constructor params plus fitted state — to one HDF5
    file.  Safe on multihost: every dataset and the manifest go through
    one lockstep writer pass with a single failure-propagation barrier
    (io._save_hdf5_many)."""
    if not _io.supports_hdf5():
        raise RuntimeError("h5py is required for estimator checkpointing")
    if not isinstance(est, BaseEstimator):
        raise TypeError(f"est must be a BaseEstimator, got {type(est)}")
    if not isinstance(path, str):
        raise TypeError(f"path must be str, not {type(path)}")
    import os

    if os.path.splitext(path)[-1].strip().lower() not in _io.HDF5_EXTENSIONS:
        # guard EVERY entry point (est.save, ht.save, save_estimator):
        # HDF5 bytes under a .nc/.csv name would misdirect the loader
        raise ValueError("estimator checkpoints are HDF5: use a .h5/.hdf5 path")

    ctx = _SaveContext()
    manifest = {
        "format_version": _FORMAT_VERSION,
        "root": _manifest(est, "", ctx),
    }
    if _tel.enabled:
        _tel.inc("checkpoint.saves")
        with _tel.span("ckpt:save_estimator", "io", cls=type(est).__name__, path=path):
            _io._save_hdf5_many(
                path,
                sorted(ctx.datasets.items()),
                attrs={_MANIFEST_ATTR: json.dumps(manifest)},
            )
        _tel.record_event(
            "checkpoint", site=type(est).__name__, op="save", path=path
        )
        return
    _io._save_hdf5_many(
        path,
        sorted(ctx.datasets.items()),
        attrs={_MANIFEST_ATTR: json.dumps(manifest)},
    )


def list_checkpoints(directory: str):
    """Scan one directory (non-recursively) for estimator checkpoints.

    Returns one dict per HDF5 file carrying an estimator manifest, sorted
    by filename: ``{"path", "file", "format_version", "class"}`` with
    ``class`` the root estimator's ``module:qualname``.  Files without an
    HDF5 extension are skipped, as are valid HDF5 *data* files (no
    manifest attribute).  An HDF5-named file that cannot be opened, or
    whose manifest attribute is not valid JSON, raises ``ValueError``
    naming the offending file — a registry root must surface a corrupted
    model version, not silently drop it.  Opens go through the same
    seeded-retry policy as :func:`load_estimator`, so a transient EIO
    heals instead of failing the scan.
    """
    if not _io.supports_hdf5():
        raise RuntimeError("h5py is required for estimator checkpointing")
    import os

    import h5py

    if not os.path.isdir(directory):
        raise ValueError(f"{directory} is not a directory")
    out = []
    for name in sorted(os.listdir(directory)):
        if os.path.splitext(name)[-1].strip().lower() not in _io.HDF5_EXTENSIONS:
            continue
        path = os.path.join(directory, name)

        def _open(path=path):
            _io._faults().io_open(path)
            return h5py.File(path, "r")

        try:
            f = _io._retry_open(_open, "checkpoint.list_checkpoints")
        except OSError as e:
            raise ValueError(
                f"{path} is not a readable checkpoint file (missing, "
                f"truncated, or not HDF5): {e}"
            ) from e
        with f:
            raw = f.attrs.get(_MANIFEST_ATTR)
        if raw is None:
            continue
        try:
            manifest = json.loads(raw)
        except (TypeError, ValueError) as e:
            raise ValueError(f"{path}: corrupt estimator manifest: {e}") from e
        if not isinstance(manifest, dict):
            raise ValueError(
                f"{path}: corrupt estimator manifest: expected a JSON "
                f"object, got {type(manifest).__name__}"
            )
        root = manifest.get("root")
        out.append(
            {
                "path": path,
                "file": name,
                "format_version": manifest.get(
                    "format_version", manifest.get("format")
                ),
                "class": root.get("class") if isinstance(root, dict) else None,
            }
        )
    return out


def _resolve_class(class_path: str):
    mod_name, _, qual = class_path.partition(":")
    if not _is_heat_tpu_module(mod_name):
        raise ValueError(
            f"refusing to import estimator class from {mod_name!r} "
            "(only heat_tpu estimators are loadable)"
        )
    mod = importlib.import_module(mod_name)
    obj: Any = mod
    for part in qual.split("."):
        obj = getattr(obj, part)
    if not (isinstance(obj, type) and issubclass(obj, BaseEstimator)):
        raise TypeError(f"{class_path} is not a BaseEstimator subclass")
    return obj


def _decode(entry: Dict[str, Any], path: str, cache: Dict[str, Any]):
    kind = entry["kind"]
    if kind == "scalar":
        value = entry["value"]
        if entry.get("tuple"):
            value = tuple(value)
        return value
    if kind == "nparray":
        return np.asarray(entry["data"], dtype=np.dtype(entry["dtype"])).reshape(
            entry["shape"]
        )
    if kind == "dndarray":
        key = entry["key"]
        if key not in cache:
            dtype = getattr(types, entry["dtype"])
            try:
                cache[key] = _io.load_hdf5(path, key, dtype=dtype, split=entry["split"])
            except KeyError as e:
                raise ValueError(
                    f"{path}: checkpoint dataset {key!r} is missing "
                    "(truncated or corrupted save)"
                ) from e
        return cache[key]
    if kind == "nparray_dataset":
        key = entry["key"]
        if key not in cache:
            dtype = getattr(types, entry["heat_dtype"])
            try:
                loaded = _io.load_hdf5(path, key, dtype=dtype, split=None)
            except KeyError as e:
                raise ValueError(
                    f"{path}: checkpoint dataset {key!r} is missing "
                    "(truncated or corrupted save)"
                ) from e
            cache[key] = loaded.numpy().astype(np.dtype(entry["dtype"]))
        return cache[key]
    if kind == "estimator":
        return _instantiate(entry["manifest"], path, cache)
    raise ValueError(f"unknown checkpoint entry kind {kind!r}")


def _instantiate(
    manifest: Dict[str, Any], path: str, cache: Dict[str, Any]
) -> BaseEstimator:
    cls = _resolve_class(manifest["class"])
    kwargs = {
        name: _decode(entry, path, cache)
        for name, entry in manifest["params"].items()
    }
    est = cls(**kwargs)
    for name, entry in manifest["fitted"].items():
        setattr(est, name, _decode(entry, path, cache))
    return est


def load_estimator(path: str) -> BaseEstimator:
    """Reconstruct an estimator saved by :func:`save_estimator`: the class
    is re-imported, constructed from its saved parameters (DNDarray
    params load with their recorded split), and the fitted attributes —
    including nested fitted estimators — are restored.  Arrays the save
    deduplicated load once and are re-linked."""
    if not _io.supports_hdf5():
        raise RuntimeError("h5py is required for estimator checkpointing")
    import h5py

    def _open():
        _io._faults().io_open(path)
        return h5py.File(path, "r")

    try:
        # transient EIO at the open heals under the bounded, seeded retry
        # policy; only an exhausted policy surfaces as the ValueError below
        f = _io._retry_open(_open, "checkpoint.load_estimator")
    except OSError as e:
        raise ValueError(
            f"{path} is not a readable estimator checkpoint (missing, "
            f"truncated, or not HDF5): {e}"
        ) from e
    with f:
        raw = f.attrs.get(_MANIFEST_ATTR)
        if raw is None:
            raise ValueError(f"{path} is not an estimator checkpoint")
        manifest = json.loads(raw)
    # v2 writes format_version; v1 recorded it under the legacy "format"
    version = manifest.get("format_version", manifest.get("format"))
    if version not in _READABLE_VERSIONS:
        raise ValueError(
            f"{path}: unsupported checkpoint format_version {version!r} "
            f"(this build reads versions {list(_READABLE_VERSIONS)})"
        )
    if _tel.enabled:
        _tel.inc("checkpoint.loads")
        with _tel.span("ckpt:load_estimator", "io", path=path):
            est = _instantiate(manifest["root"], path, {})
        _tel.record_event(
            "checkpoint", site=type(est).__name__, op="load", path=path
        )
        return est
    return _instantiate(manifest["root"], path, {})
