"""sklearn-compatible estimator API.

Reference: heat/core/base.py:5-297 — ``BaseEstimator`` with introspective
``get_params``/``set_params`` plus the fit/predict mixins and estimator-type
predicates.  Pure-Python API contracts; identical semantics here.
"""

from __future__ import annotations

import functools
import inspect
import types as _types
from typing import Any, Dict

from ..telemetry import _core as _tel

__all__ = [
    "BaseEstimator",
    "ClassificationMixin",
    "ClusteringMixin",
    "RegressionMixin",
    "TransformMixin",
    "is_classifier",
    "is_estimator",
    "is_clusterer",
    "is_regressor",
    "is_transformer",
]


def _spanned_method(meth, label: str):
    """Wrap an estimator entry point in a telemetry span.

    The wrapper is a single predicate per call while nothing records;
    recording, every ``fit``/``predict`` is a span of kind ``entry`` named
    ``fit:<ClassName>`` / ``predict:<ClassName>`` (the class is resolved
    at call time, so subclasses inheriting a wrapped method report under
    their own name) that also holds the launches and host syncs counted
    while it was open."""

    @functools.wraps(meth)
    def wrapper(self, *args, **kwargs):
        if not _tel.recording():
            return meth(self, *args, **kwargs)
        with _tel.span(f"{label}:{type(self).__name__}", "entry"):
            return meth(self, *args, **kwargs)

    wrapper._telemetry_wrapped = True
    return wrapper


class BaseEstimator:
    """Base class for all estimators (reference base.py:5-90)."""

    def __init_subclass__(cls, **kwargs):
        # every concrete estimator's fit/predict emits a telemetry span
        # automatically — no per-estimator instrumentation to forget
        super().__init_subclass__(**kwargs)
        for name in ("fit", "predict"):
            meth = cls.__dict__.get(name)
            if (
                isinstance(meth, _types.FunctionType)
                and not getattr(meth, "_telemetry_wrapped", False)
            ):
                setattr(cls, name, _spanned_method(meth, name))

    @classmethod
    def _parameter_names(cls):
        init = cls.__init__
        if init is object.__init__:
            return []
        sig = inspect.signature(init)
        return sorted(
            p.name
            for p in sig.parameters.values()
            if p.name != "self" and p.kind not in (p.VAR_POSITIONAL, p.VAR_KEYWORD)
        )

    def get_params(self, deep: bool = True) -> Dict[str, Any]:
        """Parameters of this estimator (reference base.py:30-55)."""
        params = {}
        for name in self._parameter_names():
            value = getattr(self, name, None)
            if deep and hasattr(value, "get_params"):
                for sub_name, sub_value in value.get_params().items():
                    params[f"{name}__{sub_name}"] = sub_value
            params[name] = value
        return params

    def set_params(self, **params) -> "BaseEstimator":
        """Set estimator parameters (reference base.py:56-90)."""
        if not params:
            return self
        valid = self.get_params(deep=True)
        nested = {}
        for key, value in params.items():
            key, delim, sub_key = key.partition("__")
            if key not in valid:
                raise ValueError(f"Invalid parameter {key} for estimator {self}")
            if delim:
                nested.setdefault(key, {})[sub_key] = value
            else:
                setattr(self, key, value)
                valid[key] = value
        for key, sub_params in nested.items():
            getattr(self, key).set_params(**sub_params)
        return self

    def __repr__(self, N_CHAR_MAX: int = 700) -> str:
        params = ", ".join(f"{k}={v!r}" for k, v in sorted(self.get_params(deep=False).items()))
        return f"{self.__class__.__name__}({params})"[:N_CHAR_MAX]

    def _checkpoint_attrs(self):
        """Instance attributes :func:`heat_tpu.save_estimator` persists
        beyond the constructor params.  Default: every public ``*_``
        attribute (the sklearn fitted convention).  Estimators whose
        fitted state lives in private storage override this."""
        return [
            n for n in vars(self) if n.endswith("_") and not n.startswith("_")
        ]

    def save(self, path: str) -> None:
        """Checkpoint this estimator — constructor params plus fitted
        state — to one HDF5 file (extension; the reference persists data
        only, SURVEY §5.4).  See :func:`heat_tpu.save_estimator`."""
        from .checkpoint import save_estimator

        save_estimator(self, path)

    @classmethod
    def load(cls, path: str) -> "BaseEstimator":
        """Restore an estimator saved with :meth:`save`; raises TypeError
        if the checkpoint holds a different estimator class than ``cls``
        (call ``BaseEstimator.load`` / ``ht.load_estimator`` to accept
        any)."""
        from .checkpoint import load_estimator

        est = load_estimator(path)
        if cls is not BaseEstimator and not isinstance(est, cls):
            raise TypeError(
                f"{path} holds a {type(est).__name__}, not a {cls.__name__}"
            )
        return est


class ClassificationMixin:
    """fit/predict contract for classifiers (reference base.py:92-141)."""

    _estimator_type = "classifier"

    def fit(self, x, y):
        raise NotImplementedError()

    def fit_predict(self, x, y):
        self.fit(x, y)
        return self.predict(x)

    def predict(self, x):
        raise NotImplementedError()


class ClusteringMixin:
    """fit/fit_predict contract for clusterers (reference base.py:142-177)."""

    _estimator_type = "clusterer"

    def fit(self, x):
        raise NotImplementedError()

    def fit_predict(self, x):
        self.fit(x)
        return self.predict(x)


class RegressionMixin:
    """fit/predict contract for regressors (reference base.py:178-227)."""

    _estimator_type = "regressor"

    def fit(self, x, y):
        raise NotImplementedError()

    def fit_predict(self, x, y):
        self.fit(x, y)
        return self.predict(x)

    def predict(self, x):
        raise NotImplementedError()


class TransformMixin:
    """fit/transform contract (numpy/sklearn-parity extension)."""

    def fit(self, x):
        raise NotImplementedError()

    def transform(self, x):
        raise NotImplementedError()

    def fit_transform(self, x):
        self.fit(x)
        return self.transform(x)


def is_estimator(obj) -> bool:
    """(reference base.py:228-245)"""
    return isinstance(obj, BaseEstimator)


def is_classifier(obj) -> bool:
    """(reference base.py:246-262)"""
    return getattr(obj, "_estimator_type", None) == "classifier"


def is_clusterer(obj) -> bool:
    """(reference base.py:263-279)"""
    return getattr(obj, "_estimator_type", None) == "clusterer"


def is_regressor(obj) -> bool:
    """(reference base.py:280-297)"""
    return getattr(obj, "_estimator_type", None) == "regressor"


def is_transformer(obj) -> bool:
    """TransformMixin predicate (extension)."""
    return isinstance(obj, TransformMixin)
