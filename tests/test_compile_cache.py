"""The op engine's keyed jit cache: re-entry, clearing, stability.

Covers the `clear_cache()` / `cache_size()` / `jitted()` contract (the
cache must repopulate identically after a clear) and the `cache_stable()`
predicate that gates which callables may appear in keys (spmdlint
SPMD401's runtime twin).
"""

from functools import partial

import numpy as np

import jax.numpy as jnp

from heat_tpu.core._compile import cache_size, cache_stable, clear_cache, jitted


def _module_level_fn(x):
    return x + 1


class _Obj:
    def method(self):  # pragma: no cover - identity only
        return None


def test_jitted_reentry_hits_cache():
    clear_cache()
    calls = []

    def make():
        calls.append(1)
        return lambda a: a * 2.0

    key = ("test.reentry", 0)
    f1 = jitted(key, make)
    f2 = jitted(key, make)
    assert f1 is f2, "same key must return the same compiled callable"
    assert len(calls) == 1, "make_fn runs only on the miss"
    assert cache_size() == 1


def test_cache_repopulates_identically_after_clear():
    clear_cache()
    key = ("test.clear", 3)

    def make():
        return lambda a: a + 3.0

    x = jnp.arange(5.0)
    f1 = jitted(key, make)
    before = np.asarray(f1(x))
    assert cache_size() == 1

    clear_cache()
    assert cache_size() == 0

    f2 = jitted(key, make)
    assert f2 is not f1, "clear must really drop the entry"
    assert cache_size() == 1
    np.testing.assert_array_equal(np.asarray(f2(x)), before)
    # re-entry after repopulation is again a pure cache hit
    assert jitted(key, make) is f2 and cache_size() == 1


def test_distinct_keys_distinct_entries():
    clear_cache()
    make = lambda: lambda a: a  # noqa: E731
    jitted(("test.k", 1), make)
    jitted(("test.k", 2), make)
    assert cache_size() == 2


def test_cache_stable_accepts_import_time_singletons():
    assert cache_stable(_module_level_fn)
    assert cache_stable(jnp.add)       # jax ufunc singleton
    assert cache_stable(np.add)        # numpy ufunc
    assert cache_stable(jnp.sum)       # plain function
    assert cache_stable(jnp.maximum)   # PjitFunction singleton


def test_cache_stable_rejects_per_call_identities():
    assert not cache_stable(lambda x: x)

    def outer():
        y = 2.0

        def closure(x):
            return x * y

        return closure

    assert not cache_stable(outer())
    assert not cache_stable(_Obj().method)
    assert not cache_stable(partial(_module_level_fn, 1))


# ----------------------------------------------------------------------- #
# where a process keeps the persistent compilation cache
# ----------------------------------------------------------------------- #
def _place_in_fresh_python(env_dir, **env):
    """Run place_compile_cache() in a fresh interpreter (jax's config is
    process-wide) and return (what it returned, what jax was left with)."""
    import json

    from suite import run_in_fresh_python

    script = (
        "import json, jax\n"
        "from heat_tpu.core._compile_cache import place_compile_cache\n"
        "print('PLACED', json.dumps([place_compile_cache(), "
        "jax.config.jax_compilation_cache_dir, "
        "jax.config.jax_persistent_cache_min_compile_time_secs]))\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends\n"
    )
    overrides = dict(env) if env_dir is None else {"JAX_COMPILATION_CACHE_DIR": env_dir, **env}
    res = run_in_fresh_python(
        script, env_overrides=overrides,
        drop_env=("JAX_COMPILATION_CACHE_DIR",) if env_dir is None else (),
    )
    assert "PLACED" in res.stdout, res.stdout + res.stderr
    return json.loads(res.stdout.split("PLACED", 1)[1])


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout():
    import os

    from heat_tpu.core._compile_cache import DEFAULT_CACHE_DIR

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    returned, configured, floor = _place_in_fresh_python(None)
    assert returned == configured == DEFAULT_CACHE_DIR
    assert floor == 0.0  # the op engine's sub-second programs are cached too


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself and the helper
    sets no other directory in code."""
    outside = str(tmp_path / "cache")
    returned, configured, _ = _place_in_fresh_python(outside)
    assert returned == configured == outside


def test_compile_cache_floor_set_by_the_operator_is_left_alone():
    _, _, floor = _place_in_fresh_python(
        None, JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="2.5")
    assert floor == 2.5


def test_compile_cache_of_an_installed_package_is_not_placed(tmp_path, monkeypatch):
    """No ``pyproject.toml`` beside the package (site-packages, not a
    checkout) and nothing set from outside: the helper sets nothing."""
    import importlib.util
    import shutil

    import jax
    from heat_tpu.core import _compile_cache

    installed = tmp_path / "site-packages" / "heat_tpu" / "core"
    installed.mkdir(parents=True)
    shutil.copy(_compile_cache.__file__, installed)
    spec = importlib.util.spec_from_file_location(
        "_installed_compile_cache", installed / "_compile_cache.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert module.DEFAULT_CACHE_DIR is None
    assert module.place_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
