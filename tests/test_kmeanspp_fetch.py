"""The owner-answers row fetch of the sharded k-means++.

``arr[idx]`` with a traced ``idx`` on a row-sharded operand makes GSPMD
replicate the whole operand; ``fetch_row`` has the row's owner answer with
one all-reduce of a single row.  Held here on the CPU mesh: the helper is
bitwise ``arr[idx]``, ``_kmeanspp`` with it draws bitwise the centres of its
one-device twin, every layout that can read a row locally keeps the plain
slice, and the launch span and the wire ledger say which path ran.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.cluster._kcluster import _kmeanspp
from heat_tpu.comm.compressed import wire_model
from heat_tpu.core.communication import XlaCommunication, fetch_row
from heat_tpu.telemetry import _core

P = len(jax.devices())
W, F, K = 6, 24, 5  # rows a shard, features, centres

pytestmark = pytest.mark.skipif(P < 2, reason="needs a multi-device mesh")


@pytest.fixture
def tel():
    was = _core.is_enabled()
    telemetry.enable()
    telemetry.reset()
    yield telemetry
    telemetry.reset()
    if not was:
        telemetry.disable()


def _one_chip():
    return XlaCommunication(jax.devices()[:1])


def _kmeanspp_spans():
    return [
        e for e in telemetry.events()
        if e["type"] == "span" and e["site"] == "jit:kmeans.kmeanspp"
    ]


def _seeded_centres(x):
    km = ht.cluster.KMeans(n_clusters=K, init="probability_based", random_state=11)
    km._initialize_cluster_centers(x)
    return np.asarray(km.cluster_centers_.larray)


#: the shard boundaries and a seeded sample of the rest
FETCH_ROWS = {
    "first": 0, "end_of_shard_0": W - 1, "start_of_shard_1": W, "last": W * P - 1,
    **{f"seeded_{i}": int(r) for i, r in enumerate(np.random.default_rng(26).integers(0, W * P, 3))},
}


@pytest.mark.parametrize("idx", list(FETCH_ROWS.values()), ids=list(FETCH_ROWS))
def test_fetch_row_is_bitwise_the_row_whatever_other_shards_hold(idx):
    comm = ht.get_comm()
    rng = np.random.default_rng(idx)
    a = rng.standard_normal((W * P, F)).astype(np.float32)
    a[idx, 0] = 0.0
    owner = idx // W
    for s in range(P):  # a clamped row of another shard must not leak into the sum
        if s != owner:
            a[s * W:(s + 1) * W] = np.where(rng.random((W, F)) < 0.5, np.inf, np.nan)
    rows_sh = comm.sharding(1, 0)
    fetch = jax.jit(lambda arr, i: fetch_row(arr, i, rows_sh))
    got = fetch(jax.device_put(a, comm.sharding(2, 0)), jnp.int32(idx))
    assert got.sharding.is_fully_replicated
    assert np.asarray(got).tobytes() == a[idx].tobytes()


#: layout -> (rows, split, what the launch span must say)
LAYOUTS = {
    "split0": (W * P, 0, "owner_psum"),
    "replicated": (W * P, None, "local"),
    "split1": (W * P, 1, "local"),
    "ragged": (W * P + 3, 0, "local"),
}


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_centres_are_bitwise_the_one_device_twins_in_every_layout(tel, layout):
    """Same ``first``, same ``us`` (the seeded draws do not depend on the
    mesh), so the same rows: only the evenly row-sharded input fetches from
    the owner, and it is credited K all-reduces of F float32."""
    n, split, row_fetch = LAYOUTS[layout]
    a = np.random.default_rng(n + F).standard_normal((n, F)).astype(np.float32)
    x = ht.array(a, split=split)
    telemetry.reset()
    got = _seeded_centres(x)
    (span,) = _kmeanspp_spans()
    counters = telemetry.snapshot()["counters"]
    assert span["row_fetch"] == row_fetch
    if row_fetch == "owner_psum":
        wm = wire_model(F, P, None, op="allreduce")
        assert counters["comm.exact_bytes.f32"] == K * wm["exact_wire_bytes"]
        assert counters["comm.wire_bytes.f32"] == K * wm["wire_bytes"]
    else:
        assert "comm.exact_bytes" not in counters
    assert got.tobytes() == _seeded_centres(ht.array(a, split=None, comm=_one_chip())).tobytes()
    assert {tuple(r) for r in got} <= {tuple(r) for r in a}


@pytest.mark.parametrize("row_fetch", ["owner_psum", "local"])
def test_a_fits_launch_span_and_wire_ledger_say_which_path_ran(tel, row_fetch):
    """A whole ``fit``: on the mesh the owner answers and the ledger holds the
    K fetches (the default policy's sweeps credit nothing); on one device the
    rows are read where they lie and nothing is credited."""
    x = ht.array(
        np.random.default_rng(3).standard_normal((W * P, F)).astype(np.float32),
        split=0, comm=None if row_fetch == "owner_psum" else _one_chip(),
    )
    telemetry.reset()
    ht.cluster.KMeans(n_clusters=K, init="probability_based", max_iter=2, random_state=1).fit(x)
    (span,) = _kmeanspp_spans()
    assert span["row_fetch"] == row_fetch
    counters = telemetry.snapshot()["counters"]
    if row_fetch == "owner_psum":
        wm = wire_model(F, P, None, op="allreduce")
        assert counters["comm.exact_bytes.f32"] == K * wm["exact_wire_bytes"]
    else:
        assert "comm.exact_bytes" not in counters


def test_kmeanspp_with_given_draws_matches_its_twin_bitwise():
    """The compiled program alone, ``first`` and ``us`` handed in."""
    comm = ht.get_comm()
    rng = np.random.default_rng(5)
    a = rng.standard_normal((W * P, F)).astype(np.float32)
    first, us = jnp.int32(W + 1), jnp.asarray(rng.random(K), jnp.float32)
    sharded = _kmeanspp(
        jax.device_put(a, comm.sharding(2, 0)), first, us, rows_sh=comm.sharding(1, 0)
    )
    twin = _kmeanspp(jax.device_put(a, jax.devices()[0]), first, us)
    assert sharded.sharding.is_fully_replicated
    assert np.asarray(sharded).tobytes() == np.asarray(twin).tobytes()
