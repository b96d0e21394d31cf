"""The multi-process serving plane (design.md §25).

Layers under test, cheapest first:

- **wire**: length-prefixed frame codec — roundtrip (scalars, 2-D,
  empty, multi-blob), clean-EOF vs dead-pipe distinction, max-frame
  guard;
- **WFQ**: weighted interleave, strict priority bands, per-tenant
  bounded shed with the deterministic retry-after hint;
- **hist merge** (the LoadReport fix): ``Histogram.from_state`` is an
  exact inverse, and merging per-replica states equals the single-stream
  histogram byte-for-byte — percentiles within REL_ERROR of exact;
- **ingress wire surface**: loopback-only bind, typed 429 + Retry-After
  across the socket (stub backend — no processes);
- **process fleet**: warm replicas hello with ZERO compile/fuse misses,
  replies are byte-identical to the single-process ``FleetEngine``
  golden twin, sticky sessions pin a replica, trace ids survive the hop,
  the aggregated ``/metrics`` endpoint byte-parses and its counter sums
  reconcile with the reply ledger;
- **chaos**: kill -9 a replica mid-stream — every accepted request is
  answered exactly once, the fleet reply ledger replays byte-identically
  under ``HEAT_CHAOS_SEED``, and a hot tenant saturating its WFQ share
  sheds while the cold tenant's stream completes with bounded p99.
"""

from __future__ import annotations

import socket
import urllib.error
import urllib.request
import zlib

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.net import wire
from heat_tpu.resilience import faults, incidents
from heat_tpu.resilience import retry as retry_mod
from heat_tpu.serve import (
    FleetEngine,
    FleetMetricsServer,
    Ingress,
    IngressClient,
    ModelRegistry,
    ProcFleet,
    ServeEngine,
    ServeOverloadError,
    TenantPolicy,
    WeightedFairQueue,
    loadgen,
)
from heat_tpu.telemetry.hist import Histogram

RNG = np.random.default_rng(42)
Xn = RNG.normal(size=(64, 5)).astype(np.float32)


@pytest.fixture(autouse=True)
def _clean_harness():
    def _scrub():
        faults.clear()
        incidents.clear_incident_log()
        retry_mod.set_sleep(None)
        telemetry.disable()
        telemetry.reset()

    _scrub()
    yield
    _scrub()


@pytest.fixture(scope="module")
def fitted():
    X = ht.array(Xn, split=0)
    km = ht.cluster.KMeans(n_clusters=3, max_iter=5, random_state=0)
    km.fit(X)
    km2 = ht.cluster.KMeans(n_clusters=3, max_iter=7, random_state=1)
    km2.fit(X)
    return {"km": km, "km2": km2}


@pytest.fixture(scope="module")
def fleet_root(tmp_path_factory, fitted):
    """One registry on disk shared by every fleet in this module: three
    tenants over the same estimator, v1+v2 for the canary, and the v1
    ``.aotx`` sidecar the replicas warm from."""
    root = str(tmp_path_factory.mktemp("procfleet-models"))
    reg = ModelRegistry(root)
    for tenant in ("acme", "hot", "cold"):
        reg.publish(tenant, "km", fitted["km"])
    reg.publish("acme", "km", fitted["km2"])  # v2: canary
    src = ServeEngine(reg, max_batch_rows=32, min_bucket=8)
    bundles = src.export_warm("acme", "km", version=1)
    src.close()
    assert bundles, "AOT capture produced no serializable programs"
    reg.publish_executables("acme", "km", 1, bundles)
    return root


def payload(rows, seed=0):
    return np.random.default_rng(seed).normal(size=(rows, 5)).astype(np.float32)


# --------------------------------------------------------------------- #
# wire framing                                                           #
# --------------------------------------------------------------------- #
def test_wire_roundtrip_blobs_and_scalars():
    msg = {"kind": "predict", "rid": "r1", "version": None}
    blobs = {
        "x": np.arange(12, dtype=np.float32).reshape(3, 4),
        "s": np.array(5, dtype=np.int64),
        "e": np.empty((0, 3), dtype=np.float64),
    }
    frame = wire.encode_frame(msg, blobs)
    msg2, blobs2 = wire.decode_frame(frame[4:])
    assert msg2 == msg
    assert blobs2["x"].dtype == np.float32 and blobs2["x"].shape == (3, 4)
    assert np.array_equal(blobs2["x"], blobs["x"])
    assert blobs2["s"].shape == () and blobs2["s"] == 5
    assert blobs2["e"].shape == (0, 3)


def test_wire_same_message_same_bytes():
    # sorted keys + raw blob bytes: frames are deterministic, so ledgers
    # built over them are a pure function of the request stream
    a = wire.encode_frame({"b": 1, "a": 2}, {"x": np.ones(3, np.float32)})
    b = wire.encode_frame({"a": 2, "b": 1}, {"x": np.ones(3, np.float32)})
    assert a == b


def test_wire_clean_eof_vs_dead_pipe():
    msg = {"kind": "predict"}
    frame = wire.encode_frame(msg, {"x": np.zeros((4, 2), np.float32)})
    s1, s2 = socket.socketpair()
    s1.sendall(frame)
    s1.close()
    assert wire.recv_frame(s2)[0] == msg
    assert wire.recv_frame(s2) is None  # clean EOF at frame boundary
    s2.close()
    s1, s2 = socket.socketpair()
    s1.sendall(frame[:10])  # dies mid-frame: the kill -9 signature
    s1.close()
    with pytest.raises(wire.WireError, match="mid-frame"):
        wire.recv_frame(s2)
    s2.close()


def test_wire_max_frame_guard():
    s1, s2 = socket.socketpair()
    s1.sendall((wire.MAX_FRAME + 1).to_bytes(4, "big"))
    with pytest.raises(wire.WireError, match="MAX_FRAME"):
        wire.recv_frame(s2)
    s1.close()
    s2.close()


# --------------------------------------------------------------------- #
# weighted-fair queueing admission                                       #
# --------------------------------------------------------------------- #
def test_wfq_weighted_interleave_is_deterministic():
    q = WeightedFairQueue({
        "cold": TenantPolicy(weight=3.0),
        "hot": TenantPolicy(weight=1.0),
    })
    for i in range(8):
        q.push("hot", f"h{i}")
    for i in range(6):
        q.push("cold", f"c{i}")
    order = [q.pop(timeout=0)[0] for _ in range(14)]
    # over the backlogged prefix, cold gets ~3 services per hot one
    assert order[:8] == ["cold", "cold", "cold", "hot",
                         "cold", "cold", "cold", "hot"]
    assert order.count("cold") == 6 and order.count("hot") == 8
    q.close()
    assert q.pop(timeout=0) is None


def test_wfq_priority_band_drains_first():
    q = WeightedFairQueue({
        "batch": TenantPolicy(weight=10.0, priority=1),
        "live": TenantPolicy(weight=1.0, priority=0),
    })
    for i in range(3):
        q.push("batch", f"b{i}")
    for i in range(2):
        q.push("live", f"l{i}")
    order = [q.pop(timeout=0)[0] for _ in range(5)]
    assert order == ["live", "live", "batch", "batch", "batch"]
    q.close()


def test_wfq_per_tenant_bound_sheds_typed_and_deterministic():
    q = WeightedFairQueue({"hot": TenantPolicy(weight=1.0, max_queue_rows=8)})
    for i in range(4):
        q.push("hot", i, rows=2)
    with pytest.raises(ServeOverloadError) as e1:
        q.push("hot", 99, rows=2)
    # the cold tenant is unaffected by the hot tenant's full backlog
    q.push("cold", "c0", rows=2)
    assert q.n_shed == 1 and q.shed_by_tenant == {"hot": 1}
    assert e1.value.queue_rows == 8 and e1.value.max_queue_rows == 8
    # deterministic hint: same queue state, same hint
    with pytest.raises(ServeOverloadError) as e2:
        q.push("hot", 99, rows=2)
    assert e2.value.retry_after_s == e1.value.retry_after_s > 0
    q.close()


# --------------------------------------------------------------------- #
# histogram state merge (the LoadReport multi-source fix)                #
# --------------------------------------------------------------------- #
def test_hist_from_state_is_exact_inverse():
    h = Histogram.of([0.0, 0.4, 3.0, 3.1, 900.0, 2.5e-4])
    rebuilt = Histogram.from_state(h.state())
    assert rebuilt.state() == h.state()
    with pytest.raises(ValueError, match="scheme"):
        Histogram.from_state(dict(h.state(), scheme="log4"))


def test_merged_replica_states_equal_single_stream():
    rng = np.random.default_rng(7)
    stream = rng.lognormal(mean=1.0, sigma=1.2, size=4096)
    shards = np.array_split(stream, 5)  # 5 "replica processes"
    single = Histogram.of(stream)
    states = [Histogram.of(s).state() for s in shards]
    merged = Histogram()
    for st in states:
        merged.merge(Histogram.from_state(st))
    # bucket counts merge exactly; ``sum`` is float accumulation, so the
    # shard order can differ from the single stream in the last ulps
    ms, ss = merged.state(), single.state()
    assert ms["sum"] == pytest.approx(ss["sum"], rel=1e-12)
    del ms["sum"], ss["sum"]
    assert ms == ss
    p50, p99 = loadgen.merge_percentiles_ms(states)
    assert p50 == single.percentile(50.0)
    assert p99 == single.percentile(99.0)
    # and both sit within the documented bound of the exact sample
    for got, q in ((p50, 50), (p99, 99)):
        exact = float(np.percentile(stream, q, method="inverted_cdf"))
        assert abs(got - exact) <= Histogram.REL_ERROR * exact


def test_loadgen_report_ships_mergeable_state(fleet_root):
    reg = ModelRegistry(fleet_root)
    eng = ServeEngine(reg, max_batch_rows=32, min_bucket=8)
    try:
        rep = loadgen.run(eng, "acme", "km", seed=3, n_requests=8, twin=False)
    finally:
        eng.close()
    assert rep.latency_hist is not None
    assert rep.latency_hist["count"] == 8
    # the report's own percentiles ARE the state's percentiles: one
    # source of truth, merge-ready
    p50, p99 = loadgen.merge_percentiles_ms([rep.latency_hist])
    assert (p50, p99) == (rep.p50_ms, rep.p99_ms)


# --------------------------------------------------------------------- #
# ingress wire surface (stub backend — no replica processes)             #
# --------------------------------------------------------------------- #
class _StubBackend:
    """submit() contract double: sheds tenant 'hot', answers the rest."""

    def __init__(self):
        from concurrent.futures import Future

        self._Future = Future

    def submit(self, tenant, model, payload, *, version=None,
               request_id=None, session=None):
        if tenant == "hot":
            raise ServeOverloadError(
                "stub backlog full", retry_after_s=0.125,
                queue_rows=6, max_queue_rows=8,
            )
        fut = self._Future()
        fut.set_result({
            "value": np.asarray(payload).sum(axis=1),
            "degraded": False, "seq": 1, "latency_s": 0.001,
            "trace_id": request_id, "replica": 0, "flight_seq": 1,
        })
        return fut

    def stats(self):
        return {"accepted": 1, "resolved": 1, "replicas": 1}


def test_ingress_refuses_non_loopback_bind():
    with pytest.raises(ValueError, match="loopback only"):
        Ingress(_StubBackend(), host="0.0.0.0")


def test_ingress_429_and_replies_over_the_wire():
    with Ingress(_StubBackend()) as ing:
        assert ing.host == "127.0.0.1"
        with IngressClient("127.0.0.1", ing.port) as cli:
            r = cli.predict("acme", "km", np.ones((2, 5), np.float32),
                            request_id="rid-1", session="s0")
            assert r["rid"] == "rid-1" and r["trace_id"] == "rid-1"
            assert np.allclose(r["value"], 5.0)
            # the typed shed crosses the socket as 429 + Retry-After and
            # comes back as the same typed exception
            with pytest.raises(ServeOverloadError) as ei:
                cli.predict("hot", "km", np.ones((2, 5), np.float32))
            assert ei.value.retry_after_s == 0.125
            assert ei.value.max_queue_rows == 8
            assert cli.stats()["replicas"] == 1


# --------------------------------------------------------------------- #
# the process fleet                                                      #
# --------------------------------------------------------------------- #
def test_procfleet_end_to_end(fleet_root):
    """One 2-replica fleet carries the bulk of the process assertions
    (spawns are the expensive part): zero-compile hellos, golden-twin
    byte parity, sticky sessions, trace-id survival, ledger/metrics
    reconciliation."""
    fleet = ProcFleet(fleet_root, n_replicas=2,
                      warm_models=[("acme", "km", 1)],
                      max_batch_rows=32, min_bucket=8)
    try:
        # zero-compile spin-up, asserted from the hello frames
        hellos = [r.hello for r in fleet.alive()]
        assert len(hellos) == 2
        for h in hellos:
            assert h["installed"] > 0
            assert h["fuse_misses"] == 0, "warm replica traced a program"
            assert h["compile_misses"] == 0, "warm replica compiled"

        arrivals = loadgen.schedule(seed=11, n_requests=16, min_rows=1,
                                    max_rows=8)
        pays = loadgen.payloads(arrivals, 5, seed=11)
        futs = [
            fleet.submit("acme", "km", p, version=1,
                         request_id=f"rid-{i}", session=f"s{i % 3}")
            for i, p in enumerate(pays)
        ]
        fleet.flush()
        replies = [f.result() for f in futs]

        # trace ids survive the hop; replies carry the replica's flight
        # sequence for postmortem stitching
        assert [r["trace_id"] for r in replies] == \
            [f"rid-{i}" for i in range(16)]
        assert all(r["flight_seq"] >= 1 for r in replies)

        # sticky sessions: one session never changes replica
        by_session = {}
        for i, r in enumerate(replies):
            by_session.setdefault(f"s{i % 3}", set()).add(r["replica"])
        assert all(len(reps) == 1 for reps in by_session.values())
        assert len({next(iter(v)) for v in by_session.values()}) == 2

        # golden twin: single-process FleetEngine, same payloads —
        # byte-for-byte checksum agreement per reply
        twin = FleetEngine(ModelRegistry(fleet_root),
                           warm_models=[("acme", "km", 1)],
                           max_batch_rows=32, min_bucket=8)
        try:
            twin_crcs = []
            for p in pays:
                rep = twin.predict("acme", "km", p, version=1)
                twin_crcs.append(zlib.crc32(np.asarray(rep.value).tobytes()))
        finally:
            twin.close()
        fleet_crcs = [zlib.crc32(r["value"].tobytes()) for r in replies]
        assert fleet_crcs == twin_crcs

        # ledger: submit order, every rid exactly once, checksums match
        led = fleet.ledger()
        assert [rid for rid, _ in led] == [f"rid-{i}" for i in range(16)]
        assert [crc for _, crc in led] == fleet_crcs

        # aggregated /metrics: byte-parse the exposition and reconcile
        # the per-replica request counters against the reply ledger
        with FleetMetricsServer(fleet) as srv:
            with urllib.request.urlopen(srv.url + "/metrics") as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "text/plain; version=0.0.4")
                body = resp.read().decode()
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(srv.url + "/nope")
        samples = {}
        for line in body.splitlines():
            assert line, "exposition must not contain blank lines"
            if line.startswith("#"):
                parts = line.split()
                assert parts[1] in ("HELP", "TYPE")
                continue
            name, value = line.rsplit(" ", 1)
            float(value)  # every sample value parses
            samples[name] = value
        per_replica = [
            int(samples[f'heat_serve_requests_total{{replica="{r.index}"}}'])
            for r in fleet.alive()
        ]
        warmups = sum(h["warmups"] for h in hellos)
        assert sum(per_replica) == len(led) + warmups
        assert int(samples["heat_fleet_resolved_total"]) == len(led)
        assert int(samples["heat_fleet_replicas"]) == 2
    finally:
        fleet.close()


def test_replica_inherits_parent_policy_context(tmp_path, fitted):
    """aot.fingerprint() embeds the compile-key policy context, so a
    parent running a NON-default process-wide policy (here: a flipped
    collective-compression threshold) must ship that state to its
    replica processes — otherwise every child boots on defaults,
    soundly refuses the sidecar, and pays fresh compiles.  The hello
    contract must hold exactly as it does under defaults."""
    from heat_tpu.comm.compressed import (
        get_collective_threshold,
        set_collective_threshold,
    )

    prev = get_collective_threshold()
    set_collective_threshold(1 << 20)  # non-default: new context token
    try:
        root = str(tmp_path / "policy-models")
        reg = ModelRegistry(root)
        reg.publish("acme", "km", fitted["km"])
        src = ServeEngine(reg, max_batch_rows=32, min_bucket=8)
        bundles = src.export_warm("acme", "km", version=1)
        src.close()
        reg.publish_executables("acme", "km", 1, bundles)
        with ProcFleet(root, n_replicas=1,
                       warm_models=[("acme", "km", 1)],
                       max_batch_rows=32, min_bucket=8) as fleet:
            (rep,) = fleet.alive()
            assert rep.hello["installed"] == len(bundles)
            assert rep.hello["fuse_misses"] == 0
            assert rep.hello["compile_misses"] == 0
    finally:
        set_collective_threshold(prev)


def test_replica_dead_at_boot_is_raised_at_once(tmp_path):
    """A replica that exits before it connects back (on a chip: another
    process holds the accelerator; here: a model the registry does not
    have) is raised with its exit code and the end of its stderr as soon
    as it is gone — not after the whole spawn timeout spent in accept()."""
    import time

    from heat_tpu.serve import ReplicaBootError, ReplicaProc

    t0 = time.monotonic()
    with pytest.raises(ReplicaBootError) as err:
        ReplicaProc.spawn(
            0, registry_root=str(tmp_path / "empty"),
            warm_models=[("acme", "absent", 1)], spawn_timeout_s=120.0,
        )
    assert time.monotonic() - t0 < 60.0
    assert err.value.returncode not in (0, None)
    assert "absent" in err.value.stderr_tail


def test_procfleet_ingress_and_canary_over_processes(fleet_root):
    """The full door: IngressClient → asyncio ingress → WFQ → replica
    processes, with a canary rollout whose assignments match the
    single-process FleetEngine draw-for-draw (same seed ⇒ same rng
    stream ⇒ same versions cross the hop)."""
    from heat_tpu.serve import CanaryConfig

    canary = CanaryConfig("acme", "km", stable_version=1, canary_version=2,
                          fraction=0.4, seed=123)
    fleet = ProcFleet(fleet_root, n_replicas=2,
                      warm_models=[("acme", "km", 1)], canary=canary,
                      max_batch_rows=32, min_bucket=8)
    try:
        pays = [payload(2, seed=i) for i in range(12)]
        with Ingress(fleet) as ing, \
                IngressClient("127.0.0.1", ing.port) as cli:
            replies = [
                cli.predict("acme", "km", p, request_id=f"c-{i}")
                for i, p in enumerate(pays)
            ]
        assert [r["trace_id"] for r in replies] == \
            [f"c-{i}" for i in range(12)]
        # draw-for-draw canary agreement with the in-process twin
        twin = FleetEngine(ModelRegistry(fleet_root), canary=canary,
                           max_batch_rows=32, min_bucket=8)
        try:
            for p in pays:
                twin.predict("acme", "km", p)
        finally:
            twin.close()
        assert fleet.assignments == twin.assignments
        assert fleet.n_canary + fleet.n_stable == 12
        assert fleet.n_canary == twin.n_canary
    finally:
        fleet.close()


def test_procfleet_kill9_requeues_and_ledger_replays(fleet_root):
    """kill -9 one replica mid-stream, twice: every accepted request is
    answered exactly once (nothing lost, nothing double-answered), and
    the fleet reply ledger is byte-identical across the replays."""
    def scenario():
        fleet = ProcFleet(fleet_root, n_replicas=2,
                          warm_models=[("acme", "km", 1)],
                          max_batch_rows=32, min_bucket=8)
        try:
            arrivals = loadgen.schedule(seed=5, n_requests=24, min_rows=1,
                                        max_rows=8)
            pays = loadgen.payloads(arrivals, 5, seed=5)
            futs = []
            for i, p in enumerate(pays):
                futs.append(fleet.submit("acme", "km", p, version=1,
                                         session=f"s{i % 3}"))
                if i == 8:
                    fleet.kill_replica(0)
            fleet.flush(timeout_s=180)
            for f in futs:
                f.result()  # every accepted request answered
            st = fleet.stats()
            return fleet.ledger(), fleet.checksum(), st
        finally:
            fleet.close()

    led1, crc1, st1 = scenario()
    led2, crc2, st2 = scenario()
    assert st1["replica_losses"] == 1 and st1["respawns"] == 1
    assert st1["requeued"] >= 1
    assert len(led1) == 24
    assert len({rid for rid, _ in led1}) == 24  # exactly-once
    assert led1 == led2 and crc1 == crc2
    inc = [i for i in incidents.incident_log() if i.kind == "replica-loss"]
    assert inc and "re-queued" in inc[0].detail


def test_procfleet_two_tenant_starvation(fleet_root):
    """A hot tenant saturating its WFQ share sheds against its own
    bound; the cold tenant's trickle is admitted in full, never shed,
    and completes with a bounded p99."""
    fleet = ProcFleet(
        fleet_root, n_replicas=2,
        warm_models=[("acme", "km", 1)],
        tenants={
            "hot": TenantPolicy(weight=1.0, max_queue_rows=16),
            "cold": TenantPolicy(weight=4.0),
        },
        max_batch_rows=32, min_bucket=8,
    )
    try:
        cold_futs, hot_shed, hot_futs = [], 0, []
        for i in range(30):
            # 10:1 hot:cold pressure, hot rows large enough to backlog
            for _ in range(10):
                try:
                    hot_futs.append(
                        fleet.submit("hot", "km", payload(8, seed=i)))
                except ServeOverloadError:
                    hot_shed += 1
            cold_futs.append(
                fleet.submit("cold", "km", payload(2, seed=100 + i)))
        fleet.flush(timeout_s=180)
        assert hot_shed > 0, "hot tenant never hit its WFQ bound"
        assert fleet.wfq.shed_by_tenant.get("cold", 0) == 0
        cold = [f.result() for f in cold_futs]
        assert len(cold) == 30
        lat = loadgen.latency_hist_ms([r["latency_s"] for r in cold])
        # bounded: the cold p99 stays in interactive territory even with
        # 10x hot pressure (generous CI headroom; an unbounded starve
        # would park cold requests behind the full hot backlog)
        assert lat.percentile(99.0) < 5_000.0
    finally:
        fleet.close()
