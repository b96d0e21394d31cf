#!/usr/bin/env bash
# The reference CI runs the same suite under MPI world sizes 1..4 and 7
# (.travis.yml:17-21); here the analog is the virtual-device count of the
# CPU mesh.  Usage: scripts/run_test_matrix.sh [sizes...]
# Default covers 1/2/4 plus the awkward primes 3 and 7 (uneven shards).
set -uo pipefail
cd "$(dirname "$0")/.."
sizes=("$@")
[ $# -eq 0 ] && sizes=(1 2 3 4 7)
fail=0
echo "=== spmdlint (static SPMD-correctness gate, docs/lint.md) ==="
# cold vs warm: first run repopulates the findings cache from scratch,
# second run should be mostly cache hits — both wall times are printed by
# the CLI ("[N.NNs, cache H hit, M miss]") for the CI log
rm -rf .spmdlint-cache
echo "--- cold (no cache) ---"
if ! python scripts/spmdlint.py --baseline; then
    echo "FAILED spmdlint"
    fail=1
fi
echo "--- warm (cached) ---"
if ! python scripts/spmdlint.py --baseline -q; then
    echo "FAILED spmdlint (warm rerun disagrees with cold run)"
    fail=1
fi
# static comm-cost report artifact: splitflow-modeled wire bytes per
# function, priced with the runtime cost model (docs/lint.md)
cost_dir="${HEAT_TELEMETRY_ARTIFACT_DIR:-/tmp/heat-telemetry-artifacts}"
mkdir -p "$cost_dir"
if ! python scripts/spmdlint.py --cost-report --format=json \
        heat_tpu tests > "$cost_dir/spmd-cost-report.json"; then
    echo "FAILED spmdlint --cost-report"
    fail=1
else
    echo "cost report artifact: $cost_dir/spmd-cost-report.json"
fi
echo "=== fuse dispatch-count gate (one dispatch per fused pipeline) ==="
if ! python -m pytest tests/test_fuse.py -q -k "dispatch or single_dispatch"; then
    echo "FAILED fuse dispatch-count gate"
    fail=1
fi
echo "=== compressed collectives (parity, error bounds, policy routing) ==="
if ! python -m pytest tests/test_compressed_collectives.py -q; then
    echo "FAILED compressed collectives"
    fail=1
fi
# chaos lane: the resilience suite under a seeded fault schedule.  The
# whole injected schedule is a pure function of HEAT_CHAOS_SEED (export a
# different value to explore other schedules; every failure reproduces
# exactly by re-running with the printed seed).  Includes the
# resume-equivalence gate: preempted+resumed fits must be bitwise-equal
# to uninterrupted ones.
echo "=== chaos lane (seed=${HEAT_CHAOS_SEED:-0}: fault injection, guards, resume) ==="
if ! HEAT_CHAOS_SEED="${HEAT_CHAOS_SEED:-0}" python -m pytest tests/test_resilience.py -q; then
    echo "FAILED chaos lane (reproduce with HEAT_CHAOS_SEED=${HEAT_CHAOS_SEED:-0})"
    fail=1
fi
# elastic lane: the kill→shrink→recover cycle end-to-end under the same
# seeded chaos schedule — device loss at mesh {8→4, 4→2, 2→1} (plus the
# non-divisible 8→7 fallback) across Lasso-gd/Lasso-gd-int8/KMeans/
# lanczos, with the bitwise-vs-uninterrupted-twin gate, the retry
# engine's seeded backoff, and the deadline watchdog (docs/design.md §15)
echo "=== elastic lane (seed=${HEAT_CHAOS_SEED:-0}: device loss, mesh shrink, recovery) ==="
if ! HEAT_CHAOS_SEED="${HEAT_CHAOS_SEED:-0}" python -m pytest tests/test_elastic.py -q; then
    echo "FAILED elastic lane (reproduce with HEAT_CHAOS_SEED=${HEAT_CHAOS_SEED:-0})"
    fail=1
fi
# telemetry lane: a tier-1 smoke slice with collection armed process-wide
# (HEAT_TELEMETRY=1) — proves the instrumented hot paths stay green with
# spans/counters live and archives the event stream as a CI artifact
# (docs/design.md §13)
tel_dir="${HEAT_TELEMETRY_ARTIFACT_DIR:-/tmp/heat-telemetry-artifacts}"
mkdir -p "$tel_dir"
echo "=== telemetry lane (HEAT_TELEMETRY=1 smoke; artifacts in $tel_dir) ==="
if ! HEAT_TELEMETRY=1 \
     HEAT_TELEMETRY_JSONL="$tel_dir/events.jsonl" \
     python -m pytest tests/test_telemetry.py tests/test_fuse.py \
         tests/test_compressed_collectives.py tests/test_compile_cache.py -q; then
    echo "FAILED telemetry lane"
    fail=1
fi
echo "--- telemetry artifacts ---"
ls -l "$tel_dir" 2>/dev/null || true
# redistribution lane: the full planned-vs-monolithic parity matrix plus
# a CPU smoke asserting the planner's modeled wire bytes never
# exceed the monolithic envelope and the modeled peak respects the
# max_live_bytes bound (docs/design.md §14)
echo "=== redistribution lane (planner parity matrix + cost-model smoke) ==="
if ! python -m pytest tests/test_redistribute.py -q; then
    echo "FAILED redistribution parity matrix"
    fail=1
fi
if ! python - <<'PY'
from heat_tpu.comm import redistribute as rd

for shape, src, dst, p in [
    ((2048, 512), 0, 1, 8),
    ((2048, 512), 1, 0, 4),
    ((4096, 4096), 0, 1, 2),
    ((64, 32, 16), 0, 2, 8),
]:
    mono = rd.monolithic_model(shape, "float32", src, dst, p)
    bound = mono["peak_live_bytes"]
    # plan() raises ValueError if the schedule cannot fit the bound
    pl = rd.plan(shape, "float32", src, dst, p, max_live_bytes=bound)
    assert pl.wire_bytes <= mono["wire_bytes"], (shape, src, dst, p)
    assert pl.peak_live_bytes <= bound, (shape, src, dst, p)
print("redistribution cost-model smoke: planned wire <= monolithic, "
      "peak <= max_live_bytes for all probes")
PY
then
    echo "FAILED redistribution cost-model smoke"
    fail=1
fi
# serve lane: multi-tenant micro-batched serving (docs/design.md §17) —
# registry/batcher/engine invariants (bitwise batched==unbatched parity,
# one compiled dispatch per micro-batch, degrade isolation), then the
# chaos scenario: a fault plan armed over the seeded open-loop generator
# must poison exactly the requests it hits, and the degraded set +
# reply checksum must replay as a pure function of HEAT_CHAOS_SEED
echo "=== serve lane (seed=${HEAT_CHAOS_SEED:-0}: parity, dispatch gate, poisoned-request isolation) ==="
if ! HEAT_CHAOS_SEED="${HEAT_CHAOS_SEED:-0}" python -m pytest tests/test_serve.py -q; then
    echo "FAILED serve lane (reproduce with HEAT_CHAOS_SEED=${HEAT_CHAOS_SEED:-0})"
    fail=1
fi
if ! HEAT_CHAOS_SEED="${HEAT_CHAOS_SEED:-0}" python - <<'PY'
import tempfile
import numpy as np
import heat_tpu as ht
from heat_tpu import resilience
from heat_tpu.serve import ModelRegistry, ServeEngine, loadgen

rng = np.random.default_rng(0)
km = ht.cluster.KMeans(n_clusters=3, max_iter=5, random_state=0)
km.fit(ht.array(rng.normal(size=(64, 5)).astype(np.float32), split=0))
reg = ModelRegistry(tempfile.mkdtemp(prefix="heat-serve-lane-"))
reg.publish("ci", "km", km)
eng = ServeEngine(reg, max_batch_rows=64, min_bucket=8)
# seed=None -> HEAT_CHAOS_SEED drives arrivals, payloads, AND the plan
with resilience.inject("nonfinite", rate=0.25, seed=loadgen.chaos_seed()):
    a = loadgen.run(eng, "ci", "km", n_requests=32, twin=True)
with resilience.inject("nonfinite", rate=0.25, seed=loadgen.chaos_seed()):
    b = loadgen.run(eng, "ci", "km", n_requests=32, twin=False)
assert a.degraded == b.degraded, (a.degraded, b.degraded)
assert a.checksum == b.checksum, (a.checksum, b.checksum)
assert a.twin["bitwise_equal"], "batched replies diverged from unbatched twin"
assert a.dispatches_per_batch == 1.0, a.dispatches_per_batch
eng.close()
print(f"serve chaos scenario: {len(a.degraded)}/32 requests poisoned "
      f"(degraded={a.degraded}), batch-mates bitwise-exact, "
      f"checksum replayed, one dispatch per micro-batch")
PY
then
    echo "FAILED serve chaos scenario (reproduce with HEAT_CHAOS_SEED=${HEAT_CHAOS_SEED:-0})"
    fail=1
fi
# autoscale lane (docs/design.md §22): fleet elasticity under chaos —
# the fleet suite (watermark hysteresis, warm zero-compile scale-ups,
# canary bitwise parity, close contract), then the scale-event scenario
# replayed twice: a canaried fleet served while devices arrive and die
# on seeded schedules must produce an identical (tick ledger,
# scale-event log, canary assignment) triple both times — the whole
# elastic history is a pure function of HEAT_CHAOS_SEED
echo "=== autoscale lane (seed=${HEAT_CHAOS_SEED:-0}: watermarks, warm replicas, canary, chaos replay) ==="
if ! HEAT_CHAOS_SEED="${HEAT_CHAOS_SEED:-0}" python -m pytest tests/test_fleet.py -q; then
    echo "FAILED autoscale lane (reproduce with HEAT_CHAOS_SEED=${HEAT_CHAOS_SEED:-0})"
    fail=1
fi
if ! HEAT_CHAOS_SEED="${HEAT_CHAOS_SEED:-0}" python - <<'PY'
import tempfile
import numpy as np
import heat_tpu as ht
from heat_tpu.resilience import faults
from heat_tpu.serve import (CanaryConfig, FleetEngine, ModelRegistry,
                            WatermarkAutoscaler, loadgen)

rng = np.random.default_rng(0)
X = ht.array(rng.normal(size=(64, 5)).astype(np.float32), split=0)
km = ht.cluster.KMeans(n_clusters=3, max_iter=5, random_state=0)
km.fit(X)
km2 = ht.cluster.KMeans(n_clusters=3, max_iter=7, random_state=1)
km2.fit(X)
reg = ModelRegistry(tempfile.mkdtemp(prefix="heat-autoscale-lane-"))
reg.publish("ci", "km", km)
reg.publish("ci", "km", km2)
seed = loadgen.chaos_seed()

def scenario():
    # seed=None on the canary -> HEAT_CHAOS_SEED drives the slice, and
    # the armed fault plans replay arrivals/losses on the same seed
    can = CanaryConfig(tenant="ci", model="km", stable_version=1,
                       canary_version=2, fraction=0.3)
    auto = WatermarkAutoscaler(low=1, high=8, hysteresis=2,
                               min_replicas=1, max_replicas=3)
    fleet = FleetEngine(reg, canary=can, autoscaler=auto,
                        max_batch_rows=32, min_bucket=8)
    ledger = []
    with faults.inject("device_arrival", site="fleet.tick", nth=2, rank=1,
                       seed=seed):
        with faults.inject("device_loss", site="fleet.tick", nth=4, rank=0,
                           seed=seed):
            for step in range(6):
                for s in range(3):
                    p = np.random.default_rng([seed, step * 3 + s]).normal(
                        size=(4, 5)).astype(np.float32)
                    fleet.predict("ci", "km", p)
                rec = fleet.tick(queue_depth=10 if step < 3 else 0)
                ledger.append((rec["decision"], rec["replicas"]))
    events = [(e["action"], e["cause"], e["replicas"])
              for e in fleet.scale_events]
    out = (tuple(ledger), tuple(events), tuple(fleet.assignments))
    fleet.close()
    return out

a, b = scenario(), scenario()
assert a == b, "scale-event scenario diverged across identical-seed replays"
actions = [e[0] for e in a[1]]
assert "scale-up" in actions and "replica-loss" in actions, actions
print(f"autoscale chaos scenario (seed={seed}): {len(a[0])} ticks, "
      f"events={actions}, canary slice {sum(a[2])}/{len(a[2])} — "
      f"ledger+events+assignments replayed bit-for-bit")
PY
then
    echo "FAILED autoscale chaos scenario (reproduce with HEAT_CHAOS_SEED=${HEAT_CHAOS_SEED:-0})"
    fail=1
fi
# obs lane (docs/design.md §19): the request-scoped observability suite,
# then a /metrics scrape of a LIVE ServeEngine (Prometheus text parsed
# and byte-compared against telemetry.snapshot())
echo "=== obs lane (tracing, histograms, SLO burn, flight recorder, /metrics) ==="
if ! HEAT_CHAOS_SEED="${HEAT_CHAOS_SEED:-0}" python -m pytest tests/test_obs.py -q; then
    echo "FAILED obs suite (reproduce with HEAT_CHAOS_SEED=${HEAT_CHAOS_SEED:-0})"
    fail=1
fi
if ! python - <<'PY'
import json
import tempfile
import urllib.request

import numpy as np

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.serve import ModelRegistry, ServeEngine, loadgen

telemetry.enable()
telemetry.reset()
rng = np.random.default_rng(0)
km = ht.cluster.KMeans(n_clusters=3, max_iter=5, random_state=0)
km.fit(ht.array(rng.normal(size=(64, 5)).astype(np.float32), split=0))
reg = ModelRegistry(tempfile.mkdtemp(prefix="heat-obs-lane-"))
reg.publish("ci", "km", km)
eng = ServeEngine(reg, max_batch_rows=64, min_bucket=8)
loadgen.run(eng, "ci", "km", n_requests=16, twin=False)
srv = eng.start_metrics_server()  # 127.0.0.1, ephemeral port
text = urllib.request.urlopen(srv.url + "/metrics").read().decode()
assert urllib.request.urlopen(srv.url + "/healthz").read() == b"ok\n"
varz = json.loads(urllib.request.urlopen(srv.url + "/varz").read())
assert varz["serve"]["requests"] == 16, varz["serve"]

# parse the Prometheus text exposition and byte-compare every counter
# sample against the snapshot the registry reports directly
samples = {}
for line in text.splitlines():
    if line.startswith("#") or not line.strip():
        continue
    name, _, value = line.partition(" ")
    samples[name] = value
snap = telemetry.snapshot()
from heat_tpu.telemetry.httpz import _fmt, sanitize_metric_name
checked = 0
for cname, cval in snap["counters"].items():
    m = sanitize_metric_name(cname) + "_total"
    assert m in samples, f"counter {cname} missing from /metrics as {m}"
    assert samples[m] == _fmt(cval), (m, samples[m], cval)
    checked += 1
assert checked > 0 and "heat_serve_requests_total" in samples
eng.close()
telemetry.disable()
telemetry.reset()
print(f"/metrics scrape: {checked} counters byte-identical to snapshot(), "
      f"healthz ok, varz live ({len(samples)} samples total)")
PY
then
    echo "FAILED /metrics scrape smoke"
    fail=1
fi
# overlap lane: the latency-hiding policy (docs/design.md §18) — every
# double-buffered ring against its same-run serial twin at byte
# granularity, then the compressed + redistribution suites re-run with
# the policy forced "on" process-wide: the whole tree must be
# schedule-agnostic, not just the dedicated parity tests
echo "=== overlap lane (double-buffered rings vs serial twins, bitwise) ==="
if ! python -m pytest tests/test_overlap.py -q; then
    echo "FAILED overlap twin parity"
    fail=1
fi
if ! python - <<'PY'
import os
n = os.environ.get("HEAT_TEST_DEVICES", "8")
flag = f"--xla_force_host_platform_device_count={n}"
if flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()

from heat_tpu.comm.overlap import set_overlap

set_overlap("on")  # force the double-buffered schedule for the whole run
import sys

import pytest

raise SystemExit(pytest.main([
    "tests/test_compressed_collectives.py", "tests/test_redistribute.py",
    "-q", "-p", "no:cacheprovider",
]))
PY
then
    echo "FAILED overlap lane (suite under set_overlap('on'))"
    fail=1
fi
# mesh2d lane (docs/design.md §20): the 2-D grid suite — splits-tuple
# layouts and the split compat view, grid SUMMA against its
# panel-ordered replicated twin (bitwise), the one-dispatch and
# telemetry-matches-wire-model gates, and the factored per-mesh-axis
# redistribution plans — on BOTH grid shapes: 4 devices exercises the
# 2x2 mesh (2x4 tests self-skip), 8 devices exercises 2x2 AND 2x4.
# Then the 1-D matmul + redistribute parity suites re-run on the
# default mesh to prove the splits-tuple refactor left every legacy
# 1-D layout bit-identical, and the spmdlint baseline gate re-runs so
# the splits-tuple transfer rules (SPMD503 on tuple layouts) hold a
# zero-findings tree.
echo "=== mesh2d lane (2x2 + 2x4 grids: SUMMA twins, 2-D plans, compat view) ==="
for n in 4 8; do
    if ! HEAT_TEST_DEVICES="$n" python -m pytest tests/test_mesh2d.py -q; then
        echo "FAILED mesh2d suite at $n devices"
        fail=1
    fi
done
if ! python -m pytest tests/test_matmul_matrix.py tests/test_redistribute.py -q; then
    echo "FAILED 1-D parity suites under the splits-tuple refactor"
    fail=1
fi
if ! python scripts/spmdlint.py --baseline -q; then
    echo "FAILED spmdlint baseline with splits-tuple rules"
    fail=1
fi
# autoshard lane (docs/design.md §21): cost-driven auto-layout — every
# splitflow fixture pipeline bitwise-equal to its hand-layout twin, one
# dispatch at steady state, the modeled-cost-never-exceeds-hand bound,
# and the wire-ledger oracle (telemetry bytes for a solved call ==
# plan's modeled bytes BYTE-FOR-BYTE, both directions, at every mesh
# size) — at 4 and 8 devices.  Then the spmdlint baseline gate re-runs
# so SPMD505 (hand-placed resplit inside an autoshard-wrapped function)
# holds a zero-findings tree.
echo "=== autoshard lane (solver twins, one-dispatch gate, ledger oracle) ==="
for n in 4 8; do
    if ! HEAT_TEST_DEVICES="$n" python -m pytest tests/test_autoshard.py \
            tests/test_cost_properties.py -q; then
        echo "FAILED autoshard suite at $n devices"
        fail=1
    fi
done
if ! python scripts/spmdlint.py --baseline -q; then
    echo "FAILED spmdlint baseline with SPMD505 (autoshard hand-layout rule)"
    fail=1
fi
# linalg2d lane (docs/design.md §23): pod-scale grid linear algebra —
# the blocked/CAQR QR and QDWH polar SVD suites with their bitwise
# replicated-golden twins, serial-vs-overlap arm pinning, one-dispatch
# and ledger==wire-model gates, the ill-conditioned QDWH sweep, the
# rank-local SUMMA schedules, the wide-input/shard-geometry guards, and
# the host-sync-free norm() — at 4 devices (2x2 grid; 2x4 tests
# self-skip) and 8 (2x2 AND 2x4).  Then the splitflow suites re-run so
# the entry_qr/entry_svd grid transfer facts hold the registry oracle
# and a zero-findings tree.
echo "=== linalg2d lane (grid QR/SVD golden twins, QDWH sweep, rank-local SUMMA) ==="
for n in 4 8; do
    if ! HEAT_TEST_DEVICES="$n" python -m pytest tests/test_linalg2d.py -q; then
        echo "FAILED linalg2d suite at $n devices"
        fail=1
    fi
done
if ! python -m pytest tests/test_splitflow.py tests/test_splitflow_oracle.py -q; then
    echo "FAILED splitflow suites with the entry_qr/grid-svd transfer facts"
    fail=1
fi
# stream lane (docs/design.md §24): out-of-core streaming fits — chunk
# geometry/ragged tails, prefetch-on==prefetch-off bitwise, mini-batch
# KMeans/Lasso vs their in-memory twins, the one-dispatch-per-segment
# and slab-peak-vs-model gates, kill/resume (elastic 4<->8 included) —
# at 4 and 8 devices.  Then the chaos scenario: a transient OSError on
# the chunk-read seam mid-stream PLUS a device loss at a segment
# boundary with an elastic resume, replayed twice — the healed, resumed
# trajectory (center bytes + incident sites) must be a pure function of
# HEAT_CHAOS_SEED and bitwise-equal to the uninterrupted twin.
echo "=== stream lane (seed=${HEAT_CHAOS_SEED:-0}: prefetch twins, ragged tails, mid-stream resume) ==="
for n in 4 8; do
    if ! HEAT_TEST_DEVICES="$n" HEAT_CHAOS_SEED="${HEAT_CHAOS_SEED:-0}" \
            python -m pytest tests/test_stream.py -q; then
        echo "FAILED stream suite at $n devices"
        fail=1
    fi
done
if ! HEAT_CHAOS_SEED="${HEAT_CHAOS_SEED:-0}" python - <<'PY'
import os
import tempfile

import numpy as np

import heat_tpu as ht
from heat_tpu.io import stream
from heat_tpu.resilience import faults, incidents
from heat_tpu.resilience import retry as retry_mod
from heat_tpu.resilience.faults import DeviceLossError

seed = int(os.environ.get("HEAT_CHAOS_SEED", "0"))
rng = np.random.default_rng(seed)
data = rng.normal(size=(103, 6)).astype(np.float32)
# the armed schedule is a pure function of the seed: which chunk read
# takes the transient OSError and which segment boundary loses a device
mb, h = 16, -(-103 // 16)
io_nth = 1 + int(rng.integers(h))          # first-epoch chunk read
kill_nth = 1 + int(rng.integers(2, h - 1))  # checkpointed boundary


def scenario():
    faults.clear()
    incidents.clear_incident_log()
    retry_mod.set_sleep(lambda s: None)
    ck = os.path.join(tempfile.mkdtemp(prefix="heat-stream-lane-"), "km.h5")
    kw = dict(n_clusters=4, mini_batch=mb, max_iter=3, random_state=1)
    clean = ht.cluster.KMeans(**kw).fit(stream.ArraySource(data))
    est = ht.cluster.KMeans(checkpoint_every=1, checkpoint_path=ck, **kw)
    try:
        with faults.inject("io_error", site="stream.read", nth=io_nth,
                           max_faults=1, seed=seed):
            with faults.inject("device_loss", site="iteration",
                               nth=kill_nth, seed=seed):
                est.fit(stream.ArraySource(data))
        raise AssertionError("armed device loss never fired")
    except DeviceLossError:
        pass
    est2 = ht.cluster.KMeans(checkpoint_every=1, checkpoint_path=ck, **kw)
    est2.fit(stream.ArraySource(data), resume="elastic")
    bits = np.ascontiguousarray(
        np.asarray(est2.cluster_centers_.larray)).tobytes()
    twin = np.ascontiguousarray(
        np.asarray(clean.cluster_centers_.larray)).tobytes()
    assert bits == twin, "resumed stream fit diverged from uninterrupted twin"
    sites = tuple(getattr(i, "site", "") for i in incidents.incident_log())
    faults.clear()
    retry_mod.set_sleep(None)
    return bits, sites


a, b = scenario(), scenario()
assert a == b, "stream chaos scenario diverged across identical-seed replays"
assert any("io.stream.read" in s for s in a[1]), a[1]
print(f"stream chaos scenario (seed={seed}): OSError healed at chunk "
      f"{io_nth}, device lost at segment {kill_nth}, elastic resume "
      f"bitwise-equal to twin; incidents={a[1]} replayed bit-for-bit")
PY
then
    echo "FAILED stream chaos scenario (reproduce with HEAT_CHAOS_SEED=${HEAT_CHAOS_SEED:-0})"
    fail=1
fi
# procfleet lane (docs/design.md §25): the multi-process serving plane —
# the wire protocol / WFQ / ingress / replica-process suite, then two
# inline scenarios: (1) the 1→2→4 replica-process scaling sweep with the
# single-process FleetEngine twin CRC gate and the zero-compile hello
# assertion at every fleet size, (2) a kill -9 of a live replica
# mid-stream, replayed twice — un-acked requests re-queued to survivors,
# a warm respawn, and a reply ledger that is a pure function of
# HEAT_CHAOS_SEED (identical across both replays, no lost or
# double-answered request).
echo "=== procfleet lane (seed=${HEAT_CHAOS_SEED:-0}: wire, WFQ, ingress, replica processes) ==="
if ! HEAT_CHAOS_SEED="${HEAT_CHAOS_SEED:-0}" python -m pytest tests/test_procfleet.py -q; then
    echo "FAILED procfleet suite (reproduce with HEAT_CHAOS_SEED=${HEAT_CHAOS_SEED:-0})"
    fail=1
fi
if ! HEAT_CHAOS_SEED="${HEAT_CHAOS_SEED:-0}" python - <<'PY'
import tempfile
import zlib

import numpy as np

import heat_tpu as ht
from heat_tpu.serve import (FleetEngine, ModelRegistry, ProcFleet,
                            ServeEngine, loadgen)

rng = np.random.default_rng(0)
km = ht.cluster.KMeans(n_clusters=3, max_iter=5, random_state=0)
km.fit(ht.array(rng.normal(size=(64, 5)).astype(np.float32), split=0))
root = tempfile.mkdtemp(prefix="heat-procfleet-lane-")
reg = ModelRegistry(root)
reg.publish("ci", "km", km)
src = ServeEngine(reg, max_batch_rows=32, min_bucket=8)
bundles = src.export_warm("ci", "km", version=1)
src.close()
reg.publish_executables("ci", "km", 1, bundles)
seed = loadgen.chaos_seed()
arrivals = loadgen.schedule(seed, n_requests=24, min_rows=1, max_rows=16)
pays = loadgen.payloads(arrivals, 5, seed=seed)
rows = sum(a.rows for a in arrivals)

import time
pps = {}
crcs = None
for n in (1, 2, 4):
    with ProcFleet(root, n_replicas=n, warm_models=[("ci", "km", 1)],
                   max_batch_rows=32, min_bucket=8) as fleet:
        for rep in fleet.alive():
            assert rep.hello["fuse_misses"] == 0, rep.hello
            assert rep.hello["compile_misses"] == 0, rep.hello
        t0 = time.perf_counter()
        futs = [fleet.submit("ci", "km", p, version=1) for p in pays]
        fleet.flush()
        pps[n] = rows / (time.perf_counter() - t0)
        for f in futs:
            f.result()
        if n == 1:
            crcs = [c for _, c in fleet.ledger()]
twin = FleetEngine(reg, warm_models=[("ci", "km", 1)],
                   max_batch_rows=32, min_bucket=8)
twin_crcs = [zlib.crc32(np.asarray(
    twin.predict("ci", "km", p, version=1).value).tobytes()) for p in pays]
twin.close()
assert crcs == twin_crcs, "fleet replies diverged from single-process twin"
eff = {n: pps[n] / (n * pps[1]) for n in pps}
print(f"procfleet scaling sweep (seed={seed}): "
      + ", ".join(f"{n}x={pps[n]:.0f} pps (eff {eff[n]:.2f})"
                  for n in sorted(pps))
      + "; twin CRC gate held, every hello zero-compile")
PY
then
    echo "FAILED procfleet scaling sweep (reproduce with HEAT_CHAOS_SEED=${HEAT_CHAOS_SEED:-0})"
    fail=1
fi
if ! HEAT_CHAOS_SEED="${HEAT_CHAOS_SEED:-0}" python - <<'PY'
import tempfile

import numpy as np

import heat_tpu as ht
from heat_tpu.resilience import incidents
from heat_tpu.serve import ModelRegistry, ProcFleet, ServeEngine, loadgen

rng = np.random.default_rng(0)
km = ht.cluster.KMeans(n_clusters=3, max_iter=5, random_state=0)
km.fit(ht.array(rng.normal(size=(64, 5)).astype(np.float32), split=0))
root = tempfile.mkdtemp(prefix="heat-procfleet-chaos-")
reg = ModelRegistry(root)
reg.publish("ci", "km", km)
src = ServeEngine(reg, max_batch_rows=32, min_bucket=8)
reg.publish_executables("ci", "km", 1, src.export_warm("ci", "km", version=1))
src.close()
seed = loadgen.chaos_seed()
arrivals = loadgen.schedule(seed, n_requests=24, min_rows=1, max_rows=8)
pays = loadgen.payloads(arrivals, 5, seed=seed)


def scenario():
    incidents.clear_incident_log()
    with ProcFleet(root, n_replicas=2, warm_models=[("ci", "km", 1)],
                   max_batch_rows=32, min_bucket=8) as fleet:
        victim = fleet.alive()[0].index
        futs = []
        for i, p in enumerate(pays):
            futs.append(fleet.submit("ci", "km", p, version=1,
                                     request_id=f"rid-{i}"))
            if i == 8:
                fleet.kill_replica(victim)  # SIGKILL, mid-stream
        fleet.flush(timeout_s=180)
        for f in futs:
            f.result()
        st = fleet.stats()
        assert st["replica_losses"] == 1 and st["respawns"] == 1, st
        assert st["requeued"] >= 1, st
        led = fleet.ledger()
        assert len(led) == len(pays) == len({rid for rid, _ in led})
        return led, fleet.checksum()


a, b = scenario(), scenario()
assert a == b, "kill -9 scenario diverged across identical-seed replays"
print(f"procfleet kill -9 chaos (seed={seed}): replica SIGKILLed "
      f"mid-stream, un-acked re-queued to survivor, warm respawn, "
      f"{len(a[0])} replies — ledger+checksum replayed bit-for-bit")
PY
then
    echo "FAILED procfleet kill -9 chaos (reproduce with HEAT_CHAOS_SEED=${HEAT_CHAOS_SEED:-0})"
    fail=1
fi
# hardening lane (docs/design.md §26): fault-domain hardening of the
# serving plane — deadlines/hedges/breakers/drains suite PLUS the slow
# gray-failure chaos scenario (straggler + stalled socket + corrupt
# frame + deadline shed + hedge-cancel + drain + kill -9, all seeded,
# disposition ledger replayed twice bit-for-bit).  The chaos test
# carries the `slow` marker and is excluded from the tier-1 gate, so
# this lane runs the file WITHOUT a marker filter to pull it in.
echo "=== hardening lane (seed=${HEAT_CHAOS_SEED:-0}: deadlines, hedges, breakers, drains, gray-failure chaos) ==="
if ! HEAT_CHAOS_SEED="${HEAT_CHAOS_SEED:-0}" python -m pytest tests/test_procfleet_hardening.py -q; then
    echo "FAILED hardening lane (reproduce with HEAT_CHAOS_SEED=${HEAT_CHAOS_SEED:-0})"
    fail=1
fi
for n in "${sizes[@]}"; do
    echo "=== mesh size $n ==="
    if ! HEAT_TEST_DEVICES="$n" python -m pytest tests/ -q -x; then
        echo "FAILED at mesh size $n"
        fail=1
    fi
done
exit $fail
