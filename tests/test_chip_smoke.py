"""CPU rehearsal of ``chip_smoke.py``: its phase functions at toy sizes on the
virtual mesh, and the script itself refusing to run off the chip.

The rehearsal finds wrong paths, arguments and control flow at no chip time.
It proves nothing about the chip: the kernel-evidence checks are *expected*
to fail here (off the TPU the library takes its XLA fallbacks), which is the
proof that the smoke tells the two apart.  Where a phase needs the Pallas
path, the test steers interpret mode itself; the script has no option for it.
"""

from __future__ import annotations

import functools
import gc
import importlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import heat_tpu as ht

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

SEED = 0


def _failed(line):
    return sorted(k for k, c in line["checks"].items() if not c["ok"])


def _complete(line):
    """What every phase line carries."""
    assert line["sizes"] and line["reference"] and line["checks"]
    assert "device_dtypes" in line
    json.dumps(line)  # one JSON line


@pytest.fixture(scope="module")
def moments():
    """The phase's line, its array, and the bytes by dtype that were alive on
    this process's devices BEFORE the phase ran.  ``device_dtypes()`` reads
    ``jax.live_arrays()`` of the whole process (right for the script, whose
    child runs nothing else), and an xdist worker that ran another file first
    still holds what that file left alive: int64 arrays after ``test_obs.py``
    (``pytest tests/test_obs.py tests/test_chip_smoke.py -p xdist -n 1 --dist
    loadfile`` failed on them), ``test_serve.py``, ``test_fleet.py``,
    ``test_procfleet.py`` or ``test_stream.py``."""
    gc.collect()
    inherited = chip_smoke.device_dtypes()
    line, X = chip_smoke.phase_moments(SEED, n=4096, f=32, wide=(300, 640))
    return line, X, inherited


@pytest.fixture(scope="module")
def kmeans(moments):
    return chip_smoke.phase_kmeans(moments[1], SEED, k=8, iters=5, n_ref=2048)


def test_moments_phase(moments):
    line, X, inherited = moments
    _complete(line)
    assert _failed(line) == []
    assert X.shape == (4096, 32) and X.split == 0
    # the CPU mesh: the one-read kernel is a one-TPU process's (tests/test_colvar.py)
    assert line["variance_form"]["tall"] == line["variance_form"]["wide"] == ["two_pass"]
    # what the PHASE put on the device, whatever the worker held before it
    added = {k for k, v in line["device_dtypes"].items() if v > inherited.get(k, 0)}
    assert added == {"float32"}


def test_kmeans_phase_and_its_64_bit_labels_are_seen(kmeans):
    line, km = kmeans
    _complete(line)
    assert _failed(line) == []
    assert km.n_iter_ == 5
    assert "int64" in line["device_dtypes"]  # labels_: what Motivation 8 asked to see
    assert line["routes"] == {"layout": ["rows"]}  # 4096 x 32: tall and narrow


@pytest.mark.parametrize(
    "phase,sizes",
    [
        (chip_smoke.phase_cdist, dict(n=512, f=18, block=64)),
        (chip_smoke.phase_spectral, dict(n=512, f=18, k=8, m=64)),
        (chip_smoke.phase_lasso, dict(n=8192, f=32, sweeps=10)),
        (chip_smoke.phase_qr_svd, dict(m=4096, n=64)),
    ],
    ids=["cdist", "spectral", "lasso", "qr_svd"],
)
def test_phase_against_its_reference(phase, sizes):
    line, _ = phase(SEED, **sizes)
    _complete(line)
    assert _failed(line) == []


def test_qr_svd_phase_says_which_route_its_programs_took(monkeypatch):
    """On one device the programs of ``ht.linalg.qr`` and ``ht.linalg.svd``
    state their route, reads of A and column blocks (64 columns: one, the
    dense products); on the CPU mesh the operand is split
    over every device and factors by TSQR inside one fused program, which
    states none."""
    qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
    line, _ = chip_smoke.phase_qr_svd(SEED, m=4096, n=64)
    assert line["routes"] == {}
    # the phase's operand on one device, as on a one-chip machine
    monkeypatch.setattr(ht.random, "randn", functools.partial(ht.random.randn, comm=ht.XlaCommunication(jax.devices()[:1])))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(qr_mod, "MIN_BYTES", 0)
    line, _ = chip_smoke.phase_qr_svd(SEED, m=4096, n=64)
    _complete(line)
    assert _failed(line) == []
    assert line["routes"] == {
        "linalg.qr": {"route": "cholqr2", "a_passes": 3, "col_blocks": 1},
        "linalg.svd": {"route": "cholqr2", "a_passes": 3, "col_blocks": 1},
    }


def test_qr_svd_phase_over_the_mesh_checks_the_row_sharded_route(monkeypatch):
    """Over several devices, steered onto the chip's route: the phase's
    operand is split by rows over the mesh and takes ``cholqr2_rows``, and
    its line checks that it did."""
    qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(qr_mod, "MIN_BYTES", 0)
    line, _ = chip_smoke.phase_qr_svd(SEED, m=4096, n=64)
    _complete(line)
    assert _failed(line) == []
    assert line["checks"]["sharded_route"]["value"] == 1
    assert {v["route"] for v in line["routes"].values()} == {"cholqr2_rows"}


def test_spectral_phase_says_which_matvec_route_its_fit_took():
    """Off the chip, and under the kernel's threshold: the dense product, and
    the line says both."""
    line, _ = chip_smoke.phase_spectral(SEED, n=256, f=18, k=4, m=32)
    assert line["lanczos_matvec"] == {
        "route": ["dense"], "kernel_from_rows": 8192, "rows_under_the_kernels_threshold": True,
    }


@pytest.mark.parametrize("f,form", [(18, "unrolled"), (65, "reduce")])
def test_cdist_phase_says_which_form_its_program_took(f, form):
    line, _ = chip_smoke.phase_cdist(SEED, n=256, f=f, block=32)
    assert line["exact_form"] == [form] and _failed(line) == []


@pytest.mark.parametrize("n,f,k", [(40, 2048, 4), (37, 300, 8)])
def test_kmedians_phase_names_its_routes(n, f, k):
    """At toy size on the CPU mesh: the bisection's route and the row order of
    the wide L1 sum, the medians numpy's own on the sampled columns."""
    line, _ = chip_smoke.phase_kmedians(SEED, n=n, f=f, k=k, iters=3, sample=64)
    _complete(line)
    assert _failed(line) == []
    assert line["routes"] == {
        "medians": "rank_bisection", "assign": "manhattan", "x_passes": None, "network_max": None,
        "selections_by_network": 0, "manhattan_form": "rows",
    }
    assert line["checks"]["medians_vs_numpy_on_sample_abs"]["value"] == 0.0


def test_io_phase_round_trip_under_the_given_directory(tmp_path):
    line, _ = chip_smoke.phase_io(SEED, n=1024, f=32, work=str(tmp_path))
    _complete(line)
    assert _failed(line) == []
    assert line["native_csv_scanner_loaded"] in (True, False)
    assert os.listdir(tmp_path) == []  # the file is removed after the round trip


class _Interpreted:
    """``flash_attention`` with the Pallas interpreter on — the steering the
    script itself has no option for."""

    def __init__(self, jitted):
        self._jitted = jitted

    def __call__(self, q, k, v, causal=False):
        return self._jitted(q, k, v, causal=causal, interpret=True)

    def lower(self, q, k, v, causal=False):
        return self._jitted.lower(q, k, v, causal=causal, interpret=True)


@pytest.mark.parametrize("interpreted", [False, True], ids=["xla_fallback", "pallas_interpreter"])
def test_attention_phase_fails_on_kernel_evidence_only(monkeypatch, interpreted):
    """Off the chip the numbers still agree with the reference, on either
    path; the one thing that fails is the evidence that the Mosaic kernel is
    in the lowered program — so a silent fallback cannot pass on the chip."""
    if interpreted:
        monkeypatch.setattr(
            ht.parallel, "flash_attention", _Interpreted(ht.parallel.flash_attention)
        )
    line, _ = chip_smoke.phase_attention(SEED, S=256, H=2, D=64)
    _complete(line)
    assert _failed(line) == [
        "causal_pallas_kernel_in_program", "full_pallas_kernel_in_program"
    ]


def test_serve_then_fleet_warm_start(kmeans, tmp_path):
    work = str(tmp_path)
    serve, _ = chip_smoke.phase_serve(kmeans[1], SEED, n_requests=6, max_rows=64, work=work)
    _complete(serve)
    assert _failed(serve) == []
    assert serve["sizes"]["rows"][:2] == [1, 64]  # both ends of the range
    assert serve["aot"]["bundles"] > 0
    fleet, _ = chip_smoke.phase_fleet(work=work)
    _complete(fleet)
    assert _failed(fleet) == []
    assert fleet["hello"]["installed"] == serve["aot"]["bundles"]
    assert fleet["hello"]["fuse_misses"] == fleet["hello"]["compile_misses"] == 0
    assert sum(fleet["traced_by_requests"].values()) == 0


def test_fleet_phase_fails_when_no_bundle_was_installed(kmeans, tmp_path):
    """The warm start is asserted from the hello frame: a registry whose
    sidecar is gone (so the replica compiles afresh) fails the phase."""
    work = str(tmp_path)
    chip_smoke.phase_serve(kmeans[1], SEED, n_requests=3, max_rows=16, work=work)
    for dirpath, _, files in os.walk(os.path.join(work, "registry")):
        for name in files:
            if name.endswith(".aotx"):
                os.remove(os.path.join(dirpath, name))
    fleet, _ = chip_smoke.phase_fleet(work=work)
    assert "bundles_installed" in _failed(fleet)
    assert fleet["checks"]["replies_differing_from_in_process"]["ok"]


def test_fleet_phase_touches_no_backend(kmeans, tmp_path):
    """chip_smoke's parent runs the fleet phase itself while the replica holds
    the chip: constructing ProcFleet, Ingress and IngressClient and serving
    through them must leave jax's backend registry empty."""
    work = str(tmp_path)
    chip_smoke.phase_serve(kmeans[1], SEED, n_requests=3, max_rows=16, work=work)
    script = (
        "import sys, json\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "import chip_smoke\n"
        f"line, _ = chip_smoke.phase_fleet(work={work!r})\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, f'backends initialized: {list(xb._backends)}'\n"
        "print('FLEET_OK', json.dumps(line['checks']))\n"
    )
    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=300, cwd=ROOT
    )
    assert "FLEET_OK" in res.stdout, res.stdout[-2000:] + res.stderr[-2000:]
    checks = json.loads(res.stdout.split("FLEET_OK", 1)[1])
    assert all(c["ok"] for c in checks.values())


# ----------------------------------------------------------------------- #
# the sharded path, on four of the virtual devices
# ----------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def four():
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    return ht.XlaCommunication(jax.devices()[:4])


@pytest.fixture(scope="module")
def sharded_moments(four):
    return chip_smoke.sharded_moments(SEED, four, n=8192, f=32)


def test_sharded_moments_and_twin_data(sharded_moments, four):
    line, (X, X1) = sharded_moments
    _complete(line)
    assert _failed(line) == []
    assert X.comm.size == 4 and X1.comm.size == 1
    np.testing.assert_array_equal(X.numpy(), X1.numpy())  # same seeded data


@pytest.mark.parametrize(
    "phase,extra",
    [
        (chip_smoke.sharded_kmeans, dict(seed=SEED, k=8, iters=5)),
        (chip_smoke.sharded_resplit, dict(seed=SEED, block=256)),
        (chip_smoke.sharded_qr, dict()),
    ],
    ids=["kmeans", "resplit", "tsqr"],
)
def test_sharded_phase_matches_its_one_device_twin(sharded_moments, four, phase, extra):
    line, _ = phase(sharded_moments[1], comm=four, **extra)
    _complete(line)
    assert _failed(line) == []
    if phase is chip_smoke.sharded_kmeans:  # four devices and the one-device twin
        assert line["routes"] == {"layout": ["rows", "rows"]}


def test_sharded_ring_summa_matches_its_one_device_twin(four):
    line, _ = chip_smoke.sharded_matmul(SEED, four, mm=256)
    _complete(line)
    assert _failed(line) == []


def test_sharded_ring_attention_fails_on_kernel_evidence_only(four):
    line, _ = chip_smoke.sharded_ring_attention(SEED, four, S=1024, H=2, D=64)
    _complete(line)
    assert _failed(line) == ["pallas_kernel_in_program"]


# ----------------------------------------------------------------------- #
# the script
# ----------------------------------------------------------------------- #
def test_check_semantics():
    assert chip_smoke.check(0.5, 1.0)["ok"] and not chip_smoke.check(2.0, 1.0)["ok"]
    assert chip_smoke.check(0.99, 0.98, at_least=True)["ok"]
    assert not chip_smoke.check(float("nan"), 1.0)["ok"]  # non-finite never passes
    assert not chip_smoke.check(float("inf"), 1.0, at_least=True)["ok"]


def test_emit_stops_the_run_on_a_failed_check(monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(chip_smoke, "LINES", str(tmp_path / "out" / "lines.jsonl"))
    chip_smoke.emit({"phase": "p", "checks": {"a": chip_smoke.check(0, 1)}})
    with pytest.raises(SystemExit) as stop:
        chip_smoke.emit({"phase": "p", "checks": {"a": chip_smoke.check(2, 1)}})
    assert stop.value.code not in (0, None)
    printed = capsys.readouterr().out.splitlines()
    assert [json.loads(t)["phase"] for t in printed] == ["p", "p"]  # the line comes first
    assert len(open(chip_smoke.LINES).read().splitlines()) == 2


@pytest.mark.parametrize("args", [[], ["--chips", "4"]], ids=["one_chip", "four_chips"])
def test_script_exits_nonzero_off_the_chip_and_prints_no_result(args, tmp_path):
    """Run as the driver runs it, but held to the CPU: the device check fails
    the run at once, with no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=str(tmp_path),
    )
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert '"ok"' not in res.stdout


def test_script_alone_without_the_package_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    res = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        capture_output=True, text=True, timeout=120, env=env, cwd=str(tmp_path),
    )
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
