"""The start-up record (``telemetry.startup()``): what the package's import
statements took and every program's trace, lower and compile-or-load, kept
with telemetry disabled.

jax emits the same monitoring events on the CPU as on the chip, so every
case runs here.  The record is process-wide and bounded, and a test worker
has compiled thousands of programs before it reaches this file: the
``record`` fixture gives each test a record of its own that holds the import
rows alone.
"""

import ast
import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp
from jax._src import monitoring

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.core import _compile
from heat_tpu.core._compile import jitted
from heat_tpu.telemetry import _core

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SITES = ("compile:trace", "compile:lower", "compile:backend")


@pytest.fixture
def record(monkeypatch):
    """A start-up record of this test's own (the import rows, nothing
    dropped), telemetry disabled and its registry clean."""
    monkeypatch.setattr(_core, "_startup", [r for r in _core._startup if r["kind"] == "import"])
    monkeypatch.setattr(_core, "_startup_dropped", 0)
    was = _core.is_enabled()
    telemetry.disable()
    telemetry.reset()
    yield telemetry
    telemetry.reset()
    if was:
        telemetry.enable()


def _fresh(name):
    """A ``jitted()`` program nobody has called: ``(entry, operand)``."""
    return jitted((name, 0), lambda: lambda a: jnp.tanh(a) * 3), jnp.ones((4,), jnp.float32)


def _compiles(records, fun=None):
    return [r for r in records if r["kind"] == "compile" and fun in (None, r["fun"])]


# --------------------------------------------------------------------- #
# compile stages                                                         #
# --------------------------------------------------------------------- #
def test_a_fresh_program_leaves_one_record_a_stage(record):
    fn, x = _fresh("startup-test.fresh")
    fn(x).block_until_ready()
    mine = _compiles(telemetry.startup(), "startup-test.fresh")
    assert [r["site"] for r in mine] == list(SITES)  # in the order jax ran them
    assert all(r["type"] == "span" and r["kind"] == "compile" and r["dur"] > 0 for r in mine)
    trace, lower, backend = mine
    assert trace["ts"] <= lower["ts"] <= backend["ts"]
    assert backend["cache_hit"] is False and "retrieval_s" not in backend  # conftest keeps the cache off
    assert "cache_hit" not in trace and "cache_hit" not in lower
    # the stages lie inside the call, on the clock of the spans
    assert backend["ts"] + backend["dur"] <= _core.clock()


def test_a_second_call_leaves_none(record):
    fn, x = _fresh("startup-test.second")
    y = x + 1  # an eager program of its own, ahead of the count
    fn(x).block_until_ready()
    n = len(telemetry.startup())
    fn(x).block_until_ready()
    fn(y).block_until_ready()  # another value, the same program
    assert len(telemetry.startup()) == n


def test_disabled_the_stream_stays_empty_while_the_record_grows(record):
    assert not telemetry.recording()
    n = len(telemetry.startup())
    fn, x = _fresh("startup-test.disabled")
    fn(x).block_until_ready()
    assert telemetry.events() == () and telemetry.snapshot() == {}
    assert len(telemetry.startup()) >= n + 3
    (backend,) = [r for r in _compiles(telemetry.startup(), "startup-test.disabled") if r["site"] == SITES[2]]
    assert backend["id"] is None and backend["parent"] is None and backend["root"] is None


@pytest.mark.parametrize("switch", ["enable", "profiler"])
def test_recording_a_compile_is_a_child_of_the_launch_that_missed(record, switch, tmp_path):
    fn, x = _fresh("startup-test.child-" + switch)
    if switch == "enable":
        telemetry.enable()
    else:
        jax.profiler.start_trace(str(tmp_path))
    try:
        fn(x).block_until_ready()
        fn(x).block_until_ready()
    finally:
        if switch == "profiler":
            jax.profiler.stop_trace()
    spans = telemetry.profiled_spans() if switch == "profiler" else telemetry.events()
    first, second = [e for e in spans if e["kind"] == "launch"]
    assert first["miss"] is True and "miss" not in second
    kids = [e for e in spans if e["kind"] == "compile"]
    assert sorted({e["site"] for e in kids}) == sorted(SITES)
    assert all(e["parent"] == first["id"] and e["root"] == first["root"] for e in kids)
    assert len({e["id"] for e in spans}) == len(spans)  # ids of their own
    assert all(first["ts"] <= e["ts"] and e["ts"] + e["dur"] <= first["ts"] + first["dur"] for e in kids)
    # the same records, not copies, are in the start-up record
    assert all(any(e is r for r in telemetry.startup()) for e in kids)
    # and the launch's self time is what the stages leave of it
    own = telemetry.self_times(spans)
    assert 0 <= own[first["id"]] < first["dur"]


def test_deterministic_mode_keeps_compiles_off_the_stream(record):
    """Whether a program compiles depends on what the process ran before: a
    replay of the event order cannot repeat it, so the stream (and the
    integer clock) must not see it."""
    telemetry.enable(deterministic=True)
    try:
        fn, x = _fresh("startup-test.deterministic")
        with telemetry.span("outer"):
            fn(x).block_until_ready()
        evs = telemetry.events()
        assert [e["site"] for e in evs] == ["jitted:startup-test.deterministic", "outer"]
        assert [e["ts"] for e in evs] == [1.0, 0.0] and [e["dur"] for e in evs] == [1.0, 3.0]
        assert len(_compiles(telemetry.startup(), "startup-test.deterministic")) == 3
    finally:
        telemetry.disable()


def test_reset_keeps_the_record(record):
    fn, x = _fresh("startup-test.reset")
    fn(x).block_until_ready()
    before = telemetry.startup()
    telemetry.reset()
    assert telemetry.startup() == before and len(before) > 3


def test_the_cap_drops_and_counts(record, monkeypatch):
    monkeypatch.setattr(_core, "_MAX_STARTUP", len(telemetry.startup()) + 2)
    fn, x = _fresh("startup-test.cap")
    fn(x).block_until_ready()  # three stages at least, room for two
    assert len(telemetry.startup()) == _core._MAX_STARTUP
    assert _core._startup_dropped >= 1
    telemetry.enable()
    assert telemetry.snapshot()["startup"]["dropped"] == _core._startup_dropped
    assert f"records dropped {_core._startup_dropped}" in telemetry.startup_report()


def test_nested_traces_count_once():
    """A ``jax.numpy`` function traced inside a program's trace reports its
    own duration inside the outer one: a total is the union."""
    def rec(site, ts, dur, **f):
        return dict(site=site, kind="compile", ts=ts, dur=dur, **f)

    totals = _core._startup_totals([
        dict(site="import:heat_tpu", kind="import", ts=0.0, dur=5.0),
        dict(site="import:jax", kind="import", ts=0.0, dur=2.0),
        rec("compile:trace", 10.0, 1.0, fun="outer"),
        rec("compile:trace", 10.2, 0.3, fun="_mean"),  # nested
        rec("compile:trace", 10.6, 0.2, fun="_var"),  # nested
        rec("compile:lower", 11.0, 0.5, fun="outer"),
        rec("compile:backend", 11.5, 2.0, fun="outer", cache_hit=True),
        rec("compile:backend", 20.0, 0.25, fun="other", cache_hit=False),
    ])
    assert totals == {"import_s": 5.0, "trace_lower_s": 1.5, "compile_load_s": 2.25, "programs": 2}
    assert _core.covered_s([(0, 2), (1, 3), (5, 6)]) == 4 and _core.covered_s([(0, 4)], 1, 2) == 1


def test_the_package_has_one_listener_and_kinds_name_the_record():
    mine = [l for l in monitoring.get_event_duration_listeners() if l is _compile._on_compile_stage]
    assert len(mine) == 1
    assert {"import", "compile"} <= set(_core.KINDS)
    assert {r["kind"] for r in _core._startup} <= {"import", "compile"}


# --------------------------------------------------------------------- #
# import stages                                                          #
# --------------------------------------------------------------------- #
def _import_statements(path):
    """What each import statement of an ``__init__`` file imports, in file
    order (the standard library's apart: ``time`` is the stamps' own)."""
    with open(path) as fh:
        tree = ast.parse(fh.read())
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            what = node.names[0].name.split(".")[-1]
        elif isinstance(node, ast.ImportFrom):
            what = node.module or node.names[0].name
        else:
            continue
        if what not in ("time", "os"):
            out.append((node.lineno, what))
    return [what for _, what in sorted(out)]


def _imports():
    rows = [r for r in telemetry.startup() if r["kind"] == "import"]
    root = rows[0]
    assert root["site"] == "import:heat_tpu" and root["parent"] is None and root["id"] == 0
    return root, rows


def test_one_import_record_a_statement_in_file_order():
    root, rows = _imports()
    top = [r["site"] for r in rows if r["parent"] == 0]
    pkg = os.path.dirname(ht.__file__)
    assert top == ["import:" + w for w in _import_statements(os.path.join(pkg, "__init__.py"))]
    assert top[0] == "import:jax" and "import:serve" in top
    core = next(r for r in rows if r["site"] == "import:core")  # the statement that imported it
    inner = [r["site"] for r in rows if r["parent"] == core["id"]]
    assert inner == ["import:core." + w for w in _import_statements(os.path.join(pkg, "core", "__init__.py"))]
    assert "import:core.statistics" in inner and "import:core.io" in inner
    assert len(rows) == 1 + len(top) + len(inner)


def test_import_stages_tile_their_parent():
    root, rows = _imports()
    end = root["ts"] + root["dur"]
    top = [r for r in rows if r["parent"] == 0]
    # a statement runs from the end of the one before it: the stages tile the root
    assert top[0]["ts"] == root["ts"]
    assert all(a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1e-9) for a, b in zip(top, top[1:]))
    assert sum(r["dur"] for r in top) == pytest.approx(root["dur"], rel=0.01)
    core = next(r for r in rows if r["site"] == "import:core")
    inner = [r for r in rows if r["parent"] == core["id"]]
    assert all(core["ts"] <= r["ts"] and r["ts"] + r["dur"] <= core["ts"] + core["dur"] + 1e-9 for r in inner)
    assert sum(r["dur"] for r in inner) == pytest.approx(core["dur"], rel=0.01)
    assert all(r["dur"] >= 0 and r["ts"] + r["dur"] <= end + 1e-9 and r["root"] == 0 for r in rows)
    # on the clock of the spans: the package was imported before now
    assert end <= _core.clock()


def test_the_report_names_the_largest_stage_first(record):
    fn, x = _fresh("startup-test.report")
    fn(x).block_until_ready()
    text = telemetry.startup_report().splitlines()
    root, rows = _imports()
    assert text[0] == f"import heat_tpu {root['dur']:.3f} s"
    by_site = {}
    for r in rows:
        if r["parent"] == 0:
            by_site[r["site"]] = by_site.get(r["site"], 0.0) + r["dur"]
    largest = max(by_site, key=by_site.get)
    assert text[1].split()[0] == largest and text[1].endswith("%")
    # the shares of the top-level stages listed make up the import but for rounding
    stage_rows = [l for l in text[1:] if l.startswith("  import:") or l.startswith("  (")]
    assert sum(float(l.split(" s")[0].split()[-1]) for l in stage_rows) == pytest.approx(root["dur"], abs=0.01)
    # a subpackage's statements lie indented under it
    assert any(l.startswith("    import:core.") for l in text)
    (row,) = [l for l in text if l.split()[:1] == ["startup-test.report"]]
    assert row.endswith("0/1")  # one load, not from the cache
    assert text[-1].startswith("totals: import ") and "programs 1," in text[-1]
    # nested traces (``tanh``'s own, where jax reports one) have no row of their own
    assert all(l.split()[-1].count("/") == 1 for l in text if l.startswith("  startup-test"))


def test_snapshot_carries_the_totals(record):
    fn, x = _fresh("startup-test.snapshot")
    fn(x).block_until_ready()
    telemetry.enable()
    block = telemetry.snapshot()["startup"]
    assert sorted(block) == ["compile_load_s", "dropped", "import_s", "programs", "trace_lower_s"]
    assert block["programs"] == 1 and block["dropped"] == 0
    assert block["import_s"] == _imports()[0]["dur"]
    assert 0 < block["trace_lower_s"] and 0 < block["compile_load_s"]


# --------------------------------------------------------------------- #
# readers outside the package                                            #
# --------------------------------------------------------------------- #
def test_chip_smoke_timed_counts_from_the_record(record, monkeypatch):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    assert not hasattr(chip_smoke, "CompileCounter")
    lines = []
    monkeypatch.setattr(chip_smoke, "emit", lines.append)
    fn, x = _fresh("startup-test.smoke")
    want = 12 * float(jnp.tanh(2.0))
    base = chip_smoke.compile_counts()

    def phase(scale):
        return {"checks": {}}, float(fn(x * scale).sum())

    assert chip_smoke.timed("cold", phase, 2.0) == pytest.approx(want)  # what the phase passes on
    chip_smoke.timed("warm", phase, 2.0)
    cold, warm = lines
    assert cold["phase"] == "cold" and cold["cold_wall_s"] >= 0
    # the phase's programs: the ``jitted()`` one and the eager ``multiply`` / ``sum`` around it
    assert cold["compiles"]["requests"] >= 1 and cold["compiles"]["from_cache"] == 0
    assert warm["compiles"] == {"requests": 0, "from_cache": 0}
    assert chip_smoke.compile_counts() == (base[0] + cold["compiles"]["requests"], 0)


CHILD = """
import json, sys, time
t0 = time.monotonic()
import heat_tpu as ht
t1 = time.monotonic()
from jax._src import xla_bridge
started = xla_bridge.backends_are_initialized()
from heat_tpu import telemetry
from heat_tpu.core._compile_cache import place_compile_cache
place_compile_cache()
import jax, jax.numpy as jnp
from heat_tpu.core._compile import jitted, clear_cache

def run():
    fn = jitted(("startup-test.cached", 0), lambda: lambda a: jnp.tanh(a) * 3)
    fn(jnp.ones((4,), jnp.float32)).block_until_ready()

run()
clear_cache(); jax.clear_caches()   # the process forgets the program; the directory does not
run()
loads = [r for r in telemetry.startup() if r["site"] == "compile:backend" and r["fun"] == "startup-test.cached"]
root = telemetry.startup()[0]
print(json.dumps({"started": started, "loads": loads, "root": root, "t0": t0, "t1": t1,
                  "events": len(telemetry.events())}))
"""


def test_a_child_with_the_persistent_cache_reads_hits_and_starts_no_backend(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_ENABLE_COMPILATION_CACHE="true")
    proc = subprocess.run([sys.executable, "-c", CHILD], cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["started"] is False  # registering the listener and stamping the imports start no backend
    assert got["events"] == 0
    first, second = got["loads"]
    assert first["cache_hit"] is False and "retrieval_s" not in first
    assert second["cache_hit"] is True and 0 < second["retrieval_s"] <= second["dur"]
    # the root begins at the package's first line and ends at its last: inside the child's own stamps
    root = got["root"]
    assert root["site"] == "import:heat_tpu"
    assert got["t0"] <= root["ts"] and root["ts"] + root["dur"] <= got["t1"]
    assert (got["t1"] - got["t0"]) - root["dur"] < 0.05
