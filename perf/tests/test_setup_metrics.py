"""The four per-layer readers that move ``setup_s`` (``import_s``,
``trace_lower_s``, ``compile_load_s``, ``programs_in_setup``): each on a
synthetic start-up record, ``None`` where there is nothing to read (an empty
record, no traced window, a program without ``telemetry.startup``: what the
parent commit gives), records stamped after the window's first span left out,
and all four found by name from ``BENCHMARK.json`` in every cell."""

import importlib
import json
import os

import pytest

from conftest import REPO

NAMES = ("import_s", "trace_lower_s", "compile_load_s", "programs_in_setup")
VIEW = {"trace": {"jobs": 2, "window_s": 1.0, "busy_s": 0.5}}
WINDOW_START = 100.0


def _reader(name):
    return importlib.import_module("layer_metrics." + name).read


def _rec(site, ts, dur, **fields):
    kind = "import" if site.startswith("import:") else "compile"
    return dict(type="span", site=site, kind=kind, id=None, parent=None, root=None, ts=ts, dur=dur, tid=1, **fields)


#: a process's set-up, then what the comparison compiles after the window
RECORD = (
    _rec("import:heat_tpu", 1.0, 5.25),
    _rec("import:jax", 1.0, 2.0),
    _rec("import:core", 3.0, 3.0),
    _rec("import:core.statistics", 3.5, 1.25),
    # datagen's program
    _rec("compile:trace", 20.0, 0.5, fun="make"),
    _rec("compile:lower", 20.5, 0.25, fun="make"),
    _rec("compile:backend", 20.75, 1.0, fun="make", cache_hit=True, retrieval_s=0.75),
    # a program whose trace holds two nested traces: counted once
    _rec("compile:trace", 30.125, 0.125, fun="_mean"),
    _rec("compile:trace", 30.5, 0.25, fun="_var"),
    _rec("compile:trace", 30.0, 1.0, fun="stat.moment2"),
    _rec("compile:lower", 31.0, 0.5, fun="stat.moment2"),
    _rec("compile:backend", 31.5, 0.5, fun="stat.moment2", cache_hit=False),
    # an eager program of the warm-up
    _rec("compile:trace", 40.0, 0.0625, fun="convert_element_type"),
    _rec("compile:lower", 40.0625, 0.0625, fun="convert_element_type"),
    _rec("compile:backend", 40.125, 0.125, fun="convert_element_type", cache_hit=True, retrieval_s=0.0625),
    # after the window began: the reference's programs, a compile inside the window
    _rec("compile:trace", 100.5, 4.0, fun="late"),
    _rec("compile:lower", 104.5, 4.0, fun="late"),
    _rec("compile:backend", 108.5, 4.0, fun="late", cache_hit=False),
    _rec("import:heat_tpu", 200.0, 1.0),  # a reload, say
)
WANT = {"import_s": 5.25, "trace_lower_s": 2.375, "compile_load_s": 1.625, "programs_in_setup": 3.0}


@pytest.fixture
def program(monkeypatch):
    """``heat_tpu.telemetry`` with a record and a traced window of the
    test's own: ``program(record, spans)`` sets what the readers find."""
    from heat_tpu import telemetry

    def put(record, spans):
        monkeypatch.setattr(telemetry, "startup", lambda: tuple(record), raising=False)
        monkeypatch.setattr(telemetry, "profiled_spans", lambda: tuple(spans))

    return put


def _spans(start=WINDOW_START):
    # spans land at their exit: the first in the list is not the earliest
    return [
        dict(type="span", site="jitted:x", kind="launch", id=1, parent=0, root=0, ts=start + 0.5, dur=0.1),
        dict(type="span", site="fit:KMeans", kind="entry", id=0, parent=None, root=0, ts=start, dur=1.0),
    ]


@pytest.mark.parametrize("name", NAMES)
def test_reads_the_set_up_of_a_synthetic_record(program, name):
    program(RECORD, _spans())
    assert _reader(name)(VIEW) == WANT[name]


@pytest.mark.parametrize("name", NAMES)
def test_records_after_the_windows_first_span_are_left_out(program, name):
    """The same record under a window that began later, then earlier: what
    began before the window counts, whatever its list position."""
    program(RECORD, _spans(start=150.0))
    late = {"import_s": 5.25, "trace_lower_s": 2.375 + 8.0, "compile_load_s": 1.625 + 4.0, "programs_in_setup": 4.0}
    assert _reader(name)(VIEW) == late[name]
    program(RECORD, _spans(start=25.0))  # datagen's program alone
    early = {"import_s": 5.25, "trace_lower_s": 0.75, "compile_load_s": 1.0, "programs_in_setup": 1.0}
    assert _reader(name)(VIEW) == early[name]


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read(program, name, monkeypatch):
    """What the parent commit gives, and a run that traced nothing: the
    reader returns None and does not raise, and the line leaves the metric
    out."""
    from heat_tpu import telemetry

    program((), _spans())  # an empty record
    assert _reader(name)(VIEW) is None
    program(RECORD, ())  # no traced window
    assert _reader(name)(VIEW) is None
    program(RECORD, _spans(start=0.5))  # a window ahead of every record
    assert _reader(name)(VIEW) is None
    program(RECORD, _spans())
    assert _reader(name)({"trace": None}) is None
    monkeypatch.delattr(telemetry, "startup")  # a program without the record
    assert _reader(name)(VIEW) is None


def test_a_record_without_the_package_import_has_no_import_s(program):
    program([r for r in RECORD if r["kind"] == "compile"], _spans())
    assert _reader("import_s")(VIEW) is None
    assert _reader("programs_in_setup")(VIEW) == 3.0


def test_found_by_name_in_every_cell(program):
    import run

    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    added = [m for m in bench["per_layer"] if m["name"] in NAMES]  # by name: later PRs append theirs
    assert [m["name"] for m in added] == list(NAMES)
    assert all("workloads" not in m and m["moves"] == "setup_s" and m["better"] == "lower" for m in added)
    assert [m["layer"] for m in added] == ["package", "op engine", "op engine", "op engine"]
    assert {m["name"]: m["source"] for m in added}["programs_in_setup"] == "program_counter"
    # every cell reports ``setup_s``, so every cell reports the four
    assert all("workloads" not in m for m in bench["end_to_end"] if m["name"] == "setup_s")
    program(RECORD, _spans())
    for cell in (w["name"] for w in bench["workloads"]):
        loaded = run.load_cell(cell)
        loaded["bench"] = dict(bench, per_layer=added)
        got = run.layer_metrics(loaded, VIEW)
        assert got == {
            "import_s": {"value": 5.25, "unit": "s"},
            "trace_lower_s": {"value": 2.375, "unit": "s"},
            "compile_load_s": {"value": 1.625, "unit": "s"},
            "programs_in_setup": {"value": 3.0, "unit": "count"},
        }
    # on a program without the record the line leaves all four out
    from heat_tpu import telemetry

    del telemetry.startup
    assert run.layer_metrics(loaded, VIEW) == {}


def test_a_real_window_reads_the_process_own_set_up(tmp_path):
    """End to end on the CPU as ``run.py --trace 1`` does it: a program
    compiled ahead of a profiler trace counts, one compiled inside the window
    does not, and the import is the package's own."""
    import jax
    import jax.numpy as jnp

    from heat_tpu import telemetry
    from heat_tpu.core._compile import jitted

    telemetry.disable()
    telemetry.reset()
    before = jitted(("setup-metrics-test.before", 0), lambda: lambda a: jnp.cos(a) + 1)
    inside = jitted(("setup-metrics-test.inside", 0), lambda: lambda a: jnp.sin(a) + 1)
    x = jnp.ones((8,), jnp.float32)
    before(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        before(x).block_until_ready()
        got = {name: _reader(name)(VIEW) for name in NAMES}
        inside(x).block_until_ready()
        again = {name: _reader(name)(VIEW) for name in NAMES}
    finally:
        jax.profiler.stop_trace()
    assert again == got  # the window's own compile moved nothing
    records = telemetry.startup()
    assert got["import_s"] == records[0]["dur"] > 0
    assert got["programs_in_setup"] >= 1 and got["trace_lower_s"] > 0 and got["compile_load_s"] > 0
    start = min(e["ts"] for e in telemetry.profiled_spans())
    funs = {r["fun"] for r in records if r["kind"] == "compile" and r["ts"] < start}
    assert "setup-metrics-test.before" in funs and "setup-metrics-test.inside" not in funs
    # and the window's compile is in the window's spans, under its launch
    (late,) = [e for e in telemetry.profiled_spans() if e["site"] == "compile:backend"]
    assert late["fun"] == "setup-metrics-test.inside" and late["parent"] is not None
    telemetry.reset()
