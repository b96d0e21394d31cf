"""From a profiler trace (``*.xplane.pb``) to device busy and idle time,
program launches, exposed collective time and attributed idle gaps.  Nothing
but ``jax.profiler.ProfileData`` and interval arithmetic; the benchmark's
own, so that every PR computes the same numbers in the same way.  Checked on
the recorded trace under ``tests/data/`` (``tests/test_trace_reduce.py``).

What it reads (TPU v5e, jax 0.9 traces; ``tools/dump_trace.py`` prints a
trace's planes and lines for a look by hand):

- one plane per chip, ``/device:TPU:<n>``: line ``XLA Modules`` holds one
  event for each execution of a compiled program, line ``XLA Ops`` one for
  each operation inside them (a ``while`` and the operations of its body
  both appear, overlapping; an event's name is the operation's whole HLO
  text, ``%name = shape opcode(operands)``), line ``Async XLA Ops`` the
  spans of asynchronous operations from their start to their done;
- plane ``/host:CPU``: one line per host thread; the benchmark's own
  ``TraceAnnotation`` around each job (``JOB_SPAN``) and the runtime's own
  host events lie there, on the clock of the device planes.

All times are seconds.  Intervals are ``(start, end)`` pairs.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

JOB_SPAN = "perf_job"
DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"

#: operations that move data between chips (their ``-start``/``-done`` halves too)
COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast", "ragged-all-to-all",
)
#: operations that only hold other operations: neither compute nor exchange
CONTAINER_PREFIXES = ("while", "conditional", "call")


# ------------------------------------------------------------------ intervals
def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(merged: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in merged)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        i = j
        while i < len(b) and b[i][0] < hi:
            if b[i][0] > cur:
                out.append((cur, b[i][0]))
            cur = max(cur, b[i][1])
            i += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` that merged busy intervals leave."""
    return subtract([(lo, hi)], clip(merged, lo, hi))


# ---------------------------------------------------------------- the trace
def _base(name: str) -> str:
    """``%all-reduce.3 = f32[8,32] all-reduce(...)`` -> ``all-reduce.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def short(name: str, width: int = 120) -> str:
    """An operation's name and the head of its shape, for the breakdown."""
    return name.lstrip("%")[:width]


def is_collective(name: str) -> bool:
    return _base(name).startswith(COLLECTIVE_PREFIXES)


def is_container(name: str) -> bool:
    b = _base(name)
    return any(b == p or b.startswith(p + ".") for p in CONTAINER_PREFIXES)


def find_trace(trace_dir: str) -> str:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime,
    )
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def open_profile(path: str):
    """``jax.profiler.ProfileData`` of an ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as fh:
            return ProfileData.from_serialized_xspace(fh.read())
    return ProfileData.from_file(path)


def load(path: str) -> dict:
    """Read a trace file into plain lists:
    ``{"devices": {plane: {"modules": [(name, s, e)], "ops": [...], "async": [...]}},
    "host": {line: [(name, s, e)]}}``."""
    data = open_profile(path)
    devices: Dict[str, dict] = {}
    host: Dict[str, list] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = {"modules": [], "ops": [], "async": []}
            for line in plane.lines:
                key = {MODULE_LINE: "modules", OP_LINE: "ops", ASYNC_LINE: "async"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    dev[key].append((ev.name, s, s + ev.duration_ns * 1e-9))
            devices[plane.name] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = []
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    evs.append((ev.name, s, s + ev.duration_ns * 1e-9))
                if evs:
                    host[line.name] = evs
    return {"devices": devices, "host": host}


def job_spans(host: Dict[str, list], name: str = JOB_SPAN) -> List[Interval]:
    """The benchmark's own spans around its jobs, in time order."""
    return sorted((s, e) for evs in host.values() for n, s, e in evs if n == name)


def _host_name_at(host_line: list, lo: float, hi: float, skip: str) -> Optional[str]:
    """The shortest host event (other than ``skip``) that covers the middle
    of ``[lo, hi]``: what the host thread was doing in that gap."""
    mid = 0.5 * (lo + hi)
    best = None
    for n, s, e in host_line:
        if n != skip and s <= mid <= e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return None if best is None else best[0]


def reduce(loaded: dict, job_span: str = JOB_SPAN, top: int = 10) -> Optional[dict]:
    """The summary the per-layer readers and the result line use, or None
    where the trace holds no device plane or no job span.

    The traced window runs from the first job span's start to the last one's
    end.  Per device: ``busy_s`` (union of operation intervals inside the
    window), ``launches`` (module events that start inside it; the summary takes the
    chip that saw most, since the small unsharded programs run on one chip only),
    ``collective_s`` and ``collective_exposed_s`` (collective intervals, and
    the part of them no compute operation on that device overlaps).
    ``breakdown`` is the contract's: operations by time (mean over devices)
    and idle gaps of the idlest device, summed by what the host did in them.
    """
    spans = job_spans(loaded["host"], job_span)
    if not spans or not loaded["devices"]:
        return None
    lo, hi = spans[0][0], spans[-1][1]
    job_line = next(
        evs for evs in loaded["host"].values() if any(n == job_span for n, _, _ in evs)
    )
    per_device = {}
    op_seconds: Dict[str, float] = {}
    for plane, dev in sorted(loaded["devices"].items()):
        ops = [(n, *c) for n, s, e in dev["ops"] for c in clip([(s, e)], lo, hi)]
        source = ops or [(n, *c) for n, s, e in dev["modules"] for c in clip([(s, e)], lo, hi)]
        busy = merge((s, e) for _, s, e in source)
        compute = merge(
            (s, e) for n, s, e in ops if not is_collective(n) and not is_container(n)
        )
        coll = merge(
            (max(s, lo), min(e, hi))
            for n, s, e in list(dev["ops"]) + list(dev.get("async", ()))
            if is_collective(n)
        )
        per_device[plane] = {
            "busy_s": total(busy),
            "launches": sum(1 for _, s, _ in dev["modules"] if lo <= s <= hi),
            "collective_s": total(coll),
            "collective_exposed_s": total(subtract(coll, compute)),
            "idle": gaps(busy, lo, hi),
        }
        for n, s, e in ops:
            if not is_container(n):
                op_seconds[short(n)] = op_seconds.get(short(n), 0.0) + (e - s)
    if not any(d["busy_s"] > 0 for d in per_device.values()):
        return None
    ndev = len(per_device)
    idlest = min(per_device.values(), key=lambda d: d["busy_s"])
    gap_seconds: Dict[str, float] = {}
    for a, b in idlest["idle"]:
        what = _host_name_at(job_line, a, b, job_span) or (
            job_span if any(s <= 0.5 * (a + b) <= e for s, e in spans) else "between_jobs"
        )
        gap_seconds[what] = gap_seconds.get(what, 0.0) + (b - a)

    def ranked(d):
        return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": hi - lo,
        "jobs": len(spans),
        "devices": ndev,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / ndev,
        "busy_s_min": idlest["busy_s"],
        "launches": max(d["launches"] for d in per_device.values()),
        "collective_s_max": max(d["collective_s"] for d in per_device.values()),
        "collective_exposed_s_max": max(d["collective_exposed_s"] for d in per_device.values()),
        "breakdown": {
            "device_ops": ranked({n: v / ndev for n, v in op_seconds.items()}),
            "idle_gaps": ranked(gap_seconds),
        },
    }
