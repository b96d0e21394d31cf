"""Planned redistribution: ``resplit``/``alltoall`` as compiled schedules.

``resplit`` is the framework's most expensive layout primitive.  The
reference implements it as one monolithic ``Alltoallv``
(reference communication.py:764-881) that materialises worst-case
receive buffers; our port's monolithic path hands the whole src→dst
change to a single GSPMD reshard (:meth:`XlaCommunication.apply_sharding`)
— fast when XLA pattern-matches an all-to-all, but opaque, and in the
general case lowered as **all-gather + slice**: every device briefly
holds the full array.

This module is the alternative: a redistribution **planner** in the
style of *Memory-efficient array redistribution through portable
collective communication* (arXiv 2112.01075).  :func:`plan` decomposes
any (src split → dst split) change over the 1-D mesh into a short
schedule of primitive steps —

``("pad", axis, n)``
    local zero-pad of a ragged target axis to the canonical padded
    length (``size * shard_width(n)``),
``("slice", axis)``
    dynamic-slice discard: each device keeps its own slab along
    ``axis`` (replicated → split; zero wire bytes),
``("allgather", axis)``
    all-gather fraction: the split axis is gathered back to full length
    (split → replicated; ``(p-1)/p`` of the array per device),
``("view", axis)`` / ``("assemble", axis)``
    local reshape bookkeeping around the rotation stage, and
``("rotate", k)``
    one :func:`jax.lax.ppermute` hop with shift ``k``: every device
    ships exactly the ``1/p²``-sized piece of the global array that
    position ``(i+k) mod p`` needs — the split→split schedule is
    ``p-1`` such rotations, moving ``(p-1)/p²`` of the array per device
    (a factor ``p`` fewer wire bytes than gather-and-slice) while never
    holding more than input shard + output shard + one piece.

The cost model (:meth:`Plan.wire_bytes` / :meth:`Plan.peak_live_bytes`,
:func:`monolithic_model` for the one-shot reshard's envelope) follows
:func:`heat_tpu.comm.compressed.wire_model`'s conventions — per-device
bytes, block-padded compressed payloads — and is the same arithmetic the
telemetry ledger is credited with, so modeled ratios and accounted bytes
cannot drift apart.  ``plan(..., max_live_bytes=)`` turns the model into
a hard bound: a schedule whose modeled peak exceeds it raises instead of
silently over-allocating.

Plans execute as **ONE compiled program** (a ``jitted`` ``shard_map``
whose cache key includes the plan signature and, via
:func:`heat_tpu.core._compile.register_key_context`, the redistribution
*and* collective-precision policies).  Exact transmission is the
default and is bitwise-identical to the monolithic reshard; under
``set_collective_precision("bf16"|"int8_block"|"auto")`` the wire-moving
steps (rotations and gather fractions) ride the block-scaled quantized
encoding of :mod:`heat_tpu.comm.compressed`.

Policy
    ``ht.comm.set_redistribution("planned" | "monolithic" | "auto")``.
    ``"monolithic"`` keeps the seed's single GSPMD reshard;
    ``"planned"`` routes every eligible eager ``resplit`` /
    ``alltoall`` / ``commit_split`` through the planner; ``"auto"``
    (the default) applies the planner only where it beats the
    monolithic envelope — split→split changes of at least
    :func:`get_redistribution_threshold` bytes — and leaves everything
    else on the proven monolithic path.  Tracers (``ht.fuse`` / user
    jit), single-device meshes, multi-process meshes, and
    non-canonically-committed inputs always fall back.  The policy is
    part of every program cache key, so flipping it retraces instead of
    replaying a stale program.

Telemetry: each executed plan opens a ``comm:resplit`` span and credits
its modeled bytes to the wire ledger under op ``"resplit"``
(``comm.collectives.resplit`` counter, ``comm.wire_ratio`` gauges),
plus a ``comm.resplit.planned`` counter — docs/design.md §14.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from typing import Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec

from ..core._compile import context_token, jitted, register_key_context
from ..telemetry import _core as _tel
from . import _costs
from . import compressed as _cq
from .compressed import BLOCK
from .overlap import overlap_enabled, timed_dispatch

__all__ = [
    "Plan",
    "get_redistribution",
    "get_redistribution_threshold",
    "grid_redistribute_or_none",
    "monolithic_model",
    "plan",
    "plan_cache_size",
    "clear_plan_cache",
    "redistribute",
    "redistribution",
    "set_redistribution",
    "set_redistribution_threshold",
]

_POLICIES = ("planned", "monolithic", "auto")
_POLICY = "auto"
#: "auto" plans only split→split changes of at least this many bytes —
#: below it the p-1 rotation hops cost more dispatch latency than the
#: monolithic reshard's single collective saves in wire time.
_AUTO_THRESHOLD = 1 << 16


# --------------------------------------------------------------------- #
# policy (mirrors compressed.set_collective_precision)                   #
# --------------------------------------------------------------------- #
def set_redistribution(policy: str) -> None:
    """Set the process-wide redistribution policy.

    ``"monolithic"``
        Every layout change is one GSPMD reshard (the seed behavior).
    ``"planned"``
        Every eligible eager layout change runs the planner's compiled
        schedule (bitwise-identical values; bounded peak memory).
    ``"auto"``
        The default: planner for split→split changes of at least
        :func:`get_redistribution_threshold` bytes, monolithic
        otherwise.
    """
    global _POLICY
    if policy not in _POLICIES:
        raise ValueError(
            f"unknown redistribution policy {policy!r}: expected one of {_POLICIES}"
        )
    _POLICY = policy


def get_redistribution() -> str:
    """The current process-wide redistribution policy."""
    return _POLICY


@contextlib.contextmanager
def redistribution(policy: str):
    """Context-manager form of :func:`set_redistribution`."""
    prev = _POLICY
    set_redistribution(policy)
    try:
        yield
    finally:
        set_redistribution(prev)


def set_redistribution_threshold(nbytes: int) -> None:
    """Minimum array size (bytes) that ``"auto"`` policy plans."""
    global _AUTO_THRESHOLD
    nbytes = int(nbytes)
    if nbytes < 0:
        raise ValueError("threshold must be non-negative")
    _AUTO_THRESHOLD = nbytes


def get_redistribution_threshold() -> int:
    """Current ``"auto"``-policy array-size threshold in bytes."""
    return _AUTO_THRESHOLD


@register_key_context
def _redist_token() -> Tuple:
    """The redistribution policy's contribution to every compiled-program
    cache key (``jitted`` and the ``ht.fuse`` cache): flipping the policy
    keys fresh entries instead of replaying programs whose layout
    behavior was decided under the other policy."""
    return ("redist", _POLICY, _AUTO_THRESHOLD)


# --------------------------------------------------------------------- #
# the plan                                                               #
# --------------------------------------------------------------------- #
@dataclass(frozen=True)
class Plan:
    """One redistribution schedule plus its cost model.

    Immutable and hashable — :attr:`key` is the program-cache signature
    (the executing ``jitted`` entry is keyed on it, so equal plans share
    one compiled program).
    """

    global_shape: Tuple[int, ...]  # TRUE (unpadded) global shape
    dtype: str                     # jnp dtype name
    #: 1-D plans carry split ints; N-D (grid) plans carry splits tuples
    #: (``splits[d]`` = mesh axis sharding array dim ``d``)
    src: Union[int, Tuple[Optional[int], ...], None]
    dst: Union[int, Tuple[Optional[int], ...], None]
    size: int
    mode: Optional[str]            # wire mode of compressible steps
    steps: Tuple[Tuple, ...]
    #: modeled bytes each device puts on the wire (mode-dependent)
    wire_bytes: int
    #: same traffic shipped as the exact dtype (the ratio's denominator)
    exact_wire_bytes: int
    #: modeled peak live bytes per device while the program runs
    peak_live_bytes: int
    max_live_bytes: Optional[int] = None
    #: set on grid plans: the mesh the splits tuples index into.  The
    #: schedule is the per-mesh-axis 1-D factoring of
    #: :func:`heat_tpu.comm._costs.grid_plan_cost` — wire bytes sum over
    #: stages, the peak is the max stage peak, still ONE dispatch.
    mesh_shape: Optional[Tuple[int, ...]] = None

    @property
    def key(self) -> Tuple:
        return (
            self.global_shape, self.dtype, self.src, self.dst,
            self.size, self.mode, self.steps, self.mesh_shape,
        )

    @property
    def out_shape(self) -> Tuple[int, ...]:
        """Global shape of the result: the true shape with ragged
        destination axes padded to their canonical lengths."""
        shape = list(self.global_shape)
        if self.mesh_shape is not None:
            for d, g in enumerate(self.dst):
                if g is not None:
                    p = self.mesh_shape[g]
                    shape[d] = p * (-(-shape[d] // p))
            return tuple(shape)
        if self.dst is not None:
            w = -(-shape[self.dst] // self.size)
            shape[self.dst] = self.size * w
        return tuple(shape)

    def wire_model(self, compute_ms_per_step: float = 0.0) -> dict:
        """Cost-model dict in the :func:`compressed.wire_model` shape —
        the single source for telemetry accounting.

        ``critical_path_ms`` prices the schedule's wire time under both
        ring schedules (:func:`heat_tpu.comm._costs.critical_path_ms`):
        ``"serial"`` sums wire + compute per hop, ``"overlap"`` is the
        pipelined ``max(wire, compute)`` roofline the overlap policy
        targets.  ``compute_ms_per_step`` defaults to 0 (pure wire
        bound)."""
        exact = self.exact_wire_bytes
        hops = sum(1 for s in self.steps if s[0] == "rotate")
        return {
            "steps": len(self.steps),
            "rotate_hops_per_device": hops,
            "exact_wire_bytes": exact,
            "wire_bytes": self.wire_bytes,
            "peak_live_bytes": self.peak_live_bytes,
            "bytes_ratio": round(self.wire_bytes / exact, 4) if exact else None,
            "critical_path_ms": {
                "serial": _costs.critical_path_ms(
                    self.wire_bytes, hops, compute_ms_per_step, overlap=False
                ),
                "overlap": _costs.critical_path_ms(
                    self.wire_bytes, hops, compute_ms_per_step, overlap=True
                ),
            },
        }

    def explain(self) -> str:
        """Human-readable schedule (one line per step)."""
        head = (
            f"redistribute {self.global_shape} {self.dtype} "
            f"split {self.src} -> {self.dst} over {self.size} devices "
            f"[wire {self.wire_bytes} B/dev, peak {self.peak_live_bytes} B/dev"
            + (f", mode {self.mode}" if self.mode else "")
            + "]"
        )
        lines = [head]
        for s in self.steps:
            lines.append(f"  {s[0]}" + (f" {s[1:]}" if len(s) > 1 else ""))
        if not self.steps:
            lines.append("  (no-op)")
        return "\n".join(lines)


def _itemsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def _encoded_bytes(n_elems: int, mode: Optional[str], itemsize: int) -> int:
    """Bytes one payload of ``n_elems`` occupies on the wire under
    ``mode`` — delegates to the shared jax-free model in
    :mod:`heat_tpu.comm._costs` (block-padded; one f32 scale per BLOCK
    for int8), which the static analyzer loads by file path."""
    return _costs.encoded_bytes(n_elems, mode, itemsize)


def monolithic_model(global_shape, dtype, src, dst, size: int) -> dict:
    """Per-device cost envelope of the one-shot GSPMD reshard.

    split→None is an all-gather (``(p-1)/p`` of the array per device;
    the full array live).  None→split is a local slice (zero wire).
    split→split is modeled as the reference ``Alltoallv``'s envelope —
    the general GSPMD lowering gathers then slices, so the wire bytes
    are the all-gather's and the peak briefly holds the full array plus
    the input shard.  (When XLA does pattern-match a true all-to-all the
    monolithic wire cost drops to the planner's; this model is the
    *envelope* the planner must beat, mirroring the worst-case receive
    buffers of reference communication.py:764-881.)
    """
    shape = tuple(int(s) for s in global_shape)
    return _costs.monolithic_cost(shape, _itemsize(dtype), src, dst, size)


#: plan cache — keyed like the compile cache (request signature + the
#: registered key-context tokens, so policy flips re-plan)
_PLANS: dict = {}


def plan_cache_size() -> int:
    return len(_PLANS)


def clear_plan_cache() -> None:
    _PLANS.clear()


def _as_splits(spelling, ndim: int, mesh_ndim: int) -> Tuple[Optional[int], ...]:
    """Normalize a split spelling (None / int / tuple) to the splits
    tuple over an ``mesh_ndim``-axis mesh — the 1-D int form promotes to
    its one-hot tuple on mesh axis 0 (the exact ``split`` compat view)."""
    if spelling is None:
        return (None,) * ndim
    if isinstance(spelling, (tuple, list)):
        return tuple(None if g is None else int(g) for g in spelling)
    entries = [None] * ndim
    entries[int(spelling) % ndim] = 0
    return tuple(entries)


def plan(
    global_shape,
    dtype,
    src,
    dst,
    size: int,
    *,
    mesh_shape: Optional[Tuple[int, ...]] = None,
    max_live_bytes: Optional[int] = None,
) -> Plan:
    """Plan the redistribution of a ``global_shape`` array committed at
    split ``src`` to split ``dst`` over a ``size``-device mesh.

    ``global_shape`` is the TRUE shape; a ragged destination axis is
    padded by the schedule itself (matching
    :meth:`XlaCommunication.commit_split`), while a ragged *source* axis
    is rejected — canonically committed inputs are divisible by
    construction, anything else reaches the planner as replicated.

    On an N-D mesh (``mesh_shape`` with more than one axis), ``src`` and
    ``dst`` are splits TUPLES (``splits[d]`` = mesh axis sharding array
    dim ``d``; int/None spellings promote via the compat view) and the
    schedule is the per-mesh-axis 1-D factoring of
    :func:`heat_tpu.comm._costs.grid_plan_cost` — each stage reuses the
    rotate/allgather/slice step algebra along one named mesh axis, and
    the whole chain still executes as ONE compiled dispatch.

    ``max_live_bytes`` bounds the modeled per-device peak: a schedule
    that cannot fit raises ``ValueError`` (the split→split rotation
    schedule is already both minimal-traffic and minimal-memory, so the
    bound is a guarantee check, not a search knob — see design.md §14).
    For grid plans the bound applies to the max over stages.
    """
    shape = tuple(int(s) for s in global_shape)
    ndim = len(shape)
    p = int(size)
    if p < 1:
        raise ValueError(f"mesh size must be >= 1, got {p}")
    grid = mesh_shape is not None and len(tuple(mesh_shape)) > 1
    if not grid and (isinstance(src, (tuple, list)) or isinstance(dst, (tuple, list))):
        # tuple spellings over a 1-D mesh are exactly their compat ints
        if isinstance(src, (tuple, list)):
            src = next((d for d, g in enumerate(src) if g == 0), None)
        if isinstance(dst, (tuple, list)):
            dst = next((d for d, g in enumerate(dst) if g == 0), None)
    if grid:
        mesh_shape = tuple(int(s) for s in mesh_shape)
        if math.prod(mesh_shape) != p:
            raise ValueError(
                f"mesh_shape {mesh_shape} does not tile {p} device(s)"
            )
        src = _as_splits(src, ndim, len(mesh_shape))
        dst = _as_splits(dst, ndim, len(mesh_shape))
        ckey = (shape, jnp.dtype(dtype).name, src, dst, p, mesh_shape,
                max_live_bytes) + context_token()
        cached = _PLANS.get(ckey)
        if cached is not None:
            return cached
        p_obj = _build_grid_plan(shape, dtype, src, dst, mesh_shape, max_live_bytes)
        _PLANS[ckey] = p_obj
        return p_obj
    if src is not None:
        src = int(src) % ndim
    if dst is not None:
        dst = int(dst) % ndim
    if src is not None and shape[src] % p:
        raise ValueError(
            f"ragged source axis: shape {shape} axis {src} does not divide "
            f"over {p} devices (a canonically committed input is divisible; "
            "ragged arrays live replicated and plan as src=None)"
        )
    ckey = (shape, jnp.dtype(dtype).name, src, dst, p, max_live_bytes) + context_token()
    cached = _PLANS.get(ckey)
    if cached is not None:
        return cached
    p_obj = _build_plan(shape, dtype, src, dst, p, max_live_bytes)
    _PLANS[ckey] = p_obj
    return p_obj


def _build_plan(shape, dtype, src, dst, p, max_live_bytes) -> Plan:
    # the arithmetic lives in the shared jax-free model (comm/_costs.py),
    # which the static analyzer loads by file path — delegation, not
    # duplication, is what keeps lint's cost report and the runtime
    # ledger byte-identical
    dt = jnp.dtype(dtype).name
    cost = _costs.plan_cost(
        shape, dt, src, dst, p,
        mode_for=lambda nbytes: _cq.reduce_mode(dtype, nbytes),
    )
    if max_live_bytes is not None and cost["peak_live_bytes"] > max_live_bytes:
        raise ValueError(
            f"no schedule for {shape} {dt} split {src}->{dst} over {p} "
            f"devices fits max_live_bytes={max_live_bytes}: the minimal "
            f"schedule needs {cost['peak_live_bytes']} live bytes per device"
        )
    return Plan(
        global_shape=tuple(shape), dtype=dt, src=src, dst=dst, size=p,
        mode=cost["mode"], steps=cost["steps"],
        wire_bytes=int(cost["wire_bytes"]),
        exact_wire_bytes=int(cost["exact_wire_bytes"]),
        peak_live_bytes=int(cost["peak_live_bytes"]),
        max_live_bytes=max_live_bytes,
    )


def _build_grid_plan(shape, dtype, src, dst, mesh_shape, max_live_bytes) -> Plan:
    # same delegation as _build_plan: the stage factoring AND its byte
    # arithmetic live in the shared jax-free model
    dt = jnp.dtype(dtype).name
    cost = _costs.grid_plan_cost(
        shape, dt, src, dst, mesh_shape,
        mode_for=lambda nbytes: _cq.reduce_mode(dtype, nbytes),
    )
    if max_live_bytes is not None and cost["peak_live_bytes"] > max_live_bytes:
        raise ValueError(
            f"no schedule for {tuple(shape)} {dt} splits {src}->{dst} over "
            f"mesh {tuple(mesh_shape)} fits max_live_bytes={max_live_bytes}: "
            f"the minimal factored schedule needs {cost['peak_live_bytes']} "
            "live bytes per device"
        )
    return Plan(
        global_shape=tuple(shape), dtype=dt, src=src, dst=dst,
        size=int(math.prod(mesh_shape)),
        mode=cost["mode"], steps=cost["steps"],
        wire_bytes=int(cost["wire_bytes"]),
        exact_wire_bytes=int(cost["exact_wire_bytes"]),
        peak_live_bytes=int(cost["peak_live_bytes"]),
        max_live_bytes=max_live_bytes,
        mesh_shape=tuple(mesh_shape),
    )


# --------------------------------------------------------------------- #
# execution: one compiled shard_map program per plan                     #
# --------------------------------------------------------------------- #
def _ship_start(piece, mode: Optional[str]):
    """Phase 1 of one rotation ship: encode the piece into its wire
    leaves (the piece itself when transmission is exact)."""
    if mode is None:
        return (piece,)
    n = int(math.prod(piece.shape)) if piece.shape else 1
    flat = piece.reshape(-1).astype(jnp.float32)
    padded = max(BLOCK, -(-n // BLOCK) * BLOCK)
    flat = jnp.pad(flat, (0, padded - n))
    return _cq._encode(flat, mode, BLOCK)


def _ship_send(leaves, axis_name, perm):
    """Phase 2: put the wire leaves on the ring."""
    return tuple(jax.lax.ppermute(leaf, axis_name, perm) for leaf in leaves)


def _ship_finish(leaves, mode: Optional[str], shape, dtype):
    """Phase 3: decode the received leaves back into a piece."""
    if mode is None:
        return leaves[0]
    n = int(math.prod(shape)) if shape else 1
    return _cq._decode(leaves, mode)[:n].reshape(shape).astype(dtype)


def _ship(piece, axis_name, perm, mode: Optional[str]):
    """Move one rotation piece to its destination: a raw ppermute when
    transmission is exact, else encode → ppermute the wire leaves →
    decode (the quantize-once-forward-bytes discipline of the rings).
    The three phases are split out so the overlapped schedule can issue
    rotation ``k+1``'s send before finishing rotation ``k``."""
    leaves = _ship_send(_ship_start(piece, mode), axis_name, perm)
    return _ship_finish(leaves, mode, piece.shape, piece.dtype)


def _pad_axis(x, axis: int, pad: int):
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _axis_kernel(name: str, p: int, src, dst, src_len: int, dst_len: int,
                 mode: Optional[str], overlapped: bool):
    """The local body of ONE 1-D redistribution stage along the named
    mesh axis ``name`` (ring size ``p``) — the rotate/allgather/slice
    step algebra, parameterized so the 1-D program uses it directly and
    the grid program chains one stage per mesh axis.  ``src_len`` /
    ``dst_len`` are the stage-global extents of the moving dims (the
    whole-array extents for a 1-D plan; the current padded extents of a
    grid stage, whose other sharded dims are already local inside the
    grid ``shard_map``)."""
    if dst is not None:
        w_d = -(-dst_len // p)
        pad_d = p * w_d - dst_len

    if src is None:
        # replicated -> split: pad (maybe) + dynamic-slice discard
        def kernel(x):
            if pad_d:
                x = _pad_axis(x, dst, pad_d)
            i = jax.lax.axis_index(name)
            return jax.lax.dynamic_slice_in_dim(x, i * w_d, w_d, axis=dst)

    elif dst is None:
        # split -> replicated: all-gather fraction (compressed ring when
        # the precision policy says so — quantize once, forward bytes)
        def kernel(x):
            if mode is None:
                return jax.lax.all_gather(x, name, axis=src, tiled=True)
            moved = jnp.moveaxis(x, src, 0)
            stacked = _cq.ring_allgather_q(moved, name, size=p, mode=mode, block=BLOCK)
            full = stacked.reshape((p * moved.shape[0],) + moved.shape[1:])
            return jnp.moveaxis(full, 0, src)

    else:
        # split -> split: view the local slab as p destination pieces,
        # keep our own, rotate the other p-1 to their owners
        w_s = src_len // p

        def kernel(x):
            if pad_d:
                x = _pad_axis(x, dst, pad_d)
            i = jax.lax.axis_index(name)
            out_shape = list(x.shape)
            out_shape[src] = p * w_s
            out_shape[dst] = w_d
            out = jnp.zeros(tuple(out_shape), x.dtype)

            def piece_at(j):
                return jax.lax.dynamic_slice_in_dim(x, j * w_d, w_d, axis=dst)

            out = jax.lax.dynamic_update_slice_in_dim(
                out, piece_at(i), i * w_s, axis=src
            )
            pshape = tuple(
                w_d if a == dst else d for a, d in enumerate(x.shape)
            )

            def send(k):
                perm = [(t, (t + k) % p) for t in range(p)]
                return _ship_send(
                    _ship_start(piece_at((i + k) % p), mode), name, perm
                )

            if overlapped:
                # pipelined rotations: the p-1 ships are data-independent,
                # so rotation k+1's encode + ppermute is issued before
                # rotation k's decode + update — at most two pieces in
                # flight, and each hop's wire hides behind the previous
                # hop's decode math.  Same encode/decode per piece, updates
                # at distinct offsets: bitwise-equal to the serial arm.
                inflight = send(1)
                for k in range(1, p):
                    nxt = send(k + 1) if k + 1 < p else None
                    pc = _ship_finish(inflight, mode, pshape, x.dtype)
                    out = jax.lax.dynamic_update_slice_in_dim(
                        out, pc, ((i - k) % p) * w_s, axis=src
                    )
                    inflight = nxt
            else:
                for k in range(1, p):
                    pc = _ship_finish(send(k), mode, pshape, x.dtype)
                    out = jax.lax.dynamic_update_slice_in_dim(
                        out, pc, ((i - k) % p) * w_s, axis=src
                    )
            return out

    return kernel


def _make_program(p_obj: Plan, comm):
    """Build the one compiled program executing ``p_obj`` — a single
    ``shard_map`` whose body runs every step of the schedule (a chain of
    per-mesh-axis ``shard_map`` stages inside the one program for grid
    plans)."""
    if not p_obj.steps:  # identity: let apply_sharding's no-op path handle it
        return None
    if p_obj.mesh_shape is not None:
        return _make_grid_program(p_obj, comm)
    mesh, name = comm._mesh, comm.axis_name
    p = p_obj.size
    src, dst, mode = p_obj.src, p_obj.dst, p_obj.mode
    shape = p_obj.global_shape
    ndim = len(shape)
    # pipelined rotation schedule under the overlap policy (in every
    # compiled-program cache key via the registered token)
    overlapped = overlap_enabled(p)

    kernel = _axis_kernel(
        name, p, src, dst,
        shape[src] if src is not None else 0,
        shape[dst] if dst is not None else 0,
        mode, overlapped,
    )
    in_spec = PartitionSpec() if src is None else comm.spec(ndim, src)
    out_spec = PartitionSpec() if dst is None else comm.spec(ndim, dst)

    def _f(x):
        return shard_map(
            kernel, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
            check_vma=False,
        )(x)

    return _f


def _make_grid_program(p_obj: Plan, comm):
    """The one compiled program of a grid plan: a chain of per-mesh-axis
    1-D stages (each a ``shard_map`` over the full grid mesh whose body
    moves data along ONE named axis via :func:`_axis_kernel`), executed
    inside a single ``jitted`` program — one dispatch for the whole
    factored schedule.  Stage order, extents, and wire modes are replayed
    from :func:`heat_tpu.comm._costs.grid_plan_cost`, the same arithmetic
    the plan's byte figures came from."""
    mesh = comm._mesh
    names = comm.axis_names
    mesh_shape = p_obj.mesh_shape
    shape = p_obj.global_shape
    ndim = len(shape)
    cost = _costs.grid_plan_cost(
        shape, p_obj.dtype, p_obj.src, p_obj.dst, mesh_shape,
        mode_for=lambda nbytes: _cq.reduce_mode(p_obj.dtype, nbytes),
    )
    state = list(p_obj.src)
    ext = list(shape)
    stage_fns = []
    for (g, sd, td), mode in zip(cost["stages"], cost["stage_modes"]):
        p = mesh_shape[g]
        kernel = _axis_kernel(
            names[g], p, sd, td,
            ext[sd] if sd is not None else 0,
            ext[td] if td is not None else 0,
            mode, overlap_enabled(p),
        )
        in_spec = comm.spec(ndim, tuple(state))
        if sd is not None:
            state[sd] = None
        if td is not None:
            state[td] = g
            ext[td] = p * (-(-ext[td] // p))
        out_spec = comm.spec(ndim, tuple(state))
        stage_fns.append((kernel, in_spec, out_spec))

    def _f(x):
        for kernel, in_spec, out_spec in stage_fns:
            x = shard_map(
                kernel, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
                check_vma=False,
            )(x)
        return x

    return _f


def redistribute(
    array,
    split: Optional[int],
    comm=None,
    *,
    src: Optional[int] = None,
    max_live_bytes: Optional[int] = None,
):
    """Redistribute a global array to ``split`` via the planned schedule.

    The explicit entry point under the policy seam: plans (cached), then
    executes the schedule as ONE compiled dispatch, crediting the
    telemetry ledger.  ``src`` defaults to the array's committed split
    axis.  Values are bitwise-identical to the monolithic reshard; a
    ragged destination axis comes back padded to its canonical length
    (the :meth:`~heat_tpu.core.communication.XlaCommunication.commit_split`
    contract).
    """
    from ..core.communication import sanitize_comm

    comm = sanitize_comm(comm)
    if comm.mesh_ndim > 1:
        if src is None:
            src = comm._splits_of(array)
        p_obj = plan(
            tuple(int(s) for s in array.shape), array.dtype, src, split,
            comm.size, mesh_shape=comm.mesh_shape,
            max_live_bytes=max_live_bytes,
        )
        return execute(array, p_obj, comm)
    if src is None:
        src = comm._split_axis_of(array)
    p_obj = plan(
        tuple(int(s) for s in array.shape), array.dtype, src, split, comm.size,
        max_live_bytes=max_live_bytes,
    )
    return execute(array, p_obj, comm)


def grid_redistribute_or_none(array, dst_splits, comm, allow_pad: bool):
    """The N-D-mesh redistribution-policy seam behind
    :meth:`XlaCommunication.resplit` / ``commit_split``: the planned grid
    result, or None when the change stays on the monolithic path.

    Fallback mirrors the 1-D ``_planned_resplit`` contract: policy
    "monolithic"; tracers and fuse traces; host values and empty arrays;
    multi-process meshes; sources committed on a foreign mesh, ragged, or
    non-canonical; ragged destinations when the caller's contract forbids
    padding.  Policy "auto" additionally demands a sharded→sharded change
    of at least :func:`get_redistribution_threshold` bytes.
    """
    from ..core._tracing import in_trace

    policy = get_redistribution()
    if policy == "monolithic" or comm.size == 1:
        return None
    if isinstance(array, jax.core.Tracer) or in_trace():
        return None
    if not isinstance(array, jax.Array) or not getattr(array, "ndim", 0):
        return None
    if any(int(s) == 0 for s in array.shape) or jax.process_count() > 1:
        return None
    mesh_shape = comm.mesh_shape
    dst = tuple(dst_splits)
    src = comm._splits_of(array)
    if any(g is not None for g in src):
        if getattr(array.sharding, "mesh", None) != comm._mesh:
            return None
        if any(
            g is not None and int(array.shape[d]) % mesh_shape[g]
            for d, g in enumerate(src)
        ):
            return None  # ragged source: monolithic handles it replicated
    if src == dst:
        return None  # no-op: apply_sharding's early-outs are cheaper
    if not allow_pad and any(
        g is not None and int(array.shape[d]) % mesh_shape[g]
        for d, g in enumerate(dst)
    ):
        return None
    if policy == "auto" and (
        all(g is None for g in src)
        or all(g is None for g in dst)
        or _nelems(array.shape) * jnp.dtype(array.dtype).itemsize
        < get_redistribution_threshold()
    ):
        return None
    p_obj = plan(
        tuple(int(s) for s in array.shape), array.dtype, src, dst, comm.size,
        mesh_shape=mesh_shape,
    )
    return execute(array, p_obj, comm)


def _nelems(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def execute(array, p_obj: Plan, comm):
    """Run a :class:`Plan` on ``array`` as one compiled dispatch."""
    if tuple(int(s) for s in array.shape) != p_obj.global_shape:
        raise ValueError(
            f"plan was built for shape {p_obj.global_shape}, got {tuple(array.shape)}"
        )
    fn_make = _make_program(p_obj, comm)
    if fn_make is None:  # no-op plan: just certify the layout
        return comm.apply_sharding(array, p_obj.dst)
    # out_shardings pins the exact committed spec form: shard_map's
    # out_specs normalize trailing Nones away, and the result must
    # compare EQUAL to the monolithic reshard's sharding (callers use
    # sharding equality for their no-op early-outs)
    out_sh = comm.sharding(len(p_obj.global_shape), p_obj.dst)
    plan_sig = p_obj.key  # plain data: (shape, dtype, src, dst, size, mode, steps)
    fn = jitted(
        ("comm.resplit", comm, plan_sig), lambda: fn_make,
        jit_kwargs={"out_shardings": out_sh},
    )
    eager = not isinstance(array, jax.core.Tracer)
    if _tel.enabled and eager:
        _tel.account_bytes(
            "resplit", p_obj.mode or "f32", p_obj.exact_wire_bytes, p_obj.wire_bytes
        )
        _tel.inc("comm.resplit.planned")
        ring_ov = overlap_enabled(p_obj.size) and any(
            s[0] == "rotate" for s in p_obj.steps
        )
        with _tel.span(
            "comm:resplit", "comm",
            src=p_obj.src, dst=p_obj.dst, mesh=p_obj.size,
            steps=len(p_obj.steps), mode=p_obj.mode or "f32",
        ):
            return timed_dispatch("resplit", ring_ov, lambda: fn(array))
    return fn(array)
