"""Per-rule checkers for the SPMD-correctness analyzer.

Each checker walks one :class:`~heat_tpu.analysis.core.FileContext` and
yields findings.  Rule SPMD101 is *hybrid* static/dynamic: permutation
builders are fixed at trace time (the whole point — ppermute perms are
compile-time metadata), so the checker extracts the builder expression and
EVALUATES it for every mesh size 1..8, checking that each result is a
valid partial bijection.  The evaluation sandbox executes only
module-level ``def`` source from the analyzed file plus arithmetic
builtins — never imports, never jax.
"""

from __future__ import annotations

import ast
import builtins
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .core import _FUNC_TYPES, FileContext
from .rules import Finding, rule

__all__ = [
    "MESH_SIZES",
    "check_partial_bijection",
    "verify_ring_schedule",
    "verify_zigzag_builders",
]

#: every perm builder is evaluated for these mesh sizes — 1 (degenerate),
#: powers of two (real TPU slices), and the awkward primes the test
#: matrix also sweeps
MESH_SIZES = tuple(range(1, 9))

_SIZE_NAMES = {"size", "p", "n", "world_size", "num_devices", "mesh_size"}

_SAFE_BUILTINS = {
    k: getattr(builtins, k)
    for k in (
        "range", "len", "min", "max", "abs", "enumerate", "zip", "sum",
        "list", "tuple", "sorted", "reversed", "int", "divmod",
    )
}


# --------------------------------------------------------------------- #
# permutation ground truth (shared with the runtime property tests)      #
# --------------------------------------------------------------------- #
def check_partial_bijection(perm, size: int) -> Optional[str]:
    """Validate one ppermute permutation for mesh ``size``: pairs of ints
    in range, no duplicated source, no duplicated destination (partial
    perms are legal — absent destinations receive zeros).  Returns an
    error string or None."""
    try:
        pairs = [(int(s), int(d)) for s, d in perm]
    except (TypeError, ValueError):
        return f"not a sequence of (src, dst) pairs: {perm!r}"
    srcs = [s for s, _ in pairs]
    dsts = [d for _, d in pairs]
    bad = [x for x in srcs + dsts if not 0 <= x < size]
    if bad:
        return f"index {bad[0]} out of range for mesh size {size}"
    if len(set(srcs)) != len(srcs):
        dup = sorted(s for s in set(srcs) if srcs.count(s) > 1)
        return f"duplicate source(s) {dup} at mesh size {size}"
    if len(set(dsts)) != len(dsts):
        dup = sorted(d for d in set(dsts) if dsts.count(d) > 1)
        return f"duplicate destination(s) {dup} at mesh size {size} (collision: two shards write one slot)"
    return None


def verify_ring_schedule(ring_source, sizes: Sequence[int] = MESH_SIZES) -> Optional[str]:
    """Check ``ring_source(position, round, size)`` against the +1 ring
    rotation it documents: simulate ``[(i, (i+1) % size)]`` applied
    ``round`` times and compare origins."""
    for s in sizes:
        origins = list(range(s))
        for r in range(s):
            for pos in range(s):
                if ring_source(pos, r, s) != origins[pos]:
                    return (
                        f"ring_source({pos}, {r}, {s}) = {ring_source(pos, r, s)}"
                        f" but the +1 rotation delivers block {origins[pos]}"
                    )
            origins = [origins[(pos - 1) % s] for pos in range(s)]
    return None


def verify_zigzag_builders(
    zigzag_perms=None,
    zigzag_inverse_perms=None,
    zigzag_chunk_owner=None,
    sizes: Sequence[int] = MESH_SIZES,
) -> Optional[str]:
    """Full-bijection + round-trip checks for the zig-zag resplit
    schedules.  Each stream perm must be a TOTAL bijection (every device
    sends and receives exactly once), and forward-then-inverse must
    restore the contiguous chunk layout."""
    for s in sizes:
        streams = {}
        if zigzag_perms is not None:
            streams["zigzag_perms"] = zigzag_perms(s)
        if zigzag_inverse_perms is not None:
            streams["zigzag_inverse_perms"] = zigzag_inverse_perms(s)
        for name, perms in streams.items():
            for k, perm in enumerate(perms):
                err = check_partial_bijection(perm, s)
                if err is None and len({d for _, d in perm}) != s:
                    err = f"stream does not cover every device at size {s}"
                if err:
                    return f"{name}({s}) stream {k}: {err}"
        if zigzag_perms is not None and zigzag_chunk_owner is not None:
            fwd = zigzag_perms(s)
            for i in range(s):
                for k in (0, 1):
                    dst = dict(fwd[k])[i]
                    want = zigzag_chunk_owner(2 * i + k, s)
                    if dst != want:
                        return (
                            f"zigzag_perms({s}) sends chunk {2 * i + k} to "
                            f"{dst}, zigzag_chunk_owner says {want}"
                        )
        if zigzag_perms is not None and zigzag_inverse_perms is not None:
            # forward then inverse must restore the contiguous layout:
            # chunk c starts at device c // 2, comes home to c // 2
            fwd, inv = zigzag_perms(s), zigzag_inverse_perms(s)
            for c in range(2 * s):
                home = dict(fwd[c % 2])[c // 2]
                # at its zig-zag home the chunk is the low half iff c < s;
                # low halves ride the even-chunk stream of the inverse
                stream = inv[0] if (c < s) == (home % 2 == 0) else inv[1]
                back = dict(stream)[home]
                if back != c // 2:
                    return (
                        f"zig-zag round trip broken at size {s}: chunk {c} "
                        f"returns to device {back}, expected {c // 2}"
                    )
    return None


# --------------------------------------------------------------------- #
# sandboxed evaluation of perm expressions                               #
# --------------------------------------------------------------------- #
class _Unresolvable(Exception):
    pass


def _module_def_env(ctx: FileContext) -> Dict[str, object]:
    """Exec every module-level ``def`` from source into one shared env.
    Definition never runs the body, so jax-using helpers exec fine and
    only fail (NameError) if a perm expression actually calls them —
    which we catch and treat as unverifiable."""
    env: Dict[str, object] = {"__builtins__": _SAFE_BUILTINS}
    for st in ctx.tree.body:
        if isinstance(st, ast.FunctionDef):
            src = ast.get_source_segment(ctx.source, st)
            if src is None:
                continue
            try:
                exec(compile(ast.parse(src), f"<{ctx.relpath}>", "exec"), env)
            except Exception:
                continue
    return env


def _free_names(expr: ast.AST) -> List[str]:
    bound = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.comprehension):
            for t in ast.walk(node.target):
                if isinstance(t, ast.Name):
                    bound.add(t.id)
        elif isinstance(node, ast.Lambda):
            bound.update(a.arg for a in node.args.args)
    out = []
    for node in ast.walk(expr):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            if node.id not in bound and node.id not in _SAFE_BUILTINS:
                out.append(node.id)
    return out


def _eval_expr(ctx: FileContext, expr: ast.AST, at: ast.AST, size: int,
               env: Dict[str, object], depth: int = 0):
    """Evaluate ``expr`` with mesh-size variables bound to ``size``.
    Free names resolve through (in order): the module-def env, nearest
    assignment (constants, ``*.size`` attributes, recursively evaluable
    expressions), parameter defaults, and the size-name convention."""
    if depth > 6:
        raise _Unresolvable("resolution too deep")
    local: Dict[str, object] = {}
    params = {}
    for fn in ctx.enclosing_functions(at):
        if isinstance(fn, ast.Lambda):
            args = fn.args
        else:
            args = fn.args
        names = [a.arg for a in args.args + args.kwonlyargs]
        defaults = list(args.defaults)
        for name, default in zip(reversed(args.args), reversed(defaults)):
            params.setdefault(name.arg, default)
        for name in names:
            params.setdefault(name, None)
    for name in _free_names(expr):
        if name in env or name in local:
            continue
        rec = ctx.lookup(name, at)
        if rec is not None and rec[0] == "expr":
            val = rec[1]
            if isinstance(val, ast.Constant):
                local[name] = val.value
                continue
            if isinstance(val, ast.Attribute) and val.attr == "size":
                local[name] = size
                continue
            try:
                local[name] = _eval_expr(ctx, val, at, size, env, depth + 1)
                continue
            except _Unresolvable:
                pass
        if name in params:
            default = params[name]
            if name in _SIZE_NAMES:
                local[name] = size
                continue
            if isinstance(default, ast.Constant) and default.value is not None:
                local[name] = default.value
                continue
            raise _Unresolvable(f"parameter {name!r}")
        if name in _SIZE_NAMES:
            local[name] = size
            continue
        raise _Unresolvable(f"name {name!r}")
    code = compile(ast.Expression(body=_strip_locations(expr)), "<perm>", "eval")
    merged = dict(env)
    merged.update(local)
    try:
        return eval(code, merged)
    except _UnresolvableErrors as e:
        raise _Unresolvable(str(e))


_UnresolvableErrors = (NameError, AttributeError, TypeError, ValueError, IndexError, KeyError)


def _strip_locations(expr: ast.AST) -> ast.AST:
    import copy

    new = copy.deepcopy(expr)
    return ast.fix_missing_locations(
        ast.copy_location(new, ast.Expr(lineno=1, col_offset=0))
    )


#: builders whose results SPMD101 verifies whenever the analyzed file
#: defines them — the schedule metadata of the zig-zag causal ring
_BUILDER_NAMES = ("zigzag_perms", "zigzag_inverse_perms", "zigzag_chunk_owner", "ring_source")


@rule("SPMD101", "ppermute permutations must be statically-valid bijections", dynamic=True)
def check_ppermute_bijection(ctx: FileContext) -> Iterable[Finding]:
    """Every ``jax.lax.ppermute`` perm that is visible as a comprehension,
    a literal, or a call into a local builder is evaluated for mesh sizes
    1..8 and validated as a partial bijection (distinct sources, distinct
    destinations, indices in range).  Files defining the zig-zag /ring
    schedule builders additionally get their cycle structure verified
    against simulation."""
    env = None  # built lazily: most files have no ppermute at all

    def get_env():
        nonlocal env
        if env is None:
            env = _module_def_env(ctx)
        return env

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not ctx.resolves_to(node.func, "ppermute"):
            continue
        perm_expr = None
        if len(node.args) >= 3:
            perm_expr = node.args[2]
        else:
            for kw in node.keywords:
                if kw.arg == "perm":
                    perm_expr = kw.value
        if perm_expr is None:
            continue
        expr, at = perm_expr, node
        if isinstance(expr, ast.Name):
            rec = ctx.lookup(expr.id, node)
            if rec is None:
                continue  # parameter or unknown: checked at its builder
            if rec[0] == "expr":
                expr = rec[1]
            else:  # tuple-unpack from a builder call
                call, idx = rec[1], rec[2]
                expr = ast.Subscript(
                    value=call, slice=ast.Constant(value=idx), ctx=ast.Load()
                )
        if isinstance(expr, ast.Name):
            continue  # parameter-fed perms are validated at the builder
        for size in MESH_SIZES:
            try:
                perm = _eval_expr(ctx, expr, at, size, get_env())
            except _Unresolvable:
                break  # not statically evaluable here: builder-site duty
            err = check_partial_bijection(perm, size)
            if err:
                yield ctx.finding(
                    "SPMD101", node,
                    f"ppermute perm is not a valid permutation: {err}",
                    hint="every (src, dst) pair needs distinct sources and "
                    "distinct destinations in [0, mesh size); rebuild the "
                    "perm from the mesh size, not from data",
                )
                break

    # schedule builders defined here: verify cycle structure by simulation
    defs = {
        name: ctx.module_function(name)
        for name in _BUILDER_NAMES
        if ctx.module_function(name) is not None
    }
    if defs:
        env = get_env()
        have = {k: env.get(k) for k in defs if callable(env.get(k))}
        err = None
        if "ring_source" in have:
            err = verify_ring_schedule(have["ring_source"])
            anchor = defs["ring_source"]
        if err is None and ("zigzag_perms" in have or "zigzag_inverse_perms" in have):
            err = verify_zigzag_builders(
                zigzag_perms=have.get("zigzag_perms"),
                zigzag_inverse_perms=have.get("zigzag_inverse_perms"),
                zigzag_chunk_owner=have.get("zigzag_chunk_owner"),
            )
            anchor = defs.get("zigzag_perms") or defs.get("zigzag_inverse_perms")
        if err:
            yield ctx.finding(
                "SPMD101", anchor,
                f"schedule builder fails simulation: {err}",
                hint="the perm-builder contract is checked for mesh sizes "
                "1..8 against a direct simulation of the ring/zig-zag "
                "layout; see tests/test_spmdlint.py for the ground truth",
            )


# --------------------------------------------------------------------- #
# SPMD102: collective axis names vs the enclosing shard_map              #
# --------------------------------------------------------------------- #
#: collective leaf name -> positional index of its axis-name argument
_COLLECTIVES = {
    "psum": 1, "pmax": 1, "pmin": 1, "pmean": 1, "ppermute": 1,
    "all_gather": 1, "all_to_all": 1, "psum_scatter": 1, "pshuffle": 1,
    "pbroadcast": 1, "pcast": 1, "axis_index": 0,
    # heat_tpu.comm.compressed ring collectives (in-kernel forms)
    "ring_allreduce_q": 1, "ring_allreduce_q_ef": 2, "ring_allgather_q": 1,
    "allreduce_q": 6,
}


def _axis_exprs_of_collective(call: ast.Call, leaf: str) -> List[ast.AST]:
    idx = _COLLECTIVES[leaf]
    expr = None
    if len(call.args) > idx:
        expr = call.args[idx]
    else:
        for kw in call.keywords:
            if kw.arg in ("axis_name", "axes", "axis"):
                expr = kw.value
    if expr is None:
        return []
    if isinstance(expr, (ast.Tuple, ast.List)):
        return list(expr.elts)
    return [expr]


def _is_axis_name_binding(ctx: FileContext, name: str, at: ast.AST) -> bool:
    rec = ctx.lookup(name, at)
    return (
        rec is not None
        and rec[0] == "expr"
        and isinstance(rec[1], ast.Attribute)
        and rec[1].attr == "axis_name"
    )


@rule("SPMD102", "collective axis names must match the enclosing shard_map mesh axis")
def check_axis_names(ctx: FileContext) -> Iterable[Finding]:
    """Inside each ``shard_map`` kernel, every collective's axis-name
    argument must be (a) one of the axis expressions named by the
    PartitionSpecs of the shard_map's in/out specs, (b) a variable bound
    from some ``*.axis_name``, or (c) a parameter (the helper-function
    pass-through, validated at its call sites).  Anything else is a
    mesh/axis mismatch waiting for a different mesh to crash on."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not ctx.resolves_to(node.func, "shard_map"):
            continue
        kernel = ctx._fn_node_of(node.args[0], node) if node.args else None
        if kernel is None:
            for kw in node.keywords:
                if kw.arg == "f":
                    kernel = ctx._fn_node_of(kw.value, node)
        if kernel is None:
            continue
        spec_tokens: set = set()
        spec_strings: set = set()
        for kw in node.keywords:
            if kw.arg not in ("in_specs", "out_specs"):
                continue
            for sub in ast.walk(kw.value):
                if isinstance(sub, ast.Call) and ctx.resolves_to(
                    sub.func, "PartitionSpec", "P"
                ):
                    for a in sub.args:
                        if isinstance(a, ast.Constant):
                            if isinstance(a.value, str):
                                spec_strings.add(a.value)
                        elif isinstance(a, (ast.Name, ast.Attribute)):
                            spec_tokens.add(ast.dump(_strip_locations(a)))

        kernel_params = {a.arg for a in kernel.args.args + kernel.args.kwonlyargs}
        for sub in ast.walk(kernel):
            if not isinstance(sub, ast.Call):
                continue
            dotted = ctx.resolve(sub.func) or ""
            leaf = dotted.rsplit(".", 1)[-1]
            if leaf not in _COLLECTIVES:
                continue
            if not (
                "jax" in dotted
                or "lax" in dotted
                or dotted == leaf
                or "compressed" in dotted
            ):
                continue
            for expr in _axis_exprs_of_collective(sub, leaf):
                if isinstance(expr, ast.Constant):
                    if expr.value is None:
                        continue
                    if spec_strings and expr.value in spec_strings:
                        continue
                    if not spec_strings and not spec_tokens:
                        continue  # specs not statically visible
                    yield ctx.finding(
                        "SPMD102", sub,
                        f"collective {leaf!r} names axis {expr.value!r}, "
                        f"not an axis of the enclosing shard_map "
                        f"({sorted(spec_strings) or 'symbolic specs'})",
                        hint="use the mesh axis named in the shard_map's "
                        "PartitionSpecs (conventionally the variable bound "
                        "from comm.axis_name)",
                    )
                    continue
                if isinstance(expr, ast.Name):
                    enclosing_params = set(kernel_params)
                    for fn in ctx.enclosing_functions(sub):
                        enclosing_params.update(
                            a.arg for a in fn.args.args + fn.args.kwonlyargs
                        )
                    if expr.id in enclosing_params:
                        continue  # pass-through: call sites carry the proof
                    if ast.dump(_strip_locations(expr)) in spec_tokens:
                        continue
                    if _is_axis_name_binding(ctx, expr.id, sub):
                        continue
                    yield ctx.finding(
                        "SPMD102", sub,
                        f"collective {leaf!r} axis {expr.id!r} does not "
                        "match the enclosing shard_map's mesh axis",
                        hint="bind the axis once (`name = comm.axis_name`) "
                        "and use that same variable in the PartitionSpecs "
                        "and every collective",
                    )
                elif isinstance(expr, ast.Attribute):
                    if expr.attr == "axis_name":
                        continue
                    if ast.dump(_strip_locations(expr)) in spec_tokens:
                        continue
                    yield ctx.finding(
                        "SPMD102", sub,
                        f"collective {leaf!r} axis expression is not the "
                        "enclosing shard_map's mesh axis",
                        hint="pass the axis name bound from comm.axis_name",
                    )


# --------------------------------------------------------------------- #
# SPMD201: trace purity                                                  #
# --------------------------------------------------------------------- #
_BANNED_CALLS = {
    "time.time": "wall-clock reads bake one value into the compiled program",
    "time.perf_counter": "wall-clock reads bake one value into the compiled program",
    "time.monotonic": "wall-clock reads bake one value into the compiled program",
    "time.sleep": "host sleeps are invisible to the compiled program",
    "print": "host print runs at TRACE time only (once, with tracers)",
    "open": "file I/O at trace time runs once, not per call",
    "input": "blocking host I/O inside a traced function",
    "breakpoint": "debugger traps do not survive tracing",
}
_BANNED_PREFIXES = {
    "numpy.random.": "numpy RNG is host state: traced once, frozen forever "
    "— use jax.random with an explicit key",
    "random.": "stdlib RNG is host state: traced once, frozen forever — "
    "use jax.random with an explicit key",
}


@rule("SPMD201", "no host effects inside jit/shard_map/pallas-traced functions")
def check_trace_purity(ctx: FileContext) -> Iterable[Finding]:
    """Functions handed to ``jit``/``shard_map``/``pallas_call`` (or
    defined inside an op-engine ``jitted`` factory) run ONCE at trace
    time; host effects inside them silently freeze (RNG, clocks) or
    vanish (print, I/O), and ``global`` writes make the cached executable
    depend on hidden state."""
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and ctx.in_traced_context(node):
            dotted = ctx.resolve(node.func)
            if dotted is None:
                continue
            if dotted in _BANNED_CALLS:
                yield ctx.finding(
                    "SPMD201", node,
                    f"host effect {dotted!r} inside a traced function",
                    hint=_BANNED_CALLS[dotted],
                )
                continue
            for prefix, why in _BANNED_PREFIXES.items():
                if dotted.startswith(prefix) and not dotted.startswith("jax."):
                    yield ctx.finding(
                        "SPMD201", node,
                        f"host RNG {dotted!r} inside a traced function",
                        hint=why,
                    )
                    break
        elif isinstance(node, ast.Global) and ctx.in_traced_context(node):
            yield ctx.finding(
                "SPMD201", node,
                f"global-variable write ({', '.join(node.names)}) inside a "
                "traced function",
                hint="traced functions must be pure: thread state through "
                "arguments/carries, or move the mutation outside the jit",
            )


# --------------------------------------------------------------------- #
# SPMD202: host-sync coercions on traced values                          #
# --------------------------------------------------------------------- #
#: method calls that materialize a device value on the host
_SYNC_METHODS = {"item", "tolist", "numpy"}
#: numpy entry points that pull a traced array back to host memory
_NP_MATERIALIZERS = {"asarray", "array", "ascontiguousarray", "asfortranarray"}
#: scalar coercions that force a device→host sync when fed a traced value
_COERCIONS = {"float", "int", "bool", "complex"}
#: attribute leaves that are compile-time metadata, not device values —
#: coercing these is free and legitimate (``int(x.shape[0])``)
_STATIC_ATTRS = {
    "shape", "gshape", "lshape", "ndim", "size", "split", "itemsize",
    "dtype", "balanced",
}
#: array-method reductions whose results are device values
_REDUCTION_METHODS = {
    "sum", "max", "min", "mean", "prod", "norm", "argmax", "argmin",
    "all", "any", "std", "var", "dot", "astype",
}


def _is_static_expr(ctx: FileContext, expr: ast.AST, at: ast.AST, depth: int = 0) -> bool:
    """True when ``expr`` is visibly compile-time metadata (shape/ndim
    arithmetic, constants, ``len()``) — coercing it never touches the
    device."""
    if depth > 5:
        return False
    if isinstance(expr, ast.Constant):
        return True
    if isinstance(expr, ast.Attribute):
        return expr.attr in _STATIC_ATTRS
    if isinstance(expr, ast.Subscript):
        return _is_static_expr(ctx, expr.value, at, depth + 1)
    if isinstance(expr, ast.BinOp):
        return _is_static_expr(ctx, expr.left, at, depth + 1) and _is_static_expr(
            ctx, expr.right, at, depth + 1
        )
    if isinstance(expr, ast.UnaryOp):
        return _is_static_expr(ctx, expr.operand, at, depth + 1)
    if isinstance(expr, ast.Call):
        return isinstance(expr.func, ast.Name) and expr.func.id == "len"
    if isinstance(expr, ast.Name):
        rec = ctx.lookup(expr.id, at)
        if rec is not None and rec[0] == "expr":
            return _is_static_expr(ctx, rec[1], at, depth + 1)
    return False


def _is_device_value_expr(ctx: FileContext, expr: ast.AST) -> bool:
    """True when ``expr`` visibly produces a device value: any ``jax.*``
    call, an array-method reduction, or a ``.larray``/``._buffer``
    access anywhere inside it."""
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Call):
            dotted = ctx.resolve(sub.func) or ""
            if dotted.startswith("jax.") or dotted == "jax":
                return True
            if isinstance(sub.func, ast.Attribute) and sub.func.attr in _REDUCTION_METHODS:
                return True
        elif isinstance(sub, ast.Attribute) and sub.attr in ("larray", "_buffer"):
            return True
    return False


@rule("SPMD202", "no host-sync coercions of traced values inside traced functions")
def check_host_sync(ctx: FileContext) -> Iterable[Finding]:
    """Inside functions traced by ``jit``/``shard_map``/``fuse`` (or
    nested in an op-engine ``jitted`` factory), value-forcing operations —
    ``.item()``/``.tolist()``/``.numpy()``, ``np.asarray``/``np.array``,
    and ``float()``/``int()``/``bool()``/``complex()`` of device values —
    either crash on the tracer (``TracerConversionError`` / heat_tpu's
    ``FuseTraceError``) or, worse, silently freeze a trace-time constant
    into the compiled program.  Coercions of static metadata
    (``int(x.shape[0])``) are exempt; a bare-name coercion is flagged only
    when its assignment visibly produced a device value, so python-int
    loop bookkeeping never trips it."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not ctx.in_traced_context(node):
            continue
        if isinstance(node.func, ast.Attribute) and node.func.attr in _SYNC_METHODS:
            yield ctx.finding(
                "SPMD202", node,
                f"host-sync method .{node.func.attr}() inside a traced function",
                hint="the result is a tracer, not a value: keep the "
                "computation on-device (jnp.where / lax.cond) or move "
                "this step outside the traced function",
            )
            continue
        dotted = ctx.resolve(node.func) or ""
        leaf = dotted.rsplit(".", 1)[-1]
        if leaf in _NP_MATERIALIZERS and dotted.startswith("numpy."):
            yield ctx.finding(
                "SPMD202", node,
                f"numpy materialization {dotted!r} inside a traced function",
                hint="np.asarray on a tracer forces a host copy (or "
                "crashes); use jnp equivalents so the value stays in the "
                "compiled program",
            )
            continue
        if (
            isinstance(node.func, ast.Name)
            and node.func.id in _COERCIONS
            and node.func.id not in ctx.aliases  # shadowed by an import
            and len(node.args) == 1
        ):
            arg = node.args[0]
            if _is_static_expr(ctx, arg, node):
                continue
            flagged = _is_device_value_expr(ctx, arg)
            if not flagged and isinstance(arg, ast.Name):
                rec = ctx.lookup(arg.id, node)
                flagged = (
                    rec is not None
                    and rec[0] == "expr"
                    and _is_device_value_expr(ctx, rec[1])
                )
            if flagged:
                yield ctx.finding(
                    "SPMD202", node,
                    f"scalar coercion {node.func.id}() of a device value "
                    "inside a traced function",
                    hint="this blocks on device→host transfer per call (or "
                    "raises under fuse); keep the decision on-device with "
                    "jnp.where / lax.cond, or hoist the sync out of the "
                    "traced region",
                )


# --------------------------------------------------------------------- #
# SPMD203: quantized collectives must carry inexact payloads             #
# --------------------------------------------------------------------- #
#: quantized-collective leaf name -> positional index of its payload
_QUANTIZED_COLLECTIVES = {
    "ring_allreduce_q": 0, "ring_allreduce_q_ef": 0, "ring_allgather_q": 0,
    "allreduce_q": 0, "allgather_q": 0, "quantize_blocks": 0,
}
#: dtype leaves whose values must survive a collective bit-exactly
_EXACT_DTYPE_LEAVES = {
    "int8", "int16", "int32", "int64", "uint8", "uint16", "uint32",
    "uint64", "bool_", "bool", "integer", "signedinteger",
}


def _exact_dtype_expr(ctx: FileContext, expr: ast.AST) -> Optional[str]:
    """The integer/bool dtype named by ``expr`` (``jnp.int32``,
    ``"int64"``, ...), or None when it is not visibly exact."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return expr.value if expr.value in _EXACT_DTYPE_LEAVES else None
    dotted = ctx.resolve(expr) or ""
    leaf = dotted.rsplit(".", 1)[-1]
    return leaf if leaf in _EXACT_DTYPE_LEAVES else None


def _visibly_exact_payload(
    ctx: FileContext, expr: ast.AST, at: ast.AST, depth: int = 0
) -> Optional[str]:
    """The exact dtype ``expr`` visibly carries, or None.  Follows
    ``.astype(...)`` tails, ``dtype=`` keywords of constructors, and
    single-assignment name bindings (same lookup discipline as SPMD202's
    device-value tracking)."""
    if depth > 5:
        return None
    if isinstance(expr, ast.Call):
        if isinstance(expr.func, ast.Attribute) and expr.func.attr == "astype":
            if expr.args:
                return _exact_dtype_expr(ctx, expr.args[0])
            for kw in expr.keywords:
                if kw.arg == "dtype":
                    return _exact_dtype_expr(ctx, kw.value)
            return None
        for kw in expr.keywords:
            if kw.arg == "dtype":
                return _exact_dtype_expr(ctx, kw.value)
        return None
    if isinstance(expr, ast.Name):
        rec = ctx.lookup(expr.id, at)
        if rec is not None and rec[0] == "expr":
            return _visibly_exact_payload(ctx, rec[1], at, depth + 1)
    return None


@rule("SPMD203", "quantized collectives must not carry integer/exact-dtype payloads")
def check_quantized_payload_dtype(ctx: FileContext) -> Iterable[Finding]:
    """Block-scaled quantized collectives (``ring_allreduce_q`` and
    friends) round their payload to int8-with-scales: floats degrade
    gracefully, but integer/bool payloads — indices, counts, masks,
    labels — silently corrupt, because a count that comes back 79.6
    instead of 80 is not "less precise", it is wrong.  Flags any quantized
    collective whose payload expression visibly carries an exact dtype
    (``.astype(jnp.int32)``, a ``dtype=jnp.int64`` constructor, or a name
    bound to one).  Exact payloads belong on ``jax.lax.psum`` — the
    runtime twin of this rule is ``reduce_mode``'s TypeError on explicit
    compression of exact dtypes."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = ctx.resolve(node.func) or ""
        leaf = dotted.rsplit(".", 1)[-1]
        if leaf not in _QUANTIZED_COLLECTIVES:
            continue
        if not ("compressed" in dotted or "comm" in dotted or dotted == leaf):
            continue
        idx = _QUANTIZED_COLLECTIVES[leaf]
        if len(node.args) <= idx:
            continue
        dt = _visibly_exact_payload(ctx, node.args[idx], node)
        if dt is not None:
            yield ctx.finding(
                "SPMD203", node,
                f"quantized collective {leaf!r} payload visibly has exact "
                f"dtype {dt!r}",
                hint="int8 block-scaling rounds the payload: integer/bool "
                "values (counts, indices, masks) corrupt silently.  Keep "
                "exact dtypes on jax.lax.psum, or cast to float only if "
                "approximate results are genuinely acceptable",
            )


# --------------------------------------------------------------------- #
# SPMD204: quantized collectives in guard-disabled regions               #
# --------------------------------------------------------------------- #
def _guard_off_call(ctx: FileContext, expr: ast.AST, leaf_name: str) -> bool:
    """True when ``expr`` is a ``guard("off")`` / ``set_guard_policy("off")``
    call (positionally or via ``policy=``) from the resilience layer (or a
    bare name, the fixture/test spelling)."""
    if not isinstance(expr, ast.Call):
        return False
    dotted = ctx.resolve(expr.func) or ""
    if dotted.rsplit(".", 1)[-1] != leaf_name:
        return False
    if not (
        dotted == leaf_name
        or "resilience" in dotted
        or "guards" in dotted
        or "heat_tpu" in dotted
    ):
        return False
    policy = expr.args[0] if expr.args else None
    if policy is None:
        for kw in expr.keywords:
            if kw.arg == "policy":
                policy = kw.value
    return isinstance(policy, ast.Constant) and policy.value == "off"


@rule("SPMD204", "quantized collectives in guard-disabled regions need an explicit suppression")
def check_guard_disabled_collectives(ctx: FileContext) -> Iterable[Finding]:
    """A quantized collective under ``guard("off")`` runs with its
    numerical health checks stripped: non-finite or saturated payloads
    pass through the int8 ring unchallenged, which is precisely the
    failure mode the guards exist to catch.  Flags any quantized
    collective call (``allreduce_q`` and friends, the SPMD203 set) that
    is lexically inside a ``with guard("off")`` block or follows a
    ``set_guard_policy("off")`` call in the same scope, unless the line
    carries ``# spmdlint: disable=SPMD204`` — disabling guards around a
    compressed collective must be a visible, deliberate decision."""
    off_sets: List[Tuple[ast.AST, int]] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and _guard_off_call(ctx, node, "set_guard_policy"):
            encl = ctx.enclosing_functions(node)
            off_sets.append((encl[0] if encl else ctx.tree, node.lineno))

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = ctx.resolve(node.func) or ""
        leaf = dotted.rsplit(".", 1)[-1]
        if leaf not in _QUANTIZED_COLLECTIVES:
            continue
        if not ("compressed" in dotted or "comm" in dotted or dotted == leaf):
            continue
        reason = None
        cur: Optional[ast.AST] = node
        while cur is not None:
            cur = ctx.parents.get(cur)
            if isinstance(cur, ast.With):
                for item in cur.items:
                    if _guard_off_call(ctx, item.context_expr, "guard"):
                        reason = 'a `with guard("off")` block'
                        break
            if reason:
                break
        if reason is None:
            encl = ctx.enclosing_functions(node)
            scope = encl[0] if encl else ctx.tree
            for s, ln in off_sets:
                if s is scope and ln < node.lineno:
                    reason = 'a set_guard_policy("off") call above it'
                    break
        if reason:
            yield ctx.finding(
                "SPMD204", node,
                f"quantized collective {leaf!r} runs inside {reason} "
                "with numerical health guards disabled",
                hint="compressed collectives silently propagate non-finite "
                "or saturated payloads when unguarded; re-enable guards "
                "(policy 'raise'/'warn'/'degrade'), or mark the call with "
                "`# spmdlint: disable=SPMD204` if running unguarded is a "
                "deliberate, reviewed decision",
            )


# --------------------------------------------------------------------- #
# SPMD205: host timing inside traced functions                           #
# --------------------------------------------------------------------- #
#: host clocks whose reading inside a traced body is a trace-time
#: constant — including the `_ns` variants SPMD201 does not list
_TIMING_CALLS = {
    "time.time", "time.time_ns",
    "time.perf_counter", "time.perf_counter_ns",
    "time.monotonic", "time.monotonic_ns",
    "time.process_time", "time.process_time_ns",
}
#: dotted-suffix forms of the telemetry span entry point (`from heat_tpu
#: import telemetry` and the internal `from ..telemetry import _core`)
_SPAN_SUFFIXES = ("telemetry.span", "telemetry._core.span")


@rule("SPMD205", "host-side timing inside traced functions measures trace time, not run time")
def check_trace_timing(ctx: FileContext) -> Iterable[Finding]:
    """A traced body runs ONCE, at trace time, with abstract tracers: a
    ``time.*`` read or a ``telemetry.span`` opened inside it brackets the
    *tracing* of the program — microseconds of Python — not the compiled
    execution it stands for, and the measured value is frozen into the
    cache.  Deliberately overlaps SPMD201 on the wall-clock reads (either
    finding alone should stop the commit) and extends the set with the
    ``_ns``/``process_time`` variants and the telemetry span API, whose
    timing intent makes the trace/run confusion easy to miss."""
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call) and ctx.in_traced_context(node)):
            continue
        dotted = ctx.resolve(node.func)
        if dotted is None:
            continue
        if dotted in _TIMING_CALLS:
            yield ctx.finding(
                "SPMD205", node,
                f"host clock {dotted!r} read inside a traced function",
                hint="the read happens once at trace time and its value is "
                "baked into the compiled program; time the jitted call at "
                "its HOST call site (after block_until_ready), or use "
                "jax.profiler device traces",
            )
        elif any(dotted == s or dotted.endswith("." + s) for s in _SPAN_SUFFIXES):
            yield ctx.finding(
                "SPMD205", node,
                "telemetry.span opened inside a traced function",
                hint="the span brackets TRACING (one-time Python), not the "
                "compiled execution; move the span to the host call site "
                "around the jitted/fused call, as the op engine already "
                "does for its own sites",
            )


# --------------------------------------------------------------------- #
# SPMD206: monolithic resplit inside a loop body                         #
# --------------------------------------------------------------------- #
#: layout-change entry points whose repeated monolithic execution is the
#: worst-case pattern: each iteration pays a full GSPMD reshard
#: (gather+slice envelope) where one hoisted resplit — or the planned
#: rotation schedule — was expected
_RESPLIT_CALLS = {"resplit", "resplit_", "alltoall", "commit_split"}


def _planned_policy_call(ctx: FileContext, expr: ast.AST, leaf_name: str) -> bool:
    """True when ``expr`` is a ``redistribution("planned"|"auto")`` /
    ``set_redistribution("planned"|"auto")`` call (positionally or via
    ``policy=``) from the comm layer (or a bare name, the fixture/test
    spelling) — the exemption: under the planner, a loop-body resplit
    replays one bounded compiled schedule instead of the monolithic
    worst case."""
    if not isinstance(expr, ast.Call):
        return False
    dotted = ctx.resolve(expr.func) or ""
    if dotted.rsplit(".", 1)[-1] != leaf_name:
        return False
    if not (
        dotted == leaf_name
        or "comm" in dotted
        or "redistribute" in dotted
        or "heat_tpu" in dotted
    ):
        return False
    policy = expr.args[0] if expr.args else None
    if policy is None:
        for kw in expr.keywords:
            if kw.arg == "policy":
                policy = kw.value
    return isinstance(policy, ast.Constant) and policy.value in ("planned", "auto")


@rule("SPMD206", "monolithic split→split resplit inside a loop body")
def check_resplit_in_loop(ctx: FileContext) -> Iterable[Finding]:
    """A ``resplit``/``alltoall``/``commit_split`` lexically inside a
    ``for``/``while`` body repeats the framework's single most expensive
    layout primitive every iteration — under the monolithic policy each
    pass is a worst-case GSPMD reshard (all-gather + slice envelope,
    reference ``Alltoallv`` communication.py:764-881).  Almost always
    the change is loop-invariant and hoists, or belongs under the
    planned redistribution policy, whose compiled rotation schedule
    moves ``(p-1)/p²`` of the array per device with bounded peak memory
    and replays from the program cache.  Exempt when the call sits
    inside a ``with redistribution("planned"|"auto")`` block or follows
    a ``set_redistribution("planned"|"auto")`` call in the same scope;
    traced bodies (jit/shard_map/fuse) are also exempt — there the
    "call" is a sharding constraint compiled once, not a per-iteration
    collective."""
    planned_sets: List[Tuple[ast.AST, int]] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and _planned_policy_call(
            ctx, node, "set_redistribution"
        ):
            encl = ctx.enclosing_functions(node)
            planned_sets.append((encl[0] if encl else ctx.tree, node.lineno))

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = ctx.resolve(node.func) or ""
        leaf = dotted.rsplit(".", 1)[-1]
        if leaf not in _RESPLIT_CALLS:
            continue
        # a resplit is a method of a DNDarray/comm object (or the comm
        # module's function) — a bare local helper named `resplit` is
        # not the layout primitive
        if "." not in dotted:
            continue
        if ctx.in_traced_context(node):
            continue
        in_loop = False
        exempt = False
        cur: Optional[ast.AST] = node
        while cur is not None:
            cur = ctx.parents.get(cur)
            if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
                in_loop = True
            if isinstance(cur, ast.With):
                for item in cur.items:
                    if _planned_policy_call(ctx, item.context_expr, "redistribution"):
                        exempt = True
                        break
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break  # loop containment is per-function, not lexical-outward
        if not in_loop or exempt:
            continue
        encl = ctx.enclosing_functions(node)
        scope = encl[0] if encl else ctx.tree
        if any(s is scope and ln < node.lineno for s, ln in planned_sets):
            continue
        yield ctx.finding(
            "SPMD206", node,
            f"monolithic layout change {leaf!r} inside a loop body pays a "
            "worst-case reshard every iteration",
            hint="hoist the resplit out of the loop if the layout is "
            "loop-invariant; otherwise run it under the planned "
            "redistribution policy (ht.comm.set_redistribution('planned') "
            "or `with redistribution(\"planned\")`), whose compiled "
            "schedule is minimal-traffic and memory-bounded — or mark the "
            "call with `# spmdlint: disable=SPMD206` if the per-iteration "
            "monolithic reshard is deliberate",
        )


# --------------------------------------------------------------------- #
# SPMD207: silent broad except around dispatch/collective/io sites       #
# --------------------------------------------------------------------- #
#: exception leaves that catch "anything that can go wrong at a guarded
#: site" — the fault classes the resilience layer exists to make visible
_BROAD_EXC = {"Exception", "BaseException", "OSError", "IOError",
              "EnvironmentError"}

#: call leaves whose failures must never vanish: file opens/loads/saves,
#: checkpoint and loop-snapshot manifests, layout changes, collectives
_GUARDED_SITE_CALLS = {
    "open", "File", "Dataset",
    "load", "save", "load_hdf5", "save_hdf5", "load_netcdf", "save_netcdf",
    "load_csv", "save_csv", "load_loop_state", "save_loop_state",
    "load_estimator", "save_estimator",
    "resplit", "resplit_", "commit_split", "apply_sharding", "redistribute",
    "alltoall", "allreduce", "allgather", "all_gather", "ppermute", "psum",
}


def _broad_handler_names(ctx: FileContext, handler: ast.ExceptHandler) -> List[str]:
    """The broad exception leaves a handler catches (empty = narrow)."""
    t = handler.type
    if t is None:
        return ["(bare except)"]
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    out = []
    for e in elts:
        dotted = ctx.resolve(e) or ""
        leaf = dotted.rsplit(".", 1)[-1]
        if leaf in _BROAD_EXC:
            out.append(leaf)
    return out


def _handler_is_silent(ctx: FileContext, handler: ast.ExceptHandler) -> bool:
    """True when nothing in the handler body makes the fault visible: no
    re-raise, no reference to the caught exception (the deferred-error
    barrier pattern binds it — ``err = e``), no incident record, no
    warning/log call."""
    caught = handler.name
    for stmt in handler.body:
        for sub in ast.walk(stmt):
            if isinstance(sub, ast.Raise):
                return False
            if caught and isinstance(sub, ast.Name) and sub.id == caught:
                return False
            if isinstance(sub, ast.Call):
                dotted = ctx.resolve(sub.func) or ""
                leaf = dotted.rsplit(".", 1)[-1]
                if leaf == "record" or "incident" in dotted:
                    return False
                if leaf in ("warn", "warning", "error", "exception", "critical"):
                    return False
    return True


@rule("SPMD207", "silent broad except around dispatch/collective/io sites")
def check_silent_broad_except(ctx: FileContext) -> Iterable[Finding]:
    """A ``try`` whose body touches a dispatch, collective, or io site
    (file opens/loads/saves, checkpoint manifests, resplits, ring
    collectives) with an ``except Exception``/``except OSError`` handler
    that neither re-raises, nor references the caught exception (the
    deferred-error barrier pattern — ``err = e`` past a collective
    fence), nor records an incident, makes the fault *invisible*: the
    fit continues on garbage, the chaos lane can't see the injection,
    and the retry/elastic machinery never engages.  Transient faults
    belong on the retry engine (``resilience.retry``); real failures
    belong in the incident log (``resilience.incidents.record``) or
    propagated."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Try):
            continue
        guarded_leaf = None
        for stmt in node.body:
            for sub in ast.walk(stmt):
                if not isinstance(sub, ast.Call):
                    continue
                dotted = ctx.resolve(sub.func) or ""
                leaf = dotted.rsplit(".", 1)[-1]
                if leaf in _GUARDED_SITE_CALLS:
                    guarded_leaf = leaf
                    break
            if guarded_leaf:
                break
        if guarded_leaf is None:
            continue
        for handler in node.handlers:
            broad = _broad_handler_names(ctx, handler)
            if not broad or not _handler_is_silent(ctx, handler):
                continue
            yield ctx.finding(
                "SPMD207", handler,
                f"broad `except {broad[0]}` swallows failures of guarded "
                f"site {guarded_leaf!r} without re-raise or incident "
                "record — the fault becomes invisible",
                hint="re-raise after cleanup, bind and defer the exception "
                "past the barrier (err = e), route transients through "
                "resilience.retry, or record it with "
                "resilience.incidents.record(...); mark the handler with "
                "`# spmdlint: disable=SPMD207` if the swallow is deliberate",
            )


# --------------------------------------------------------------------- #
# SPMD208: unbucketed dynamic batch shape entering a compiled program    #
# --------------------------------------------------------------------- #
#: shape-canonicalization helpers: a slice bound routed through one of
#: these is drawn from a finite shape space (powers of two / shard
#: multiples), so the compiled-program cache stays bounded
_BUCKETING_CALLS = {
    "bucket_rows", "next_pow2", "_padded_len", "pad_to_bucket",
    "pad_to_shards", "pad_batch",
}


def _is_compiled_callable(ctx: FileContext, func: ast.AST, at: ast.AST) -> bool:
    """True when ``func`` is a compiled-program value: a direct
    ``fuse(f)(...)`` / ``jitted(key, make)(...)`` product, a name bound
    from one, or a function defined under ``@fuse`` / ``@jax.jit``."""
    if isinstance(func, ast.Call):
        return ctx.resolves_to(func.func, "fuse", "jitted", "jit", "jax.jit")
    if isinstance(func, ast.Name):
        rec = ctx.lookup(func.id, at)
        if (
            rec is not None
            and rec[0] == "expr"
            and isinstance(rec[1], ast.Call)
            and ctx.resolves_to(rec[1].func, "fuse", "jitted", "jit", "jax.jit")
        ):
            return True
        fn = ctx.local_function(func.id, at)
        if isinstance(fn, ast.FunctionDef):
            for dec in fn.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                if ctx.resolves_to(target, "fuse", "jit", "jax.jit"):
                    return True
    return False


def _bound_is_bucketed(ctx: FileContext, bound: ast.AST, at: ast.AST) -> bool:
    """True when the slice bound routes through a bucketing helper —
    directly (``x[:bucket_rows(n)]``) or via one local assignment
    (``b = bucket_rows(n); ... x[:b]``)."""
    for sub in ast.walk(bound):
        if isinstance(sub, ast.Call):
            dotted = ctx.resolve(sub.func) or ""
            if dotted.rsplit(".", 1)[-1] in _BUCKETING_CALLS:
                return True
        if isinstance(sub, ast.Name):
            rec = ctx.lookup(sub.id, at)
            if rec is not None and rec[0] == "expr" and isinstance(rec[1], ast.Call):
                dotted = ctx.resolve(rec[1].func) or ""
                if dotted.rsplit(".", 1)[-1] in _BUCKETING_CALLS:
                    return True
    return False


def _dynamic_slice_operand(
    ctx: FileContext, expr: ast.AST, at: ast.AST
) -> Optional[ast.Subscript]:
    """The offending Subscript when ``expr`` is (or is a name
    once-assigned from) a slice whose bounds are dynamic and unbucketed;
    None otherwise."""
    if isinstance(expr, ast.Name):
        rec = ctx.lookup(expr.id, at)
        if rec is not None and rec[0] == "expr":
            expr = rec[1]
    if not isinstance(expr, ast.Subscript):
        return None
    sl = expr.slice
    slices = [sl] if isinstance(sl, ast.Slice) else (
        [e for e in sl.elts if isinstance(e, ast.Slice)]
        if isinstance(sl, ast.Tuple) else []
    )
    bounds = [b for s in slices for b in (s.lower, s.upper) if b is not None]
    dynamic = [
        b for b in bounds
        if any(isinstance(sub, (ast.Name, ast.Call)) for sub in ast.walk(b))
    ]
    if not dynamic:
        return None
    if all(_bound_is_bucketed(ctx, b, at) for b in dynamic):
        return None
    return expr


@rule("SPMD208", "unbucketed dynamic batch shape entering a compiled program in a loop")
def check_unbucketed_dynamic_batch(ctx: FileContext) -> Iterable[Finding]:
    """A call to a compiled program (``fuse(...)`` / ``jitted(...)``
    product or ``@fuse``-decorated function) lexically inside a
    ``for``/``while`` body, where an operand is a slice with
    data-dependent bounds (``queue[off : off + n]``), retraces and
    recompiles once per DISTINCT shape — the compiled-program cache keys
    on operand avals, so a request-sized slice turns the cache into an
    unbounded compile treadmill (one entry per batch size ever seen,
    each a full trace+lower+compile pause at serving time).

    The fix is the serving pad discipline: round the row count to a
    power of two and zero-pad (``serve.bucket_rows`` + ``pad_batch``, or
    ``pad_to_shards`` on a split axis) so the shape space is finite and
    every steady-state call replays a warm program.  Bounds routed
    through those bucketing helpers — directly or via one local
    assignment — are exempt, as are constant bounds and traced bodies
    (inside a trace the slice is program structure, not a per-call
    shape)."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        if not _is_compiled_callable(ctx, node.func, node):
            continue
        if ctx.in_traced_context(node):
            continue
        in_loop = False
        cur: Optional[ast.AST] = node
        while cur is not None:
            cur = ctx.parents.get(cur)
            if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
                in_loop = True
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break  # loop containment is per-function, as in SPMD206
        if not in_loop:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            offending = _dynamic_slice_operand(ctx, arg, node)
            if offending is None:
                continue
            yield ctx.finding(
                "SPMD208", node,
                f"dynamic-shape operand {ast.unparse(offending)!r} enters a "
                "compiled program inside a loop — every distinct slice "
                "length is a fresh trace+compile, an unbounded program "
                "cache",
                hint="bucket the row count to a finite shape space before "
                "the call (serve.bucket_rows + pad_batch zero-padding, or "
                "pad_to_shards on a split axis) and slice the result "
                "AFTER the compiled call; mark the call with "
                "`# spmdlint: disable=SPMD208` if the slice lengths are "
                "genuinely bounded",
            )
            break


# --------------------------------------------------------------------- #
# SPMD209: serialized ring body — same-round ppermute consumption        #
# --------------------------------------------------------------------- #
#: loop-tracing entry points whose body argument runs once per ring
#: round; the indices name the traced body function(s), mirroring
#: :data:`~heat_tpu.analysis.core._TRACING_CALLS`
_LOOP_BODY_CALLS = {"fori_loop": (2,), "scan": (0,), "while_loop": (0, 1)}

#: calls that package a ppermute result without touching its values —
#: building a payload tuple is shipping, not consuming
_CONTAINER_CALLS = {"tuple", "list"}


def _overlap_gated(ctx: FileContext, node: ast.AST) -> bool:
    """True when ``node`` sits under an ``if`` whose test — or a ``with``
    whose context manager — names the overlap policy (an identifier
    containing ``overlap``).  That is the exemption: the file already
    branches on the double-buffer schedule, and BOTH arms of the branch
    are deliberate (the serial arm is the policy's bitwise twin, not an
    oversight).  The walk crosses function boundaries on purpose: a loop
    body ``def`` nested under ``if overlapped:`` is gated too."""
    cur: Optional[ast.AST] = node
    while cur is not None:
        cur = ctx.parents.get(cur)
        if isinstance(cur, ast.If):
            for sub in ast.walk(cur.test):
                label = sub.id if isinstance(sub, ast.Name) else (
                    sub.attr if isinstance(sub, ast.Attribute) else ""
                )
                if "overlap" in label.lower():
                    return True
        if isinstance(cur, ast.With):
            for item in cur.items:
                expr = item.context_expr
                target = expr.func if isinstance(expr, ast.Call) else expr
                dotted = ctx.resolve(target) or ""
                if "overlap" in dotted.rsplit(".", 1)[-1].lower():
                    return True
    return False


def _round_body(ctx: FileContext, node: ast.AST, loop_fns: set):
    """The per-round body containing ``node``: the nearest lexical
    ``for``/``while`` inside the enclosing function, or the enclosing
    function itself when it is the body argument of a jax loop
    combinator.  ``None`` when ``node`` does not run once per round."""
    cur: Optional[ast.AST] = node
    while cur is not None:
        cur = ctx.parents.get(cur)
        if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
            return cur
        if isinstance(cur, _FUNC_TYPES):
            return cur if cur in loop_fns else None
    return None


def _same_round_consumption(ctx: FileContext, node: ast.AST, body: ast.AST):
    """How the ppermute result is consumed inside its own round, or
    ``None`` when it only feeds the next round's carry.

    Two shapes count: the call nested under arithmetic or a non-container
    call in the same statement, and an assigned name loaded again later
    in the body.  Loads inside ``return`` statements are excluded — a
    returned carry IS the pipelined pattern (the value crosses into the
    next round, where overlap is possible); same-round reuse is what
    pins the wire onto the critical path."""
    stmt = ctx.enclosing_statement(node)
    cur: Optional[ast.AST] = node
    while cur is not stmt and cur is not None:
        cur = ctx.parents.get(cur)
        if isinstance(cur, (ast.BinOp, ast.UnaryOp, ast.Compare)):
            return "folded into arithmetic in the same statement"
        if isinstance(cur, ast.Call):
            leaf = (ctx.resolve(cur.func) or "").rsplit(".", 1)[-1]
            if leaf not in _CONTAINER_CALLS:
                return f"passed straight into {leaf or 'a call'}()"
    if isinstance(stmt, ast.AugAssign):
        return "augmented-assigned into live state"
    targets: set = set()
    if isinstance(stmt, ast.Assign):
        for t in stmt.targets:
            for sub in ast.walk(t):
                if isinstance(sub, ast.Name):
                    targets.add(sub.id)
    elif isinstance(stmt, ast.AnnAssign) and isinstance(stmt.target, ast.Name):
        targets.add(stmt.target.id)
    if not targets:
        return None
    after = getattr(stmt, "end_lineno", stmt.lineno) or stmt.lineno
    for sub in ast.walk(body):
        if (
            isinstance(sub, ast.Name)
            and isinstance(sub.ctx, ast.Load)
            and sub.id in targets
            and getattr(sub, "lineno", 0) > after
        ):
            ret: Optional[ast.AST] = sub
            while ret is not None and ret is not body:
                if isinstance(ret, ast.Return):
                    break
                ret = ctx.parents.get(ret)
            if isinstance(ret, ast.Return):
                continue  # next-round carry, not same-round consumption
            return f"read back as {sub.id!r} later in the round"
    return None


@rule("SPMD209", "serialized ring body: ppermute result consumed in the same round")
def check_serialized_ring_body(ctx: FileContext) -> Iterable[Finding]:
    """A ``jax.lax.ppermute`` inside a per-round body — a lexical
    ``for``/``while`` or a function passed to
    ``fori_loop``/``scan``/``while_loop`` — whose result is consumed in
    the SAME round (nested under arithmetic or a consuming call, or its
    assigned name is loaded again before the round ends) puts the wire
    hop on the critical path: every round is ``wire + compute`` instead
    of ``max(wire, compute)``, and no scheduler can hide the transfer
    because the data dependency forbids it.  Results that only feed the
    ``return``-ed carry are exempt — that IS the double-buffered shape
    (the in-flight slab crosses into the next round while this round's
    math runs).  Bodies gated on the overlap policy (under an ``if``
    test or ``with`` manager naming ``overlap``) are exempt as a pair:
    the serial arm there is the policy's deliberate bitwise twin."""
    loop_fns: set = set()
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = ctx.resolve(node.func) or ""
        leaf = dotted.rsplit(".", 1)[-1]
        if leaf in _LOOP_BODY_CALLS and (
            dotted == leaf or "jax" in dotted or "lax" in dotted
        ):
            for idx in _LOOP_BODY_CALLS[leaf]:
                if idx < len(node.args):
                    fn = ctx._fn_node_of(node.args[idx], node)
                    if fn is not None:
                        loop_fns.add(fn)

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not ctx.resolves_to(node.func, "ppermute"):
            continue
        body = _round_body(ctx, node, loop_fns)
        if body is None or _overlap_gated(ctx, node):
            continue
        how = _same_round_consumption(ctx, node, body)
        if how is None:
            continue
        yield ctx.finding(
            "SPMD209", node,
            f"ppermute result {how} — the ring round serializes as "
            "wire + compute, every hop on the critical path",
            hint="double-buffer the ring: carry (current, in-flight) "
            "slabs, issue the next round's ppermute first, and fold the "
            "PREVIOUS round's operand (parallel/primitives.py ring_map; "
            "policy in heat_tpu.comm.overlap) — or gate the serial body "
            "under `if overlap_enabled(...)` so it is the policy's "
            "deliberate twin; mark with `# spmdlint: disable=SPMD209` if "
            "the same-round dependency is inherent to the algorithm",
        )


# --------------------------------------------------------------------- #
# SPMD210: request-scoped observability inside traced functions          #
# --------------------------------------------------------------------- #
#: dotted-suffix forms of the request-scoped observability entry points
#: (`heat_tpu.telemetry`, the `heat_tpu.obs` facade, and the internal
#: `from ..telemetry import _core` spelling) — context managers and
#: calls that run at TRACE time inside a traced body
_OBS_CTX_SUFFIXES = (
    "telemetry.trace_ctx", "telemetry._core.trace_ctx", "obs.trace_ctx",
)
_OBS_CALL_SUFFIXES = (
    "telemetry.observe", "telemetry._core.observe", "obs.observe",
)
_OBS_FLIGHT_SUFFIXES = ("flight.note",)


def _obs_match(dotted: str, suffixes) -> bool:
    return any(dotted == s or dotted.endswith("." + s) for s in suffixes)


@rule("SPMD210", "request-scoped observability inside traced functions records trace time, not run time")
def check_traced_observability(ctx: FileContext) -> Iterable[Finding]:
    """The SPMD205 argument, extended to the observability layer: a
    ``telemetry.trace_ctx`` entered, a ``telemetry.observe`` recorded, or
    a ``flight.note`` appended inside a jit/shard_map/fuse-traced body
    runs ONCE, at trace time, against abstract tracers.  The trace
    context is set and torn down before the compiled program ever
    executes (no run-time event can carry the ids); the observation
    lands a single trace-time value (often a tracer's ``str()``) in the
    histogram instead of per-execution samples; the flight note records
    the *tracing* of the program, not its launches.  All three belong at
    the HOST call site — around the jitted/fused call, where the serve
    engine places them."""
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call) and ctx.in_traced_context(node)):
            continue
        dotted = ctx.resolve(node.func)
        if dotted is None:
            continue
        if _obs_match(dotted, _OBS_CTX_SUFFIXES):
            yield ctx.finding(
                "SPMD210", node,
                "telemetry.trace_ctx entered inside a traced function",
                hint="the context is installed and reset during TRACING — "
                "compiled executions carry no request ids; wrap the host "
                "call site instead (the serve engine re-establishes the "
                "context per micro-batch around its fused predict call)",
            )
        elif _obs_match(dotted, _OBS_CALL_SUFFIXES):
            yield ctx.finding(
                "SPMD210", node,
                "telemetry.observe recorded inside a traced function",
                hint="the histogram receives ONE trace-time observation "
                "(possibly of a tracer), not per-execution samples; "
                "observe the measured value at the host call site after "
                "block_until_ready",
            )
        elif _obs_match(dotted, _OBS_FLIGHT_SUFFIXES):
            yield ctx.finding(
                "SPMD210", node,
                "flight-recorder note inside a traced function",
                hint="the note records the one-time tracing, not the "
                "compiled executions; note at the host call site, or rely "
                "on the _emit mirror for enabled-telemetry events",
            )


# --------------------------------------------------------------------- #
# SPMD211: retry loop without a deadline                                 #
# --------------------------------------------------------------------- #
#: identifier fragments whose presence anywhere in the loop marks it as
#: BOUNDED: a deadline/timeout check, an attempt budget, or delegation to
#: the retry engine (``for attempt in retry(policy)`` never matches the
#: rule anyway — it is a ``for``, not a ``while True``)
_RETRY_BOUND_MARKERS = (
    "deadline", "attempt", "retry", "timeout", "tries", "budget", "backoff",
)


def _loop_mentions_bound(node: ast.While) -> bool:
    """True when any identifier in the loop smells like a bound — the
    author is counting attempts or watching a clock, so the loop is a
    (possibly hand-rolled) bounded retry, not an infinite one."""
    for sub in ast.walk(node):
        name = None
        if isinstance(sub, ast.Name):
            name = sub.id
        elif isinstance(sub, ast.Attribute):
            name = sub.attr
        elif isinstance(sub, ast.keyword):
            name = sub.arg
        if name is not None:
            low = name.lower()
            if any(m in low for m in _RETRY_BOUND_MARKERS):
                return True
    return False


def _handler_swallows_and_retries(handler: ast.ExceptHandler) -> bool:
    """True when the handler neither escapes the loop (``break``/
    ``return``) nor propagates (``raise``) — control falls back to the
    ``while True`` header and the failing call runs again, forever."""
    for stmt in handler.body:
        for sub in ast.walk(stmt):
            if isinstance(sub, (ast.Raise, ast.Break, ast.Return)):
                return False
    return True


def _retried_site(ctx: FileContext, try_node: ast.Try) -> Optional[str]:
    """The retry-worthy call inside the ``try`` body, if any: a compiled
    program call (fuse/jit product) or one of SPMD207's guarded io/layout
    sites.  Anything else failing forever is somebody else's lint."""
    for stmt in try_node.body:
        for sub in ast.walk(stmt):
            if not isinstance(sub, ast.Call):
                continue
            if _is_compiled_callable(ctx, sub.func, sub):
                return "a compiled program call"
            dotted = ctx.resolve(sub.func) or ""
            leaf = dotted.rsplit(".", 1)[-1]
            if leaf in _GUARDED_SITE_CALLS:
                return f"guarded site {leaf!r}"
    return None


@rule("SPMD211", "retry loop without a deadline around a compiled/guarded call")
def check_unbounded_retry(ctx: FileContext) -> Iterable[Finding]:
    """A ``while True`` whose body try/excepts a compiled program call or
    a guarded io/layout site, where the handler swallows and loops (no
    ``raise``/``break``/``return``), retries FOREVER: a permanent fault
    (mesh gone, manifest corrupt, sidecar deleted) turns into a silent
    busy-loop that holds the serving thread, never surfaces an incident,
    and defeats the chaos lane's determinism (fire counts diverge with
    host timing).  Bounded retries belong on the retry engine —
    ``for attempt in resilience.retry.retry(policy, site=...)`` gives a
    deadline, jittered backoff, and incident records for free.  Loops
    that visibly count attempts or check a deadline/timeout are exempt,
    as is the retry engine's own implementation."""
    if ctx.relpath.endswith("resilience/retry.py"):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.While):
            continue
        test = node.test
        if not (isinstance(test, ast.Constant) and bool(test.value)):
            continue
        if _loop_mentions_bound(node):
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Try):
                continue
            site = _retried_site(ctx, sub)
            if site is None:
                continue
            for handler in sub.handlers:
                if not _handler_swallows_and_retries(handler):
                    continue
                yield ctx.finding(
                    "SPMD211", handler,
                    f"`while True` retries {site} with no deadline or "
                    "attempt budget — a permanent fault becomes an "
                    "infinite busy-loop",
                    hint="route the call through `for attempt in "
                    "resilience.retry.retry(policy, site=...)` (deadline + "
                    "seeded backoff + incidents), or bound the loop with "
                    "an attempt counter / deadline check; mark with "
                    "`# spmdlint: disable=SPMD211` if the forever-retry "
                    "is deliberate",
                )


# --------------------------------------------------------------------- #
# SPMD212: blocking host read inside a compiled-program loop             #
# --------------------------------------------------------------------- #
#: dotted names whose call opens an on-disk dataset handle — re-opening
#: (and reading) one of these per loop iteration serializes the loop on
#: host storage latency
_HOST_READ_OPENERS = frozenset({
    "h5py.File",
    "netCDF4.Dataset",
    "scipy.io.netcdf_file",
})


def _file_handle_expr(ctx: FileContext, expr, at, depth: int = 0) -> bool:
    """True when ``expr`` evaluates to (a view of) an on-disk dataset
    handle: a direct opener call, a name once-bound to one, a subscript
    chain off one (``f[name][lo:hi]``), or its ``.variables`` mapping."""
    if depth > 8:
        return False
    if isinstance(expr, ast.Attribute) and expr.attr == "variables":
        return _file_handle_expr(ctx, expr.value, at, depth + 1)
    if isinstance(expr, ast.Subscript):
        return _file_handle_expr(ctx, expr.value, at, depth + 1)
    if isinstance(expr, ast.Call):
        return (ctx.resolve(expr.func) or "") in _HOST_READ_OPENERS
    if isinstance(expr, ast.Name):
        rec = ctx.lookup(expr.id, at)
        if rec is not None and rec[0] == "expr":
            return _file_handle_expr(ctx, rec[1], at, depth + 1)
    return False


def _blocking_host_read(ctx: FileContext, call: ast.Call) -> Optional[str]:
    """Why ``call`` is a blocking on-disk read, or None if it isn't."""
    dotted = ctx.resolve(call.func) or ""
    if dotted in _HOST_READ_OPENERS:
        return f"`{dotted}` re-opens the file every iteration"
    leaf = dotted.rsplit(".", 1)[-1]
    if (
        leaf in ("asarray", "array")
        and call.args
        and _file_handle_expr(ctx, call.args[0], call)
    ):
        return (
            f"`{leaf}` of a file-handle slice materializes the slab "
            "synchronously on the host"
        )
    if (
        isinstance(call.func, ast.Attribute)
        and call.func.attr == "read_direct"
        and _file_handle_expr(ctx, call.func.value, call)
    ):
        return "`read_direct` on an open dataset handle blocks on storage"
    return None


@rule("SPMD212", "blocking host read inside a loop that dispatches compiled programs")
def check_blocking_read_in_compiled_loop(ctx: FileContext) -> Iterable[Finding]:
    """A loop body that both reads from an on-disk dataset (h5py/netCDF4
    handle access, ``np.asarray`` over a file-handle slice) and dispatches
    a compiled program serializes the device behind host storage: every
    iteration the accelerator sits idle for the full read+copy latency
    before its next dispatch, the exact ``h·(read+copy+compute)`` serial
    schedule ``comm._costs.stream_model`` prices.  The streaming path
    reads chunk ``t+1`` on a worker thread while chunk ``t`` computes —
    ``read + h·max(read+copy, compute)`` — and its generator keeps the
    read out of the dispatching loop's body by construction.  Reads in
    traced contexts are exempt (they are staging-time constants, not
    per-dispatch io)."""
    for loop in ast.walk(ctx.tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
            continue
        compiled = None
        read = None
        why = None
        for stmt in loop.body:
            for sub in ast.walk(stmt):
                if not isinstance(sub, ast.Call) or ctx.in_traced_context(sub):
                    continue
                if compiled is None and _is_compiled_callable(ctx, sub.func, sub):
                    compiled = sub
                if read is None:
                    why = _blocking_host_read(ctx, sub)
                    if why is not None:
                        read = sub
        if compiled is not None and read is not None:
            yield ctx.finding(
                "SPMD212", read,
                "blocking host read in a loop body that also dispatches a "
                f"compiled program — {why}, so the device idles behind "
                "storage every iteration",
                hint="stream the dataset through "
                "`heat_tpu.io.stream.stream_chunks` (double-buffered "
                "host→device prefetch overlaps the next read with this "
                "chunk's compute), or hoist the read out of the loop; mark "
                "with `# spmdlint: disable=SPMD212` if the serialization "
                "is deliberate",
            )


# --------------------------------------------------------------------- #
# SPMD213: blocking socket/pipe I/O inside a compiled-program loop       #
# --------------------------------------------------------------------- #
#: module-level calls that block the calling thread on a peer process or
#: pipe — one of these per iteration serializes the device behind IPC
_BLOCKING_PIPE_CALLS = frozenset({
    "os.read",
    "subprocess.run",
    "subprocess.call",
    "subprocess.check_call",
    "subprocess.check_output",
})

#: socket methods that block until the peer answers
_SOCKET_BLOCKING_METHODS = frozenset({"recv", "recv_into", "recvfrom", "accept"})

#: constructors whose value is a socket object
_SOCKET_OPENERS = frozenset({
    "socket.socket", "socket.create_connection",
})

#: methods on a ``subprocess.Popen`` value that wait for the child
_POPEN_WAIT_METHODS = frozenset({"wait", "communicate"})


def _value_from_opener(ctx: FileContext, expr, at, openers: frozenset,
                       depth: int = 0) -> bool:
    """True when ``expr`` evaluates to a value produced by one of the
    ``openers``: a direct constructor call or a name once-bound to one."""
    if depth > 8:
        return False
    if isinstance(expr, ast.Call):
        return (ctx.resolve(expr.func) or "") in openers
    if isinstance(expr, ast.Name):
        rec = ctx.lookup(expr.id, at)
        if rec is not None and rec[0] == "expr":
            return _value_from_opener(ctx, rec[1], at, openers, depth + 1)
    return False


def _blocking_pipe_io(ctx: FileContext, call: ast.Call) -> Optional[str]:
    """Why ``call`` blocks on a socket/pipe/child, or None if it doesn't."""
    dotted = ctx.resolve(call.func) or ""
    if dotted in _BLOCKING_PIPE_CALLS:
        return f"`{dotted}` blocks the dispatching thread on a pipe/child"
    if isinstance(call.func, ast.Attribute):
        attr = call.func.attr
        if attr in _SOCKET_BLOCKING_METHODS and _value_from_opener(
            ctx, call.func.value, call, _SOCKET_OPENERS
        ):
            return f"`.{attr}` on a socket blocks until the peer answers"
        if attr in _POPEN_WAIT_METHODS and _value_from_opener(
            ctx, call.func.value, call, frozenset({"subprocess.Popen"})
        ):
            return f"`.{attr}` waits for the child process to exit"
    return None


@rule("SPMD213", "blocking socket/pipe I/O inside a loop that dispatches compiled programs")
def check_blocking_ipc_in_compiled_loop(ctx: FileContext) -> Iterable[Finding]:
    """A loop body that both performs blocking IPC (``socket.recv``,
    ``os.read``, ``subprocess.run``, ``Popen.wait``/``communicate``) and
    dispatches a compiled program serializes the device behind the peer:
    every iteration the accelerator idles for the full round-trip before
    its next dispatch — the process-boundary twin of SPMD212's storage
    stall.  The serving plane's shape is the fix: the dispatching loop
    lives in the replica process and never touches a socket, while the
    parent's RPC threads (``heat_tpu.serve.procfleet``) own the blocking
    recv and feed work through queues.  IPC in traced contexts is exempt
    (staging-time constants, not per-dispatch waits)."""
    for loop in ast.walk(ctx.tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
            continue
        compiled = None
        ipc = None
        why = None
        for stmt in loop.body:
            for sub in ast.walk(stmt):
                if not isinstance(sub, ast.Call) or ctx.in_traced_context(sub):
                    continue
                if compiled is None and _is_compiled_callable(ctx, sub.func, sub):
                    compiled = sub
                if ipc is None:
                    why = _blocking_pipe_io(ctx, sub)
                    if why is not None:
                        ipc = sub
        if compiled is not None and ipc is not None:
            yield ctx.finding(
                "SPMD213", ipc,
                "blocking socket/pipe I/O in a loop body that also "
                f"dispatches a compiled program — {why}, so the device "
                "idles behind IPC every iteration",
                hint="move the exchange off the dispatch path: a worker "
                "thread owning the socket feeds a queue the loop drains "
                "(the `heat_tpu.serve.procfleet` worker/outbox shape), or "
                "batch the IPC outside the loop; mark with "
                "`# spmdlint: disable=SPMD213` if the round-trip is "
                "deliberate",
            )


# --------------------------------------------------------------------- #
# SPMD214: unbounded blocking wait inside a worker loop                  #
# --------------------------------------------------------------------- #
def _opener_call(ctx: FileContext, expr, at, openers: frozenset,
                 depth: int = 0) -> Optional[ast.Call]:
    """The opener call that produced ``expr``'s value (a direct
    constructor call or a name once-bound to one), or None — the
    call-returning sibling of :func:`_value_from_opener`, kept separate
    so SPMD214 can inspect the opener's own arguments."""
    if depth > 8:
        return None
    if isinstance(expr, ast.Call):
        return expr if (ctx.resolve(expr.func) or "") in openers else None
    if isinstance(expr, ast.Name):
        rec = ctx.lookup(expr.id, at)
        if rec is not None and rec[0] == "expr":
            return _opener_call(ctx, rec[1], at, openers, depth + 1)
    return None


def _socket_has_timeout(ctx: FileContext, recv_call: ast.Call) -> bool:
    """True when the socket behind ``recv_call`` is visibly bounded: its
    opener passed a ``timeout`` (keyword, or ``create_connection``'s
    second positional), or the file calls ``settimeout`` with a
    non-None value on the same name."""
    opener = _opener_call(ctx, recv_call.func.value, recv_call,
                          _SOCKET_OPENERS)
    if opener is None:
        return True  # unknown provenance: not ours to flag
    if any(kw.arg == "timeout" for kw in opener.keywords):
        return True
    if (ctx.resolve(opener.func) or "").endswith("create_connection") \
            and len(opener.args) >= 2:
        return True
    if isinstance(recv_call.func.value, ast.Name):
        name = recv_call.func.value.id
        for sub in ast.walk(ctx.tree):
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr == "settimeout"
                and isinstance(sub.func.value, ast.Name)
                and sub.func.value.id == name
                and sub.args
                and not (isinstance(sub.args[0], ast.Constant)
                         and sub.args[0].value is None)
            ):
                return True
    return False


def _unbounded_wait(ctx: FileContext, call: ast.Call) -> Optional[str]:
    """Why ``call`` can block its worker thread forever, or None."""
    if not isinstance(call.func, ast.Attribute):
        return None
    attr = call.func.attr
    if attr in ("wait", "get") and not call.args and not call.keywords:
        # zero-arg wait()/get(): a Condition/Event/Queue/Popen blocking
        # call with no timeout at all (dict.get always has arguments,
        # so mapping reads never match)
        return (
            f"`.{attr}()` has no timeout, so the thread blocks forever "
            "when the notify/put/exit it waits for never comes"
        )
    if attr in _SOCKET_BLOCKING_METHODS and not _socket_has_timeout(ctx, call):
        return (
            f"`.{attr}` on a timeout-less socket blocks forever when the "
            "peer stalls without closing (the half-open gray failure)"
        )
    return None


@rule("SPMD214", "unbounded wait/recv inside a `while True` worker loop")
def check_unbounded_wait_in_worker_loop(ctx: FileContext) -> Iterable[Finding]:
    """A ``while True`` worker loop parked on a zero-timeout blocking
    call — ``cv.wait()``, ``queue.get()``, ``popen.wait()``, or a
    ``recv``/``accept`` on a socket with no timeout anywhere in sight —
    can never observe anything but the event it waits for: a peer that
    stalls without closing (the half-open socket), a producer that died
    mid-hand-off, or a shutdown flag all leave the thread wedged forever,
    unjoinable and invisible to deadlines.  That is exactly the gray
    failure the serving plane's hardening exists to catch, and the fix is
    always the same shape: wait with a timeout inside the loop and
    re-check liveness/deadline on each wakeup (the deadline-aware waits
    in ``serve.procfleet.flush`` / ``serve.wfq.pop``).  Loops that
    visibly track a bound (deadline/timeout/attempt/budget identifiers,
    same exemption as SPMD211) are exempt — the author is already
    watching a clock."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.While):
            continue
        test = node.test
        if not (isinstance(test, ast.Constant) and bool(test.value)):
            continue
        if _loop_mentions_bound(node):
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call) or ctx.in_traced_context(sub):
                continue
            why = _unbounded_wait(ctx, sub)
            if why is None:
                continue
            yield ctx.finding(
                "SPMD214", sub,
                f"unbounded blocking wait in a `while True` worker loop "
                f"— {why}",
                hint="wait with a timeout and re-check liveness/deadline "
                "each wakeup (compute the deadline once, wait the "
                "remainder — the `serve.wfq.pop` shape), or bound the "
                "socket with `settimeout`; mark with "
                "`# spmdlint: disable=SPMD214` if blocking forever is "
                "deliberate",
            )


# --------------------------------------------------------------------- #
# SPMD301/302: Pallas tiling and grids                                   #
# --------------------------------------------------------------------- #
@rule("SPMD301", "Pallas BlockSpec tiles must respect the hardware tile grid")
def check_pallas_tiling(ctx: FileContext) -> Iterable[Finding]:
    """Literal BlockSpec dimensions must sit on the TPU tile grid: the
    minor-most block dim a multiple of 128, the second-minor a multiple
    of the dtype's sublane count (8 for f32 — bf16 needs 16, flagged in
    the hint).  Size-1 dims and symbolic dims (``bq``, ``D`` — values
    produced by `_pick_block`-style helpers) are exempt: Mosaic also
    accepts block dims equal to the array dims."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not ctx.resolves_to(node.func, "BlockSpec"):
            continue
        shape = node.args[0] if node.args else None
        for kw in node.keywords:
            if kw.arg == "block_shape":
                shape = kw.value
        if not isinstance(shape, (ast.Tuple, ast.List)) or len(shape.elts) < 2:
            continue
        minor, second = shape.elts[-1], shape.elts[-2]
        if isinstance(minor, ast.Constant) and isinstance(minor.value, int):
            v = minor.value
            if v > 1 and v % 128:
                yield ctx.finding(
                    "SPMD301", node,
                    f"BlockSpec minor dim {v} is not a multiple of the "
                    "128-lane tile",
                    hint="pick a 128-multiple (or exactly the array dim); "
                    "f32 tiles are 8x128, bf16 16x128",
                )
        if isinstance(second, ast.Constant) and isinstance(second.value, int):
            v = second.value
            if v > 1 and v % 8:
                yield ctx.finding(
                    "SPMD301", node,
                    f"BlockSpec second-minor dim {v} is not a multiple of "
                    "the sublane tile (8 for f32, 16 for bf16)",
                    hint="round the block up to the dtype's sublane "
                    "multiple or use the full array dim",
                )


@rule("SPMD302", "pallas_call grids must be static")
def check_pallas_static_grid(ctx: FileContext) -> Iterable[Finding]:
    """The grid is compile-time program structure: building it from
    traced array values (``jnp.*``/``lax.*`` results) either fails to
    lower or silently re-specializes per call."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not ctx.resolves_to(node.func, "pallas_call"):
            continue
        grid = None
        for kw in node.keywords:
            if kw.arg == "grid":
                grid = kw.value
        if grid is None:
            continue
        for sub in ast.walk(grid):
            if isinstance(sub, ast.Call):
                dotted = ctx.resolve(sub.func) or ""
                if dotted.startswith(("jax.numpy.", "jax.lax.", "jax.random.")):
                    yield ctx.finding(
                        "SPMD302", sub,
                        f"pallas_call grid uses traced value {dotted!r}",
                        hint="grids must be python ints fixed at trace "
                        "time; derive them from static shapes "
                        "(x.shape[...] // block), not from array values",
                    )


# --------------------------------------------------------------------- #
# SPMD401: jitted() cache-key hygiene                                    #
# --------------------------------------------------------------------- #
_OK_KEY_ATTRS = {
    "dtype", "ndim", "shape", "size", "split", "axis_name", "name",
    "itemsize", "value",
}
_OK_KEY_CALLS = {"str", "int", "float", "bool", "tuple", "len", "repr", "frozenset", "hash"}


def _classify_key_element(ctx: FileContext, el: ast.AST, fn_scope) -> Optional[Tuple[str, str]]:
    """Return (message, hint) when ``el`` is a risky cache-key part."""
    if isinstance(el, ast.Constant):
        return None
    if isinstance(el, (ast.Tuple,)):
        for sub in el.elts:
            bad = _classify_key_element(ctx, sub, fn_scope)
            if bad:
                return bad
        return None
    if isinstance(el, (ast.List, ast.Dict, ast.Set)):
        return (
            "unhashable literal in jitted() key",
            "use a tuple (lists/dicts/sets raise TypeError at lookup)",
        )
    if isinstance(el, ast.Lambda):
        return (
            "lambda in jitted() key",
            "a fresh lambda has a fresh identity every call: the cache "
            "grows one dead entry per call and never hits",
        )
    if isinstance(el, ast.Starred):
        return ("starred element in jitted() key", "splice statically instead")
    if isinstance(el, ast.Name):
        # a name that the enclosing function CALLS is a callable value:
        # bound methods / closures in keys are the ring_map cache leak
        if fn_scope is not None:
            for sub in ast.walk(fn_scope):
                if (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Name)
                    and sub.func.id == el.id
                ):
                    return (
                        f"callable {el.id!r} in jitted() key",
                        "bound methods and closures are not identity-stable "
                        "across calls (PR-1 ring_map leak); key on stable "
                        "data instead, or gate with _compile.cache_stable() "
                        "and suppress",
                    )
        return None
    if isinstance(el, ast.Attribute):
        if el.attr in _OK_KEY_ATTRS:
            return None
        return (
            f"attribute {ast.unparse(el)!r} in jitted() key may be a bound "
            "method or per-call object",
            "key on plain data (dtype/shape/axis tuples, str(dtype), "
            "comm) — never on methods or arrays",
        )
    if isinstance(el, ast.Call):
        if isinstance(el.func, ast.Name) and el.func.id in _OK_KEY_CALLS:
            return None
        dotted = ctx.resolve(el.func) or ""
        if dotted.startswith(("jax.numpy.", "numpy.", "jax.")):
            return (
                f"array-valued call {dotted!r} in jitted() key",
                "jax arrays are unhashable and never identity-stable; key "
                "on the static parameters that produced the array",
            )
        return (
            f"unvetted call {ast.unparse(el.func)!r} in jitted() key",
            "only str/int/float/bool/tuple/len conversions are known "
            "hashable+stable; hoist anything else into a named static",
        )
    if isinstance(el, (ast.BinOp, ast.UnaryOp, ast.Compare, ast.IfExp, ast.Subscript)):
        return None  # plain data arithmetic: hashable if its parts are
    if isinstance(el, ast.JoinedStr):
        return None
    return None


@rule("SPMD401", "jitted() cache keys: hashable, identity-stable parts only")
def check_jit_cache_keys(ctx: FileContext) -> Iterable[Finding]:
    """Call sites of the op engine's ``jitted(key, make_fn)`` must build
    ``key`` from parts that are hashable AND identity-stable across calls
    — no bound methods, no lambdas/closures, no arrays.  The key must be
    a tuple literal visible at the call site (directly or via one local
    assignment) so this can be audited at all."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not ctx.resolves_to(node.func, "jitted"):
            continue
        if not node.args:
            continue
        key = node.args[0]
        anchor = node
        if isinstance(key, ast.Name):
            rec = ctx.lookup(key.id, node)
            if rec is not None and rec[0] == "expr":
                key = rec[1]
        if not isinstance(key, ast.Tuple):
            yield ctx.finding(
                "SPMD401", anchor,
                "jitted() key is not a statically-visible tuple literal",
                hint="build the key as a tuple at (or one assignment above) "
                "the call site so its parts can be audited",
            )
            continue
        if not (key.elts and isinstance(key.elts[0], ast.Constant)
                and isinstance(key.elts[0].value, str)):
            yield ctx.finding(
                "SPMD401", anchor,
                "jitted() key does not start with a namespace string",
                hint="lead with a unique op-name string so two ops can "
                "never collide on structurally-equal parameter tuples",
            )
        enclosing = ctx.enclosing_functions(node)
        fn_scope = enclosing[-1] if enclosing else None
        for el in key.elts:
            bad = _classify_key_element(ctx, el, fn_scope)
            if bad:
                yield ctx.finding("SPMD401", anchor, bad[0], hint=bad[1])


# --------------------------------------------------------------------- #
# SPMD001: suppression hygiene                                          #
# --------------------------------------------------------------------- #
@rule("SPMD001", "inline suppression of a reason-required rule must carry a reason")
def check_suppression_reasons(ctx: FileContext) -> Iterable[Finding]:
    """A ``# spmdlint: disable=...`` comment that silences a rule in
    :data:`~heat_tpu.analysis.rules.REASON_REQUIRED` (SPMD204, SPMD207 —
    the checks whose whole purpose is making a risky pattern deliberate)
    must justify itself with a ``-- reason`` tail::

        # spmdlint: disable=SPMD204 -- bench harness, guards off by design

    A bare suppression (or an empty reason after ``--``) of those rules is
    itself a finding, so silencing the check leaves an audit trail either
    way."""
    from .rules import REASON_REQUIRED

    for lineno, ids, reason in ctx.suppressions():
        gated = sorted(set(ids) & REASON_REQUIRED)
        if gated and not reason:
            anchor = ast.Pass(lineno=lineno, col_offset=0)
            yield ctx.finding(
                "SPMD001", anchor,
                f"suppression of {', '.join(gated)} has no reason",
                hint="append '-- <why this is safe here>' to the "
                "spmdlint: disable comment",
            )
