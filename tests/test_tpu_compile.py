"""The Pallas kernels of the main path — and the one XLA program the chip's
compiler is known to abort on — compiled by the chip's own compiler.

The TPU compiler is installed where there is no TPU, and compiles for a chip
that is described (``v5e:2x2``) and not attached.  It refuses what interpret
mode cannot see: a kernel whose blocks do not fit VMEM, i64 index arithmetic,
a slice off the tiling.  These tests ask it for every kernel ``chip_smoke.py``
runs, at the widths it runs them — about two seconds each, no chip time — so a
later change that the chip would refuse fails here first.

The benchmark's own programs (the Lloyd segment, the finalize, k-means++,
the exact-form cdist) are compiled too, at the cells' shapes, for the stable
names their phases carry into the compiled modules (``jax.named_scope``).

Nothing runs and nothing is timed: a compile that passes is not a chip run.

All of them live in this one file and take the topology from a module-scoped
fixture: only one process may hold the TPU library, so it must be loaded by
the one worker that is given this file, after collection, never at import.
The library's ladders ask ``jax.default_backend()`` and would take their CPU
branch here, so each test steers them onto the chip's branch itself.
"""

from __future__ import annotations

import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

import heat_tpu as ht
from heat_tpu.comm import compressed

# the package re-exports functions under their modules' names
flash_mod = importlib.import_module("heat_tpu.parallel.flash_attention")

S, H, D = 4096, 16, 64  # chip_smoke's attention phase
RING_S = 16384  # chip_smoke --chips 4: four local blocks of S


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as env:
        env.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # what is compiled for a described chip is written to the persistent
        # cache but cannot be read back without the chip: keep it off here
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return ht.XlaCommunication(topo.devices)


@pytest.fixture
def on_the_chip(monkeypatch):
    """What the library asks to find out where it runs, answered as the chip
    would: the Mosaic path of every ladder, and the "auto" policies on."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(compressed, "_interpret", lambda: False)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize(
    "dtype,causal",
    [(jnp.bfloat16, False), (jnp.bfloat16, True), (jnp.float32, False), (jnp.float32, True)],
)
def test_flash_attention_compiles(one_chip, on_the_chip, dtype, causal):
    q = jax.ShapeDtypeStruct((S, H, D), dtype, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: ht.parallel.flash_attention(q, k, v, causal=causal), q, q, q
    )
    assert "tpu_custom_call" in text, "the XLA fallback was compiled, not the kernel"


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_partial_compiles_at_the_rings_local_block(
    one_chip, on_the_chip, causal
):
    bh, block = H, RING_S // 4
    q = jax.ShapeDtypeStruct((bh, block, D), jnp.bfloat16, sharding=one_chip)
    m = jax.ShapeDtypeStruct((bh, block), jnp.float32, sharding=one_chip)
    acc = jax.ShapeDtypeStruct((bh, block, D), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v, m, l, acc: flash_mod.flash_attention_partial(
            q, k, v, m, l, acc, q_base=0, k_base=block, causal=causal
        ),
        q, q, q, m, m, acc,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("elems", [1 << 16, 1 << 20, compressed._PALLAS_MAX_ELEMS])
def test_quantize_blocks_compiles(one_chip, on_the_chip, elems):
    x = jax.ShapeDtypeStruct((elems,), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(compressed.quantize_blocks, x)


@pytest.mark.parametrize("elems", [1 << 16, 1 << 20, compressed._PALLAS_MAX_ELEMS])
def test_dequantize_blocks_compiles(one_chip, on_the_chip, elems):
    rows = elems // compressed.BLOCK
    q = jax.ShapeDtypeStruct((rows, compressed.BLOCK), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((rows, 1), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(compressed.dequantize_blocks, q, s)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_compiles_over_four_chips(four_chips, on_the_chip, causal):
    """The whole ring program — zig-zag causal and contiguous — over the
    described 2x2: the kernel inside ``shard_map`` and the K/V rotation."""
    comm = four_chips
    seq_sharded = NamedSharding(comm.mesh, PartitionSpec(comm.axis_name, None, None))
    q = jax.ShapeDtypeStruct((RING_S, H, D), jnp.bfloat16, sharding=seq_sharded)
    text = _compiled_text(
        lambda q, k, v: ht.parallel.ring_attention(
            q, k, v, causal=causal, comm=comm, local_kernel="auto"
        ),
        q, q, q,
    )
    assert "tpu_custom_call" in text, "local_kernel='auto' took the XLA engine"
    assert "collective-permute" in text


def test_svd_chain_compiles_when_lowered_with_x64_off(one_chip, topo):
    """``ht.linalg.svd``'s device chain (QR, the small SVD with vectors, the
    Q·Ur correction) at chip_smoke's size.  Lowered with x64 off, the way
    :func:`heat_tpu.core.linalg.svd.svd` lowers it.  Lowered with x64 ON the
    same program aborts the chip's compiler (SIGABRT in XLA's TransposeFolding,
    reproduced on the chip in PR 21) — which would take this process with it,
    so that side is never asked here."""
    from heat_tpu.core.dndarray import DNDarray

    svd_mod = importlib.import_module("heat_tpu.core.linalg.svd")
    comm = ht.XlaCommunication(topo.devices[:1])
    m, n = 4_194_304, 64

    def chain(x):
        a = DNDarray(x, (m, n), ht.float32, 0, ht.get_device(), comm, True)
        u, s, v = svd_mod._svd_pipeline(a, 0, ht.float32, True)
        return u.larray, s.larray, v.larray

    x = jax.ShapeDtypeStruct((m, n), jnp.float32, sharding=one_chip)
    with jax.enable_x64(False):
        compiled = jax.jit(chain).lower(x).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30


# --------------------------------------------------------------------- #
# svd_300_c1: the one-chip SVD and QR of 6 291 456 x 300                 #
# --------------------------------------------------------------------- #
TALL_M, TALL_N = 6_291_456, 300  # one flattened Cityscapes image a column


def _computations(text: str) -> dict:
    """The compiled module's computations by name, each its block of lines."""
    return {head: block for head, block in re.findall(r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.M | re.S)}


def _reads_of_a(compiled, shape: str) -> set:
    """Passes over the operand, one total for each way through the program's
    conditionals: the fusions, convolutions and custom calls of the entry
    computation, of every loop's body and of the branch taken that take an
    operand of ``shape``.  A body reads its block of rows a step, so its loop
    reads all of A once, however many of its fusions take A: the structured
    products of a Gram pass (``qr._lower_gram``, ``qr._upper_parts``) are
    several fusions that each slice the step's same block of rows."""
    text = compiled.as_text()
    comps = _computations(text)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)

    def totals(name, body=False):
        block = comps[name]
        holders = set(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = " + re.escape(shape), block, re.M))
        own = sum(
            1
            for operands in re.findall(r"^[^\n]* = [^\n]*? (?:fusion|convolution|custom-call)\(([^)]*)\)", block, re.M)
            if holders & {o.strip().lstrip("%") for o in operands.split(",")}
        )
        out = {min(own, 1) if body else own}
        for loop in re.findall(r"\bwhile\([^\n]*body=%?([\w.\-]+)", block):
            out = {t + b for t in out for b in totals(loop, body=True)}
        for line in re.findall(r"^[^\n]* conditional\([^\n]*$", block, re.M):
            names = re.findall(r"(?:true_computation|false_computation)=%?([\w.\-]+)", line)
            listed = re.search(r"branch_computations=\{([^}]*)\}", line)
            names += [n.strip().lstrip("%") for n in listed.group(1).split(",")] if listed else []
            out = {t + b for t in out for name in names for b in totals(name)}
        return out

    return totals(entry)


def _full_width_products(compiled, shape: str, n: int) -> list:
    """The convolutions (XLA's dots) inside the entry computation's loops that
    carry an operand of ``shape`` (the Gram passes over A's blocks of rows,
    not the conditional's branches) with an ``(n, n)`` operand or result: a
    Gram's whole ``qᵀq`` or a whole ``A_b·R⁻¹``, each product's every MXU
    tile."""
    text = compiled.as_text()
    comps = _computations(text)
    entry = comps[re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)]
    todo = [body for kind, body in re.findall(r"^[^\n]* = \(([^\n]*?)\) while\([^\n]*body=%?([\w.\-]+)", entry, re.M)
            if shape in kind]
    seen, found = set(), []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        block = comps[name]
        todo += re.findall(r"calls=%?([\w.\-]+)", block)
        shapes = dict(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]", block, re.M))
        for out, operands in re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\][^ ]* convolution\(([^)]*)\)", block, re.M):
            dims = [out] + [shapes.get(o.strip().lstrip("%")) for o in operands.split(",")]
            if f"{n},{n}" in dims:
                found.append((name, dims))
    return found


@pytest.mark.parametrize(
    "call,site,passes",
    [("svd", "linalg.svd", 3), ("qr", "linalg.qr", 3), ("qr_r_only", "linalg.qr", 2)],
)
def test_the_tall_svd_and_qr_compile_at_the_cells_size_and_read_a_as_their_field_says(
    one_chip, on_the_chip, call, site, passes
):
    """``ht.linalg.svd`` (and ``ht.linalg.qr``) of 6 291 456 x 300 float32 on
    one chip, the program the cell times: A and its output and nothing of
    their size beside them (no Q beside U, no working copy of A), within the
    chip, and as many passes over A as the launch span's ``a_passes`` says:
    two Gram passes, and U's (or Q's).  The other way through, the blocked
    TSQR an operand whose factor is not sound takes, reads more (its block
    QRs, then the output's own buffer, which has A's shape)."""
    from heat_tpu import telemetry
    from heat_tpu.core import _compile

    qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
    was = telemetry.is_enabled()
    telemetry.enable()
    try:
        with pytest.MonkeyPatch.context() as small:
            small.setattr(qr_mod, "MIN_BYTES", 0)
            x = ht.array(jnp.ones((512, 8), jnp.float32) + jnp.eye(512, 8, dtype=jnp.float32), split=0,
                         comm=ht.XlaCommunication(jax.devices()[:1]))
            {"svd": lambda: ht.linalg.svd(x), "qr": lambda: ht.linalg.qr(x),
             "qr_r_only": lambda: ht.linalg.qr(x, calc_q=False)}[call]()
        span = [e for e in telemetry.events() if e.get("site") == f"jitted:{site}"][-1]
    finally:
        if not was:
            telemetry.disable()
    assert span["route"] == "cholqr2" and span["precision"] == "highest"
    # the keys: (site, comm, shape, dtype, route, compute_uv / calc_q, precision)
    entry = next(fn for k, fn in _compile._CACHE.items() if k[0] == site and k[2] == (512, 8) and k[5] == (call != "qr_r_only"))
    with jax.enable_x64(False):
        compiled = entry.lower(_shape((TALL_M, TALL_N), one_chip)).compile()
    _fits_the_chip(compiled, site)
    m = compiled.memory_analysis()
    a_bytes = 4 * TALL_M * TALL_N
    assert a_bytes <= m.argument_size_in_bytes < 1.02 * a_bytes
    # blocks of 65 536 rows (the fallback's: a block's Householder QR and its Q), never (m, n)
    assert m.temp_size_in_bytes < 1 << 29, m
    assert (m.output_size_in_bytes >= a_bytes) == (call != "qr_r_only")
    assert span["a_passes"] == passes and span["fallback"] == "blocked_tsqr"
    sound, fallback = sorted(_reads_of_a(compiled, f"f32[{TALL_M},{TALL_N}]"))
    assert sound == passes
    # the Gram passes run no product whole: both Grams by their upper column blocks, Q1 by R1⁻¹'s nonzero blocks
    assert span["col_blocks"] == 1  # the traced operand's 8 columns: the dense products
    assert not _full_width_products(compiled, f"f32[{TALL_M},{TALL_N}]", TALL_N)


@pytest.mark.parametrize("rinv", [False, True], ids=["first_gram", "second_gram"])
def test_the_gram_passes_skip_the_zero_and_mirrored_tiles_at_the_cells_size(one_chip, monkeypatch, rinv):
    """``qr._gram`` at 6 291 456 x 300 compiled for the v5e, structured (three
    tiles of columns) and dense (one): only the dense loop body holds a whole
    300 x 300 product; the structured body's FLOPs by XLA's cost analysis are
    at most 0.9 of the dense body's (0.88 and 0.82 by the shapes), and its
    cycles by XLA's cost model of the chip at most 0.88 (0.83 and 0.84; the
    model's dense first Gram, 728k cycles a block of rows, is 50.9 ms a job
    on the chip).  Three products a pass, one for each tile, read 0.69 of the
    FLOPs but 0.94 and 1.06 of the cycles: a product whose kernel is one tile
    wide, or 44 columns, runs the MXU at a fraction of its rate."""
    qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
    shapes = [_shape((TALL_M, TALL_N), one_chip)] + ([_shape((TALL_N, TALL_N), one_chip)] if rinv else [])
    a_shape = f"f32[{TALL_M},{TALL_N}]"

    def compiled():
        with jax.enable_x64(False):
            return jax.jit(lambda x, *r: qr_mod._gram(x, r[0] if r else None, "highest")).lower(*shapes).compile()

    def flops(c):
        cost = c.cost_analysis()
        return (cost[0] if isinstance(cost, list) else cost)["flops"]

    def cycles(c):
        return sum(int(n) for n in re.findall(r'"estimated_cycles":"(\d+)"', c.as_text()))

    assert qr_mod._tiles(TALL_N) == 3
    structured = compiled()
    monkeypatch.setattr(qr_mod, "MXU_COLS", TALL_N)  # one tile: the dense products
    dense = compiled()
    assert flops(structured) <= 0.9 * flops(dense), (flops(structured), flops(dense))
    assert cycles(structured) <= 0.88 * cycles(dense), (cycles(structured), cycles(dense))
    assert not _full_width_products(structured, a_shape, TALL_N)
    assert _full_width_products(dense, a_shape, TALL_N)


# --------------------------------------------------------------------- #
# svd_1200_c4: the SVD of 6 291 456 x 1 200 split by rows over four chips #
# --------------------------------------------------------------------- #
ROWS_M, ROWS_N = 6_291_456, 1200  # 1 200 flattened Cityscapes images, 1 572 864 rows a chip


@pytest.fixture(scope="module")
def rows_svd(four_chips):
    """``ht.linalg.svd``'s cached program on the route ``cholqr2_rows``,
    compiled at the cell's size for the described 2x2, and the fields its
    launch span states there.  The program is made by tracing the public
    entry over a described operand (no array can lie on a described device),
    and compiled once for the tests below (four to five minutes on the CPU:
    R's 1 200 x 1 200 SVD and the fallback's block QRs)."""
    from heat_tpu.core import _compile
    from heat_tpu.core.dndarray import DNDarray

    qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
    comm, rows = four_chips, four_chips.sharding(2, 0)

    def call(a):
        u, s, v = ht.linalg.svd(DNDarray(a, tuple(a.shape), ht.float32, 0, ht.get_device(), comm, True))
        return u.larray, s.larray, v.larray

    with pytest.MonkeyPatch.context() as chip:
        chip.setattr(jax, "default_backend", lambda: "tpu")
        assert qr_mod.rows_route((ROWS_M, ROWS_N), jnp.float32, 0, comm)
        chip.setattr(qr_mod, "MIN_BYTES", 0)
        with jax.enable_x64(False):
            jax.eval_shape(call, _shape((4 * 64, 8), rows))
            # the key: (site, comm, shape, dtype, route, compute_uv, precision)
            entry = next(fn for k, fn in _compile._CACHE.items() if k[0] == "linalg.svd" and k[1] == comm and k[4] == "cholqr2_rows")
            compiled = entry.lower(_shape((ROWS_M, ROWS_N), rows)).compile()
    return compiled, qr_mod.route_fields("cholqr2_rows", True, ROWS_N, comm.size)


def test_the_row_sharded_svd_holds_a_and_u_and_fits_the_chip(rows_svd):
    """A chip holds its 1 572 864 rows of A and of U and the program's
    temporaries (the fallback's block QRs of 65 536 x 1 200 and their stack,
    1.1 GB; the sound branch's Gram passes 0.3 GB) and nothing of A's size
    beside them: 16.25 GB of the 16.9 GB a v5e gives its programs
    (``memory_stats()["bytes_limit"]``), which leaves the harness the room it
    needs between the jobs (nothing: the last job's U is released first)."""
    compiled, _ = rows_svd
    m = compiled.memory_analysis()
    shard = 4 * (ROWS_M // 4) * ROWS_N
    assert shard <= m.argument_size_in_bytes < 1.01 * shard
    assert shard <= m.output_size_in_bytes < 1.01 * shard  # U's rows, S and V
    assert m.temp_size_in_bytes < 1.25e9, m
    total = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert total < 16.5e9, m


def test_the_row_sharded_svd_moves_the_grams_alone_and_reads_a_as_its_field_says(rows_svd):
    """The collectives: on the sound branch the two all-reduces of the
    1 200 x 1 200 Grams, ``collective_bytes`` (11.52 MB a chip); on the
    fallback one all-gather of each chip's 1 200 x 1 200 R more.  No
    collective moves a shard of A or U.  Reads of A: the two Gram passes and
    U's on the sound branch, as ``a_passes`` says; the fallback reads A once
    more and U's buffer once."""
    compiled, fields = rows_svd
    text = compiled.as_text()
    n2 = ROWS_N * ROWS_N
    assert fields["collective_bytes"] == 2 * 4 * n2 and fields["shards"] == 4
    assert max(elements for _, elements in _collectives(text)) <= 4 * n2  # the gathered R stack, 4 800 x 1 200
    moved = {"sound": 0, "fallback": 0}
    for line in text.splitlines():
        found = re.search(r"= (\w+)\[([\d,]*)\]\S* " + _COLLECTIVE + r"(?:-start)?\(", line)
        if not found:
            continue
        out = 4 * math.prod(int(d) for d in found.group(2).split(",") if d)
        group = len(re.search(r"replica_groups=\{\{([\d,]*)\}", line).group(1).split(","))
        operand = out // group if found.group(3) == "all-gather" else out
        moved["fallback" if "shard_map/cond/" in line else "sound"] += operand
    assert moved == {"sound": fields["collective_bytes"], "fallback": 4 * n2}
    sound, fallback = sorted(_reads_of_a(compiled, f"f32[{ROWS_M // 4},{ROWS_N}]"))
    assert sound == fields["a_passes"] == 3 and fallback == 4


# --------------------------------------------------------------------- #
# the benchmark cells' programs, and the names their phases carry        #
# --------------------------------------------------------------------- #
CELL_F, CELL_K = 6_291_456, 8  # one flattened Cityscapes image a row, 8 clusters
CELL_ROWS = {"one_chip": 300, "four_chips": 448}  # kmeans_300_c1, kmeans_448_c4


def _op_names(compiled) -> set:
    """Every ``op_name`` of the compiled module's instructions' metadata."""
    return set(re.findall(r'op_name="([^"]+)"', compiled.as_text()))


def _assert_scopes(compiled, module: str, scopes) -> None:
    text = compiled.as_text()
    assert text.startswith(f"HloModule {module}"), text[:80]
    names = _op_names(compiled)
    for scope in scopes:
        assert any(f"/{scope}/" in n for n in names), f"{scope} is in no op_name of {module}"


@pytest.fixture(params=["one_chip", "four_chips"])
def cell(request, topo):
    """``(rows, sharding of X, sharding of everything else, rows_sh for
    k-means++)`` of the two KMeans cells: X on one chip, or row-sharded over
    the described 2x2 with the small operands replicated."""
    if request.param == "one_chip":
        one = SingleDeviceSharding(topo.devices[0])
        return CELL_ROWS["one_chip"], one, one, None
    comm = ht.XlaCommunication(topo.devices)
    return (
        CELL_ROWS["four_chips"], comm.sharding(2, 0), NamedSharding(comm.mesh, PartitionSpec()),
        comm.sharding(1, 0),
    )


def _shape(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _collectives(text):
    """``(opcode, elements of its largest operand)`` of every collective the
    compiled module holds (a tuple of combined operands too), async starts
    under their collective's name."""
    out = []
    for shape, op in re.findall(r"^\s*(?:ROOT )?%?\S+ = (.*?) " + _COLLECTIVE + r"(?:-start)?\(", text, re.M):
        arrays = re.findall(r"\w+\[([\d,]*)\]", shape)
        out.append((op, max(math.prod(int(d) for d in dims.split(",") if d) for dims in arrays)))
    return out


def test_kmeans_fit_segment_compiles_with_its_sweep_scopes(cell):
    from heat_tpu.cluster import kmeans

    rows, xs, rep, _ = cell
    x = _shape((rows, CELL_F), xs)
    carry = (_shape((), rep, jnp.int32), _shape((CELL_K, CELL_F), rep), _shape((), rep))
    cols = kmeans._feature_layout(x, CELL_K, 30)  # the cells' 30 sweeps, one segment
    assert (cols is None) == (xs is rep)  # one chip keeps its rows, four take the columns
    compiled = kmeans._fit_segment.lower(
        x, _shape((), rep), _shape((), rep, jnp.int32), carry, cols=cols
    ).compile()
    _assert_scopes(compiled, "jit__fit_segment", ["kmeans.sweep.assign", "kmeans.sweep.update"])
    if cols is None:
        return
    # across chips X goes to feature columns once, by one all-to-all (never
    # an all-gather of X: 5.6 GB a chip, PR 26); no sweep all-reduces the
    # (k, f) centre sums, only the (n, k) distance partial and the shift;
    # the one all-gather is the centres' at the end
    found = _collectives(compiled.as_text())
    assert [op for op, _ in found].count("all-to-all") == 1, found
    assert all(e < CELL_K * CELL_F for op, e in found if op == "all-reduce"), found
    assert all(e <= CELL_K * CELL_F for op, e in found if op == "all-gather"), found
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes < 16 << 30


def test_kmeans_finalize_compiles_with_its_scope(cell):
    from heat_tpu.cluster.kmeans import KMeans

    rows, xs, rep, _ = cell
    compiled = KMeans._finalize.lower(
        _shape((rows, CELL_F), xs), _shape((CELL_K, CELL_F), rep)
    ).compile()
    _assert_scopes(compiled, "jit__finalize", ["kmeans.finalize"])


def _compiled_kmeanspp(rows, xs, rep, rows_sh):
    from heat_tpu.cluster._kcluster import _kmeanspp

    return _kmeanspp.lower(
        _shape((rows, CELL_F), xs), _shape((), rep, jnp.int32), _shape((CELL_K,), rep),
        rows_sh=rows_sh,
    ).compile()


_COLLECTIVE = r"(all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)"


def test_kmeanspp_compiles_with_its_scopes(cell):
    rows, xs, rep, rows_sh = cell
    compiled = _compiled_kmeanspp(rows, xs, rep, rows_sh)
    _assert_scopes(compiled, "jit__kmeanspp", ["kmeanspp.distance", "kmeanspp.sample"])
    text, m = compiled.as_text(), compiled.memory_analysis()
    # fits beside the data on a 16 GB chip
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 16 << 30
    if rows_sh is None:
        # one chip reads the drawn row where it lies, and talks to nobody
        assert "dynamic-slice(" in text
        assert not re.findall(_COLLECTIVE + r"(-start)?\(", text)
        return
    # across chips the owner answers (``arr[idx]`` here all-gathered the whole
    # of X, 11.3 GB of scratch a chip, once a draw): nothing larger than the
    # (n,) distance vector is gathered, and the scratch is gone
    _assert_scopes(compiled, "jit__kmeanspp", ["kmeanspp.fetch"])
    for dims in re.findall(r"f32\[([\d,]*)\]\S*\s+all-gather", text):
        assert 4 * math.prod(int(d) for d in dims.split(",") if d) < 1 << 20, dims
    assert f"f32[{CELL_F}]" in {s.split("{")[0] for s in re.findall(r"(\S+)\s+all-reduce", text)}
    assert m.temp_size_in_bytes < 1 << 30


def test_kmeanspp_compiles_at_the_sources_1200_rows_on_four_chips(four_chips):
    """The weak-scaling arm's own size at four processes, 300 images a chip
    (``kmeans_448_c4`` holds 448 because the all-gather of X, 30.6 GB at this
    size, was refused): what a ``benchmark`` PR needs to grow the cell."""
    rep = NamedSharding(four_chips.mesh, PartitionSpec())
    m = _compiled_kmeanspp(
        1200, four_chips.sharding(2, 0), rep, four_chips.sharding(1, 0)
    ).memory_analysis()
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 16 << 30
    assert m.temp_size_in_bytes < 1 << 30


def _cdist_entry(quadratic: bool, rows=None):
    """The cached program ``ht.spatial.cdist`` issues at 18 features: for an
    operand on one device, or (``rows``) for one laid out by rows."""
    from heat_tpu.core import _compile

    return next(  # the key: site, expansion or not, form, the result's layout
        fn for k, fn in _compile._CACHE.items() if k[:2] == ("dist.euclidean", quadratic) and k[3] == rows
    )


def _entry_instructions(compiled):
    """``(name, shape, opcode)`` of the entry computation's instructions."""
    entry = compiled.as_text().split("\nENTRY ", 1)[1]
    return re.findall(r"^\s*(?:ROOT )?%?(\S+) = (\S+?)(?:\{\S*)? ([\w-]+)\(", entry, re.M)


def test_cdist_compiles_under_its_sites_name_with_its_scope(one_chip):
    """``cdist_40k_c1``: the ``jitted`` entry is named after its key's site,
    so the device's ``XLA Modules`` line reads ``jit_dist.euclidean`` where
    it read ``jit__lambda_``.  The exact form at 18 features is ONE pass:
    one fusion makes the 6.4 GB result, ``sqrt`` inside it (the reduce over
    the padded feature axis was followed by a second pass for the ``sqrt``)."""
    x = _shape((40_000, 18), one_chip)
    small = ht.array(jnp.zeros((8, 18), jnp.float32))
    for quadratic, scope in ((False, "cdist.exact"), (True, "cdist.quadratic")):
        ht.spatial.cdist(small, quadratic_expansion=quadratic)  # makes the cached entry
        compiled = _cdist_entry(quadratic).lower(x, x).compile()
        _assert_scopes(compiled, "jit_dist.euclidean", [scope])
        if quadratic:
            continue
        whole = [(name, op) for name, shape, op in _entry_instructions(compiled) if shape == "f32[40000,40000]"]
        assert [op for _, op in whole] == ["fusion"], whole
        assert "sqrt" in whole[0][0] and "reduce" not in whole[0][0], whole
        assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_cdist_on_rows_over_four_chips_writes_each_chips_rows_and_moves_no_n_by_n(four_chips):
    """80 000 x 18 by rows over the described 2x2, through the public entry
    (traced over a described operand to make its cached program: no array can
    lie on a described device).  The program lays its result out by rows
    itself; left to GSPMD, either form moves n x n intermediates through an
    all-to-all and needs 114 GB a chip."""
    from heat_tpu.core.dndarray import DNDarray

    comm, n = four_chips, 80_000
    rows = comm.sharding(2, 0)

    def call(a):
        X = DNDarray(a, tuple(a.shape), ht.float32, 0, ht.get_device(), comm, True)
        return ht.spatial.cdist(X).larray

    jax.eval_shape(call, _shape((64, 18), rows))
    x = _shape((n, 18), rows)
    compiled = _cdist_entry(False, rows).lower(x, x).compile()
    assert compiled.output_shardings.is_equivalent_to(rows, 2)
    text = compiled.as_text()
    whole = [(name, op) for name, shape, op in _entry_instructions(compiled) if shape == f"f32[{n // 4},{n}]"]
    assert [op for _, op in whole] == ["fusion"] and "sqrt" in whole[0][0], whole
    # Y's 18 columns are assembled on every chip; nothing of D's size moves
    moved = [
        line[: found.start()]  # "%name = <shape, or a tuple of shapes>"
        for line in text.splitlines()
        if (found := re.search(r" " + _COLLECTIVE + r"(-start)?\(", line))
    ]
    assert moved, "Y has to reach every chip"
    for shapes in moved:
        for dims in re.findall(r"f32\[([\d,]*)\]", shapes):
            assert math.prod(int(d) for d in dims.split(",") if d) <= 18 * n, shapes[:200]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28


# --------------------------------------------------------------------- #
# spectral_40k_c1: the similarity, the Laplacian, the Lanczos segment    #
# --------------------------------------------------------------------- #
SPEC_N, SPEC_F, SPEC_M, SPEC_K = 40_000, 18, 300, 8


def _fits_the_chip(compiled, name: str) -> None:
    """Arguments, outputs (less what they share with the arguments) and
    temporaries together under the chip's 16 GB."""
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
        + m.temp_size_in_bytes
    )
    assert total < 16e9, (name, m)


@pytest.mark.parametrize("quadratic,scope", [(True, "rbf.quadratic"), (False, "rbf.exact")])
def test_rbf_compiles_at_the_cells_size_with_its_scope(one_chip, quadratic, scope):
    """The similarity ``Spectral`` asks for (the expansion, its product at the
    linalg precision) and the exact form, 40 000 x 18 -> 6.4 GB: nothing of
    that size beside the result."""
    from heat_tpu.core import _compile

    x = ht.array(jnp.zeros((8, SPEC_F), jnp.float32))  # not split: the program of one device
    ht.spatial.rbf(x, sigma=1.0, quadratic_expansion=quadratic)  # makes the cached entry
    key = ("dist.rbf", quadratic, "highest" if quadratic else None)
    entry = next(fn for k, fn in _compile._CACHE.items() if k[:3] == key and k[4] is None)
    shape = _shape((SPEC_N, SPEC_F), one_chip)
    compiled = entry.lower(shape, shape, _shape((), one_chip)).compile()
    _assert_scopes(compiled, "jit_dist.rbf", [scope])
    _fits_the_chip(compiled, "dist.rbf")
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    if quadratic:  # the product is float32's, not one bf16 pass
        (precision,) = set(re.findall(r"convolution\(.*operand_precision=\{(\w+),(\w+)\}", compiled.as_text()))
        assert precision == ("highest", "highest")


@pytest.mark.parametrize("definition", ["norm_sym", "simple"])
def test_laplacian_compiles_in_place_with_its_scopes(one_chip, definition):
    """One program turns the 6.4 GB similarity into L in its own buffer."""
    import functools

    from heat_tpu.graph import laplacian

    fn = functools.partial(
        laplacian._laplacian, definition=definition, mode="fully_connected", key="upper", val=1.0, weighted=True
    )
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(_shape((SPEC_N, SPEC_N), one_chip)).compile()
    text = compiled.as_text()
    assert "input_output_alias={ {}: (0, {}" in text.splitlines()[0]
    names = _op_names(compiled)
    for scope in ("laplacian.degree", "laplacian.normalize"):
        assert any(f"/{scope}/" in n for n in names), scope
    _fits_the_chip(compiled, "laplacian." + definition)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 4 * SPEC_N * SPEC_N and m.temp_size_in_bytes < 1 << 30


@pytest.fixture
def on_one_chip(on_the_chip, monkeypatch):
    """``on_the_chip`` for the cell's machine: the process drives one device,
    which is what opens Lanczos' symmetric-half matvec."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)


def _granted_vmem(custom_call: str) -> int:
    """The VMEM the compiler granted a Pallas kernel, off its custom call's
    line (it refuses a kernel that needs more than it states)."""
    (granted,) = re.findall(r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"\}\]', custom_call)
    return int(granted)


def _assert_the_matvec_is_the_kernel(compiled) -> None:
    """The step's product with the operator is the Pallas kernel's custom
    call, granted exactly the VMEM the kernel states (the compiler refuses a
    kernel that needs more), and no MXU product of the program runs at less
    than float32's precision."""
    from heat_tpu.core.linalg import _symv

    matvec = [line for line in compiled.as_text().splitlines() if "/lanczos.matvec/" in line]
    calls = [line for line in matvec if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, [line[:200] for line in matvec]
    assert _granted_vmem(calls[0]) == _symv._VMEM_LIMIT <= 32 << 20
    assert f"f32[{SPEC_N},{SPEC_N}]" in calls[0], "the operator is not the kernel's own operand"
    for line in compiled.as_text().splitlines():
        if re.search(r"= \S+ (convolution|dot)\(", line):
            assert "operand_precision={highest,highest}" in line, line


def test_lanczos_segment_compiles_beside_the_operator_with_float32_products(one_chip, on_one_chip):
    from heat_tpu.core.linalg import solver

    carry = (
        _shape((SPEC_N, SPEC_M), one_chip), _shape((SPEC_M, SPEC_M), one_chip),
        _shape((SPEC_N,), one_chip), _shape((SPEC_N,), one_chip),
    )
    shapes = (
        _shape((SPEC_N, SPEC_N), one_chip), _shape((SPEC_N, SPEC_M), one_chip),
        _shape((), one_chip, jnp.int32), _shape((), one_chip, jnp.int32), carry,
    )
    assert solver._matvec_route(shapes[0]) == "symmetric_half"
    lowered = solver._lanczos_segment.lower(*shapes, precision="highest")
    # as traced: the thin products of the step ask for float32's precision,
    # and the product with the operator is no dot_general at all
    dots = re.findall(r"stablehlo\.dot_general.*", lowered.as_text())
    assert dots and all("HIGHEST" in d for d in dots), dots
    assert not any(f"{SPEC_N}x{SPEC_N}" in d for d in dots), dots
    compiled = lowered.compile()
    _assert_scopes(compiled, "jit__lanczos_segment", ["lanczos.matvec", "lanczos.reorth", "lanczos.restart"])
    _fits_the_chip(compiled, "lanczos.segment")
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    _assert_the_matvec_is_the_kernel(compiled)


def test_lanczos_segment_on_a_row_sharded_operator_keeps_the_dense_product(four_chips, on_the_chip):
    """Four chips, L split by rows: no kernel (GSPMD would gather L around
    it), the parent's program, a quarter of L a chip."""
    from heat_tpu.core.linalg import solver

    comm = four_chips
    rows = NamedSharding(comm.mesh, PartitionSpec(comm.axis_name, None))
    rep = NamedSharding(comm.mesh, PartitionSpec())
    carry = (_shape((SPEC_N, SPEC_M), rep), _shape((SPEC_M, SPEC_M), rep), _shape((SPEC_N,), rep), _shape((SPEC_N,), rep))
    operator = _shape((SPEC_N, SPEC_N), rows)
    assert solver._matvec_route(operator) == "dense"
    compiled = solver._lanczos_segment.lower(
        operator, _shape((SPEC_N, SPEC_M), rep), _shape((), rep, jnp.int32), _shape((), rep, jnp.int32), carry,
        precision="highest",
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes < 4 * SPEC_N * SPEC_N // 4 + (1 << 28)


def test_lanczos_start_and_the_embedding_compile(one_chip, on_one_chip):
    import functools

    from heat_tpu.cluster import spectral
    from heat_tpu.core.linalg import solver

    start = solver._lanczos_start.lower(
        _shape((SPEC_N, SPEC_N), one_chip), _shape((SPEC_N,), one_chip), m=SPEC_M, precision="highest"
    ).compile()
    _assert_scopes(start, "jit__lanczos_start", ["lanczos.matvec"])
    _fits_the_chip(start, "lanczos.start")
    assert start.memory_analysis().temp_size_in_bytes < 1 << 30
    _assert_the_matvec_is_the_kernel(start)
    embed = jax.jit(functools.partial(spectral._embed, precision="highest")).lower(
        _shape((SPEC_N, SPEC_M), one_chip), _shape((SPEC_M, SPEC_K), one_chip)
    ).compile()
    assert any("/spectral.embed/" in n for n in _op_names(embed))


# --------------------------------------------------------------------- #
# moments_300_c1: the two programs of ht.mean and ht.std                 #
# --------------------------------------------------------------------- #
MOM_ROWS, MOM_F = 300, 6_291_456  # CELL_F: the cityscapes row


def _reads_of_the_operand(compiled) -> int:
    """How many instructions of the compiled entry computation take its
    parameter: each is one pass over the operand, whatever is fused into it."""
    entry = compiled.as_text().split("\nENTRY ", 1)[1]
    (param,) = re.findall(r"^\s*%?(\S+) = \S+ parameter\(0\)", entry, re.M)
    return len(re.findall(r"^\s*(?:ROOT )?\S+ = \S+ [\w-]+\((?:[^\n]*, )?%?" + re.escape(param) + r"[,)]", entry, re.M))


def _moments_entry(call: str, comm=None):
    """The cached entry behind ``ht.<call>(x, axis=0)`` and the launch span it
    records, made by one small call where ``_colvar.conforms`` is asked no
    least size and answered by the interpreter, so that a process that drives
    one TPU (``on_one_chip``) makes the one-read form's entry here too."""
    from heat_tpu import telemetry
    from heat_tpu.core import _colvar, _compile

    was = telemetry.is_enabled()
    telemetry.enable()
    try:
        with pytest.MonkeyPatch.context() as small:
            small.setattr(_colvar, "_interpret", lambda: True)
            small.setattr(_colvar, "MIN_BYTES", 0)
            x = ht.array(jnp.zeros((8, 512), jnp.float32), split=0, comm=comm)
            getattr(ht, call)(x, axis=0)
        site = "stat.mean" if call == "mean" else "stat.moment2"
        span = [e for e in telemetry.events() if e.get("site") == f"jitted:{site}"][-1]
    finally:
        if not was:
            telemetry.disable()
    # the keys: (site, axis, cast, keepdims) and (site, name, axis, ddof, form, keepdims)
    key = ("stat.mean", 0, None, False) if call == "mean" else ("stat.moment2", f"stat.{call}", 0, 0, span["form"], False)
    return next(fn for k, fn in _compile._CACHE.items() if k[: len(key)] == key), span


@pytest.mark.parametrize(
    "call,reads,scopes",
    [
        ("mean", 1, ["stat.mean"]),
        ("std", 1, ["stat.var.onepass"]),
        ("var", 1, ["stat.var.onepass"]),
    ],
)
def test_moments_compile_at_the_cells_size_and_read_the_operand_as_their_field_says(one_chip, on_one_chip, call, reads, scopes):
    """``ht.mean`` / ``ht.std`` / ``ht.var`` along axis 0 of 300 x 6 291 456
    float32 on one chip: the 7.55 GB operand (304 rows with the tile's
    padding) and nothing of its size beside it (no centred copy), each read
    under its scope, and as many reads as the launch span's ``reads`` field
    says: ONE each, the variance's by the kernel of ``core/_colvar.py``."""
    from heat_tpu.core import _colvar

    entry, span = _moments_entry(call, ht.XlaCommunication(jax.devices()[:1]))
    site = span["site"].split(":", 1)[1]
    compiled = entry.lower(_shape((MOM_ROWS, MOM_F), one_chip)).compile()
    _assert_scopes(compiled, f"jit_{site}", scopes)
    m = compiled.memory_analysis()
    assert 4 * MOM_ROWS * MOM_F <= m.argument_size_in_bytes < 7.7e9
    assert m.temp_size_in_bytes < 1 << 26 and m.output_size_in_bytes == 4 * MOM_F
    _fits_the_chip(compiled, site)
    assert _reads_of_the_operand(compiled) == span["reads"] == reads
    # the compiler's own count of the operand's bytes says the same (a custom
    # call's operand counts once, whatever the kernel does with it inside)
    assert round(compiled.cost_analysis()["bytes accessed0{}"] / m.argument_size_in_bytes) == reads
    if call == "mean":
        assert "form" not in span
        return
    assert span["form"] == "one_pass"
    # the one read is the kernel's, of the operand itself (not a copy laid out
    # anew), granted exactly the VMEM it states
    (kernel,) = [line for line in compiled.as_text().splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert "/stat.var.onepass/" in kernel and f"f32[{MOM_ROWS},{MOM_F}]" in kernel
    assert _granted_vmem(kernel) == _colvar._VMEM_LIMIT <= 32 << 20


def test_std_on_the_cpu_mesh_and_on_rows_over_four_chips_keeps_two_passes(four_chips, on_the_chip):
    """Every process that is not the cell's compiles the parent's program:
    two reads under their scopes, no kernel; over four chips (112 of the
    four-chip cell's 448 rows a chip) each chip reads its own rows twice and
    the partial sums meet in all-reduces."""
    entry, span = _moments_entry("std")
    assert (span["form"], span["reads"]) == ("two_pass", 2)
    comm = four_chips
    rows = NamedSharding(comm.mesh, PartitionSpec(comm.axis_name, None))
    compiled = entry.lower(_shape((448, MOM_F), rows)).compile()
    _assert_scopes(compiled, "jit_stat.moment2", ["stat.var.mean", "stat.var.centred"])
    text = compiled.as_text()
    assert "tpu_custom_call" not in text and "all-reduce" in text
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes < 4 * 448 * MOM_F // 4 + (1 << 26) and m.temp_size_in_bytes < 1 << 28
    assert _reads_of_the_operand(compiled) == 2


# --------------------------------------------------------------------- #
# kmedians_300_c1: the whole fit, one program                            #
# --------------------------------------------------------------------- #
def _passes_over(compiled, shape: str):
    """``(in the loop's body, outside it)``: how many fusions and custom calls
    of the compiled module's own computations (the fused ones apart) take an
    operand of ``shape``: each is one pass over it, whatever is fused in."""
    text = compiled.as_text()
    (body,) = set(re.findall(r"\bwhile\([^\n]*body=%?([\w.\-]+)", text))
    counts = {}
    for head, block in re.findall(r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.M | re.S):
        if "fused_computation" in head or head.startswith("region"):
            continue
        holders = set(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = " + re.escape(shape), block, re.M))
        counts[head] = sum(
            1
            for operands in re.findall(r"^[^\n]* = [^\n]*? (?:fusion|custom-call)\(([^)]*)\)", block, re.M)
            if holders & {o.strip().lstrip("%") for o in operands.split(",")}
        )
    return counts.pop(body), sum(counts.values())


def test_kmedians_fit_compiles_at_the_cells_size_and_reads_x_as_its_field_says(one_chip, on_one_chip):
    """``KMedians.fit`` at 300 x 6 291 456 float32 on one chip, the program the
    cell times: X, two sets of centres and nothing of X's size beside them (no
    sorted copy, no (n, f) temporary), the three scopes, the medians by the
    kernel of ``core/_colmedian.py`` on X itself, and as many passes over X as
    the launch span's ``x_passes`` says: two a sweep, one after the loop."""
    from heat_tpu import telemetry
    from heat_tpu.cluster import kmedians
    from heat_tpu.core import _colmedian

    x = _shape((CELL_ROWS["one_chip"], CELL_F), one_chip)
    route = kmedians._medians_route(x, CELL_K)
    assert route == "column_select"
    compiled = kmedians.KMedians._fit_loop.lower(
        x, _shape((CELL_K, CELL_F), one_chip), _shape((), one_chip), _shape((), one_chip, jnp.int32), route=route
    ).compile()
    _assert_scopes(compiled, "jit__fit_loop", ["kmedians.sweep.assign", "kmedians.sweep.medians", "kmedians.finalize"])
    m = compiled.memory_analysis()
    assert 4 * (300 + CELL_K) * CELL_F <= m.argument_size_in_bytes < 7.9e9
    assert m.temp_size_in_bytes < 1e9, m  # a second set of centres and the kernel's result, not a copy of X
    _fits_the_chip(compiled, "kmedians.fit")
    (kernel,) = [line for line in compiled.as_text().splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert "/kmedians.sweep.medians/" in kernel and f"f32[300,{CELL_F}]" in kernel
    assert _granted_vmem(kernel) == _colmedian._VMEM_LIMIT <= 48 << 20
    assert " sort(" not in compiled.as_text()  # nothing is sorted: the rows' places in cluster order are prefix counts

    # the field, from a small fit through the interpreted route
    was = telemetry.is_enabled()
    telemetry.enable()
    try:
        with pytest.MonkeyPatch.context() as small:
            small.setattr(_colmedian, "_interpret", lambda: True)
            small.setattr(_colmedian, "MIN_BYTES", 0)
            comm = ht.XlaCommunication(jax.devices()[:1])
            data = ht.array(jnp.arange(16 * 1024, dtype=jnp.float32).reshape(16, 1024) % 37, split=0, comm=comm)
            ht.cluster.KMedians(n_clusters=2, max_iter=30, tol=-1.0, random_state=1).fit(data)
        span = [e for e in telemetry.events() if e.get("site") == "jit:kmedians.fit"][-1]
    finally:
        if not was:
            telemetry.disable()
        jax.clear_caches()
    in_body, outside = _passes_over(compiled, f"f32[300,{CELL_F}]")
    assert (in_body, outside) == (2, 1)
    assert span["medians"] == route and span["x_passes"] == in_body * span["sweeps"] + outside == 61
    assert span["network_max"] == _colmedian._NETWORK_MAX


def test_kmedians_on_rows_over_four_chips_keeps_the_bisection(four_chips, on_the_chip):
    """Every process that is not the cell's compiles the rank bisection, with
    the Manhattan assignment: no kernel, the sorted copy, the three scopes."""
    from heat_tpu.cluster import kmedians

    comm = four_chips
    rows = NamedSharding(comm.mesh, PartitionSpec(comm.axis_name, None))
    rep = NamedSharding(comm.mesh, PartitionSpec())
    x = _shape((448, 1 << 16), rows)
    route = kmedians._medians_route(x, CELL_K)
    assert route == "rank_bisection"
    compiled = kmedians.KMedians._fit_loop.lower(
        x, _shape((CELL_K, 1 << 16), rep), _shape((), rep), _shape((), rep, jnp.int32), route=route
    ).compile()
    _assert_scopes(compiled, "jit__fit_loop", ["kmedians.sweep.assign", "kmedians.sweep.medians", "kmedians.finalize"])
    assert "tpu_custom_call" not in compiled.as_text() and "sort(" in compiled.as_text()
