"""The Pallas kernels of the main path — and the one XLA program the chip's
compiler is known to abort on — compiled by the chip's own compiler.

The TPU compiler is installed where there is no TPU, and compiles for a chip
that is described (``v5e:2x2``) and not attached.  It refuses what interpret
mode cannot see: a kernel whose blocks do not fit VMEM, i64 index arithmetic,
a slice off the tiling.  These tests ask it for every kernel ``chip_smoke.py``
runs, at the widths it runs them — about two seconds each, no chip time — so a
later change that the chip would refuse fails here first.

Nothing runs and nothing is timed: a compile that passes is not a chip run.

All of them live in this one file and take the topology from a module-scoped
fixture: only one process may hold the TPU library, so it must be loaded by
the one worker that is given this file, after collection, never at import.
The library's ladders ask ``jax.default_backend()`` and would take their CPU
branch here, so each test steers them onto the chip's branch itself.
"""

from __future__ import annotations

import importlib

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
