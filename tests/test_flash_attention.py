"""flash_attention — the fused Pallas kernel, run through the Pallas
interpreter on the CPU mesh (the real-TPU lowering is compiled by
tests/test_tpu_compile.py and run by chip_smoke.py), plus the fallback
contract."""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

import heat_tpu as ht
from heat_tpu.parallel import flash_attention

RNG = np.random.default_rng(11)


def _reference(q, k, v, causal, q_base=0):
    """Dense f64 attention, optionally with offset query positions."""
    qt, kt, vt = (np.moveaxis(a, -2, -3).astype(np.float64) for a in (q, k, v))
    S, Sk = qt.shape[-2], kt.shape[-2]
    scores = qt @ np.swapaxes(kt, -1, -2) / np.sqrt(q.shape[-1])
    if causal:
        q_pos = q_base + np.arange(S)[:, None]
        scores = np.where(q_pos >= np.arange(Sk)[None, :], scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.moveaxis(p @ vt, -3, -2)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("batched", [False, True])
def test_flash_matches_dense(causal, batched):
    shape = (2, 256, 2, 32) if batched else (256, 2, 32)
    q, k, v = (RNG.normal(size=shape).astype(np.float32) for _ in range(3))
    out = flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        causal=causal, interpret=True, block_q=128, block_k=128,
    )
    np.testing.assert_allclose(
        np.asarray(out), _reference(q, k, v, causal), atol=2e-5
    )


def test_flash_bf16_close():
    q, k, v = (RNG.normal(size=(256, 2, 32)).astype(np.float32) for _ in range(3))
    out = flash_attention(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16),
        causal=True, interpret=True, block_q=128, block_k=128,
    )
    assert out.dtype == jnp.bfloat16
    # bf16 matmuls with f32 softmax/accumulation: ~1e-2 against dense f64
    np.testing.assert_allclose(
        np.asarray(out, np.float32), _reference(q, k, v, True), atol=5e-2
    )


def test_flash_q_base_local_block():
    # sequence-sharded usage: queries [256:512) against the full key range
    S, H, D = 512, 2, 32
    q, k, v = (RNG.normal(size=(S, H, D)).astype(np.float32) for _ in range(3))
    out = flash_attention(
        jnp.asarray(q[256:]), jnp.asarray(k), jnp.asarray(v),
        causal=True, interpret=True, q_base=256, block_q=128, block_k=128,
    )
    np.testing.assert_allclose(
        np.asarray(out), _reference(q, k, v, True)[256:], atol=2e-5
    )


def test_fallback_honors_q_base_and_longer_kv():
    # the jnp fallback (not just the Pallas path) must apply the causal
    # mask at the offset query positions, with K/V longer than Q —
    # non-128-multiple shapes force the fallback
    S, H, D = 200, 2, 16
    q, k, v = (RNG.normal(size=(S, H, D)).astype(np.float32) for _ in range(3))
    out = flash_attention(
        jnp.asarray(q[120:]), jnp.asarray(k), jnp.asarray(v),
        causal=True, q_base=120,
    )
    np.testing.assert_allclose(
        np.asarray(out), _reference(q, k, v, True)[120:], atol=2e-5
    )


def test_flash_fallback_shapes_and_dtypes():
    # non-multiple-of-128 sequence and f64 both take the jnp path —
    # results must still be exact.  D=48 deliberately: 1/sqrt(48) is NOT
    # f32-representable, so the 1e-9 f64 assertion would catch a scale
    # rounded through f32
    q, k, v = (RNG.normal(size=(100, 2, 48)).astype(np.float32) for _ in range(3))
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True)
    np.testing.assert_allclose(np.asarray(out), _reference(q, k, v, True), atol=2e-5)
    qd = jnp.asarray(q, jnp.float64)
    out64 = flash_attention(qd, jnp.asarray(k, jnp.float64), jnp.asarray(v, jnp.float64))
    assert out64.dtype == jnp.float64
    np.testing.assert_allclose(np.asarray(out64), _reference(q, k, v, False), atol=1e-9)


@pytest.mark.parametrize("causal", [False, True])
def test_partial_chain_matches_full(causal):
    # chaining flash_attention_partial over K/V segments must reproduce
    # the full fused softmax exactly (same algebra, same order)
    from heat_tpu.parallel import flash_attention_partial

    BH, S, D = 4, 256, 32
    q, k, v = (
        jnp.asarray(RNG.normal(size=(BH, S, D)).astype(np.float32))
        for _ in range(3)
    )
    qs = jnp.moveaxis(q, 0, 1)[None]
    ks = jnp.moveaxis(k, 0, 1)[None]
    vs = jnp.moveaxis(v, 0, 1)[None]
    ref = jnp.moveaxis(
        flash_attention(qs, ks, vs, causal=causal, interpret=True,
                        block_q=128, block_k=128)[0], 0, 1,
    )
    m = jnp.full((BH, S), -jnp.inf, jnp.float32)
    l = jnp.zeros((BH, S), jnp.float32)
    acc = jnp.zeros((BH, S, D), jnp.float32)
    seg = S // 2
    for r in range(2):
        m, l, acc = flash_attention_partial(
            q, k[:, r * seg:(r + 1) * seg], v[:, r * seg:(r + 1) * seg],
            m, l, acc, q_base=0, k_base=r * seg,
            causal=causal, interpret=True, block_q=128, block_k=128,
        )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_local_kernel_matches_xla(causal):
    # the REAL ring program with the Pallas partial kernel as its local
    # engine (interpreted on the CPU mesh) must agree with the XLA
    # blockwise path — this is the long-context flagship configuration
    comm = ht.get_comm()
    if comm.size == 1:
        pytest.skip("needs a mesh")
    S, H, D = 128 * comm.size, 2, 16
    q, k, v = (RNG.normal(size=(S, H, D)).astype(np.float32) for _ in range(3))
    qs, ks, vs = (comm.apply_sharding(jnp.asarray(x), 0) for x in (q, k, v))
    a_flash = ht.parallel.ring_attention(
        qs, ks, vs, causal=causal, comm=comm, local_kernel="flash"
    )
    a_xla = ht.parallel.ring_attention(
        qs, ks, vs, causal=causal, comm=comm, local_kernel="xla"
    )
    np.testing.assert_allclose(
        np.asarray(a_flash), np.asarray(a_xla), atol=2e-6
    )
    np.testing.assert_allclose(
        np.asarray(a_flash), _reference(q, k, v, causal), atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_flash_local_kernel_matches_xla(causal):
    # the shard_map + lax.all_to_all + Pallas formulation must agree with
    # the GSPMD two-constraint + XLA attention formulation
    comm = ht.get_comm()
    if comm.size == 1:
        pytest.skip("needs a mesh")
    S, H, D = 128 * comm.size, 2 * comm.size, 16
    q, k, v = (RNG.normal(size=(S, H, D)).astype(np.float32) for _ in range(3))
    qs, ks, vs = (comm.apply_sharding(jnp.asarray(x), 0) for x in (q, k, v))
    a_flash = ht.parallel.ulysses_attention(
        qs, ks, vs, causal=causal, comm=comm, local_kernel="flash"
    )
    a_xla = ht.parallel.ulysses_attention(
        qs, ks, vs, causal=causal, comm=comm, local_kernel="xla"
    )
    np.testing.assert_allclose(
        np.asarray(a_flash), np.asarray(a_xla), atol=2e-6
    )
    np.testing.assert_allclose(
        np.asarray(a_flash), _reference(q, k, v, causal), atol=2e-5
    )


def test_ulysses_flash_rejects_nonconforming():
    comm = ht.get_comm()
    if comm.size == 1:
        pytest.skip("needs a mesh")
    # 25*size is mesh-divisible but never a 128-multiple for any mesh
    # smaller than 128 devices (25 is odd, 128 = 2^7)
    S, H = 25 * comm.size, 2 * comm.size
    q = jnp.asarray(RNG.normal(size=(S, H, 8)).astype(np.float32))
    qs = comm.apply_sharding(q, 0)
    with pytest.raises(ValueError, match="conforming"):
        ht.parallel.ulysses_attention(qs, qs, qs, comm=comm, local_kernel="flash")
    out = ht.parallel.ulysses_attention(qs, qs, qs, comm=comm, local_kernel="auto")
    assert np.isfinite(np.asarray(out)).all()


def test_ring_flash_rejects_nonconforming():
    comm = ht.get_comm()
    if comm.size == 1:
        pytest.skip("needs a mesh")
    S = 25 * comm.size  # L=25: never a 128 multiple, any mesh size
    q = jnp.asarray(RNG.normal(size=(S, 2, 8)).astype(np.float32))
    qs = comm.apply_sharding(q, 0)
    with pytest.raises(ValueError, match="conforming"):
        ht.parallel.ring_attention(qs, qs, qs, comm=comm, local_kernel="flash")
    # and 'auto' silently uses the XLA path for the same shapes
    out = ht.parallel.ring_attention(qs, qs, qs, comm=comm, local_kernel="auto")
    assert np.isfinite(np.asarray(out)).all()


def test_ring_single_block_path_uses_flash_semantics():
    # on the CPU mesh flash falls back to the jnp path; the ring
    # single-block branch must stay exact through the indirection
    S, H, D = 12, 2, 8  # not divisible by the 8-device mesh → fallback
    q, k, v = (RNG.normal(size=(S, H, D)).astype(np.float32) for _ in range(3))
    out = ht.parallel.ring_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True)
    np.testing.assert_allclose(np.asarray(out), _reference(q, k, v, True), atol=2e-5)
