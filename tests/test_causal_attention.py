"""Causal-attention correctness: the triangular-schedule flash kernel and
the load-balanced zig-zag causal ring.

Three layers of assertion:
- the kernel's trip-count rule (`_causal_chunk_bounds`) is exactly
  triangular — ~(n^2+n)/2 visited tiles, not n^2 (the pre-triangular
  kernel visited every tile and masked half of them);
- interpret-mode parity of the triangular kernel against the dense
  reference across block configurations;
- the zig-zag ring (both local engines) against the single-device causal
  reference across mesh sizes, INCLUDING a comparison against a serial
  replay of the identical fold schedule, held to the rounding of the
  operations both sides share (a stated tolerance: see
  ``test_zigzag_ring_bitwise_vs_schedule_replay``).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import heat_tpu as ht
from heat_tpu.core.communication import XlaCommunication
from heat_tpu.parallel import flash_attention
from heat_tpu.parallel.flash_attention import _causal_chunk_bounds, conforms
from heat_tpu.parallel.ring_attention import _blockwise_update
from suite import assert_within_bound

RNG = np.random.default_rng(23)


def _reference(q, k, v, causal=True):
    """Dense f64 attention."""
    qt, kt, vt = (np.moveaxis(a, -2, -3).astype(np.float64) for a in (q, k, v))
    S, Sk = qt.shape[-2], kt.shape[-2]
    scores = qt @ np.swapaxes(kt, -1, -2) / np.sqrt(q.shape[-1])
    if causal:
        scores = np.where(
            np.arange(S)[:, None] >= np.arange(Sk)[None, :], scores, -np.inf
        )
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.moveaxis(p @ vt, -3, -2)


# --------------------------------------------------------------------- #
# triangular trip counts                                                #
# --------------------------------------------------------------------- #

def _bounds(q_lo, k_lo, bq, bk, nk):
    full, total = _causal_chunk_bounds(q_lo, k_lo, bq, bk, nk)
    return int(full), int(total)


def test_triangular_tile_count():
    # bq == bk == b, q_base 0: q block qi visits exactly qi+1 tiles, so the
    # whole grid launches (n^2+n)/2 tiles instead of n^2.  This IS the
    # kernel's schedule: _stream_kv reads its loop bounds from the same
    # function.
    for n, b in [(4, 128), (8, 128), (8, 512), (32, 256)]:
        visited = 0
        for qi in range(n):
            full, total = _bounds(qi * b, 0, b, b, n)
            assert full == qi  # blocks wholly below the diagonal
            assert total == qi + 1  # plus the diagonal block itself
            visited += total
        assert visited == (n * n + n) // 2


def test_chunk_bounds_edge_cases():
    # q entirely before the k span: nothing visited (the ring's
    # fully-masked rounds cost zero folds)
    assert _bounds(0, 1024, 128, 128, 8) == (0, 0)
    assert _bounds(512, 1024, 512, 128, 8) == (0, 0)
    # q entirely after the k span: every chunk visited, none masked
    assert _bounds(1024, 0, 128, 128, 8) == (8, 8)
    # diagonal straddle with bk > bq: the diagonal chunk is masked, the
    # ones before it are full
    full, total = _bounds(256, 0, 128, 256, 4)
    assert (full, total) == (1, 2)
    # q block exactly aligned to a chunk boundary: previous chunk is
    # wholly unmasked (its last k position equals q_lo)
    full, total = _bounds(128, 0, 128, 128, 8)
    assert full == 1 and total == 2
    # clamping: bounds never exceed nk
    assert _bounds(10_000, 0, 128, 128, 4) == (4, 4)


def test_triangular_matches_dense_multiblock():
    # several q/k blocks so the dynamic per-program trip counts actually
    # differ across programs (q block 0 visits 1 chunk, block 3 visits 4)
    S, H, D = 512, 2, 32
    q, k, v = (RNG.normal(size=(S, H, D)).astype(np.float32) for _ in range(3))
    out = flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        causal=True, interpret=True, block_q=128, block_k=128,
    )
    np.testing.assert_allclose(np.asarray(out), _reference(q, k, v), atol=2e-5)


def test_triangular_q_base_offsets():
    # sequence-sharded local blocks at several q_base offsets, K/V longer
    # than Q — the per-program bounds must use GLOBAL positions
    S, H, D = 512, 2, 32
    q, k, v = (RNG.normal(size=(S, H, D)).astype(np.float32) for _ in range(3))
    ref = _reference(q, k, v)
    for lo in (0, 128, 256, 384):
        out = flash_attention(
            jnp.asarray(q[lo:lo + 128]), jnp.asarray(k), jnp.asarray(v),
            causal=True, interpret=True, q_base=lo, block_q=128, block_k=128,
        )
        np.testing.assert_allclose(
            np.asarray(out), ref[lo:lo + 128], atol=2e-5
        )


def test_conforms_rejects_non_floating():
    # the promote_types check alone admits int/bool (they promote to f32
    # weakly); the floating gate must reject them
    assert conforms(256, 32, jnp.float32)
    assert conforms(256, 32, jnp.bfloat16)
    assert not conforms(256, 32, jnp.int32)
    assert not conforms(256, 32, jnp.int8)
    assert not conforms(256, 32, jnp.bool_)
    assert not conforms(256, 32, jnp.float64)


def test_flash_int32_regression():
    # int32 q/k/v: never reaches the Pallas kernel (jnp fallback), and the
    # mesh engines refuse 'flash' outright instead of feeding the kernel
    # garbage
    comm = ht.get_comm()
    S = 128 * max(comm.size, 2)
    q = jnp.asarray(RNG.integers(-3, 3, size=(S, 2, 32)), jnp.int32)
    out = flash_attention(q, q, q, causal=True)
    assert out.shape == q.shape  # fallback path computed something sane
    if comm.size > 1:
        qs = comm.apply_sharding(q, 0)
        with pytest.raises(ValueError, match="conforming"):
            ht.parallel.ring_attention(qs, qs, qs, comm=comm, local_kernel="flash")


# --------------------------------------------------------------------- #
# zig-zag causal ring                                                   #
# --------------------------------------------------------------------- #

def _sub_comm(k):
    devs = jax.devices()
    if len(devs) < k:
        pytest.skip(f"needs {k} devices")
    return XlaCommunication(devs[:k])


@pytest.mark.parametrize("mesh_size", [1, 2, 4, 8])
@pytest.mark.parametrize("local_kernel", ["xla", "flash"])
def test_zigzag_ring_matches_single_device(mesh_size, local_kernel):
    comm = _sub_comm(mesh_size)
    # Lh = S/(2*size) = 128 so the flash engine conforms on every mesh
    S, H, D = 256 * mesh_size, 2, 16
    q, k, v = (RNG.normal(size=(S, H, D)).astype(np.float32) for _ in range(3))
    qs, ks, vs = (comm.apply_sharding(jnp.asarray(x), 0) for x in (q, k, v))
    out = ht.parallel.ring_attention(
        qs, ks, vs, causal=True, comm=comm, local_kernel=local_kernel
    )
    np.testing.assert_allclose(np.asarray(out), _reference(q, k, v), atol=2e-5)


def test_zigzag_ring_non_divisible_sequence():
    # S % size != 0 routes to the single-block branch (GSPMD fallback),
    # S % size == 0 but S % (2*size) != 0 keeps the contiguous causal
    # ring — both must still be exact
    comm = ht.get_comm()
    if comm.size == 1:
        pytest.skip("needs a mesh")
    for S in (comm.size * 4 + 1, comm.size * 5):  # indivisible / odd-L
        q, k, v = (RNG.normal(size=(S, 2, 8)).astype(np.float32) for _ in range(3))
        qs, ks, vs = (comm.apply_sharding(jnp.asarray(x), 0) for x in (q, k, v))
        out = ht.parallel.ring_attention(qs, ks, vs, causal=True, comm=comm)
        np.testing.assert_allclose(
            np.asarray(out), _reference(q, k, v), atol=2e-5
        )


def _zigzag_replay(q, k, v, size):
    """Single-device serial replay of the zig-zag ring's exact fold
    schedule (same chunks, same order, same `_blockwise_update` algebra,
    same per-device (B, H, Lh, D) operand shapes), reassembled to
    contiguous layout.  Each device's fold chain is compiled as ONE
    program — per-fold eager dispatch compiles each op separately, which
    changes XLA's fusion/FMA choices and perturbs the last ulp."""
    import functools

    S, H, D = q.shape
    Lh = S // (2 * size)
    scale = jnp.float32(1.0 / np.sqrt(D))
    # the ring's per-device view: (B=1, H, S, D); chunk c = rows
    # [c*Lh, (c+1)*Lh)
    qt, kt, vt = (jnp.moveaxis(jnp.asarray(x), 1, 0)[None] for x in (q, k, v))
    chunk = lambda t, c: t[:, :, c * Lh:(c + 1) * Lh]
    tri = (jnp.arange(Lh)[:, None] >= jnp.arange(Lh)[None, :])[None, None]

    @functools.partial(jax.jit, static_argnames=("schedule",))
    def device_out(q_lo, q_hi, ksegs, vsegs, schedule):
        st = {
            h: (
                jnp.full((1, H, Lh), -jnp.inf, jnp.float32),
                jnp.zeros((1, H, Lh, D), jnp.float32),
                jnp.zeros((1, H, Lh), jnp.float32),
            )
            for h in ("lo", "hi")
        }
        for half, ci, masked in schedule:
            st[half] = _blockwise_update(
                q_lo if half == "lo" else q_hi,
                ksegs[ci], vsegs[ci], *st[half], scale,
                mask=tri if masked else None,
            )
        return [
            st[h][1] / jnp.maximum(st[h][2], 1e-30)[..., None]
            for h in ("lo", "hi")
        ]

    out = np.zeros((1, H, S, D), np.float32)
    for i in range(size):  # device i holds chunks i and 2*size-1-i
        ci_lo, ci_hi = i, 2 * size - 1 - i
        # round 0: (lo,lo) diag, (hi,lo) full, (hi,hi) diag — then one
        # always-full (hi, chunk j) per round plus the parity-selected
        # second pair, exactly the ring body's order
        sched = [("lo", ci_lo, True), ("hi", ci_lo, False), ("hi", ci_hi, True)]
        for r in range(1, size):
            j = (i - r) % size
            sched.append(("hi", j, False))
            sched.append(
                ("lo", j, False) if j < i else ("hi", 2 * size - 1 - j, False)
            )
        ksegs = tuple(chunk(kt, c) for c in range(2 * size))
        vsegs = tuple(chunk(vt, c) for c in range(2 * size))
        o_lo, o_hi = device_out(
            chunk(qt, ci_lo), chunk(qt, ci_hi), ksegs, vsegs, tuple(sched)
        )
        out[:, :, ci_lo * Lh:(ci_lo + 1) * Lh] = np.asarray(o_lo)
        out[:, :, ci_hi * Lh:(ci_hi + 1) * Lh] = np.asarray(o_hi)
    return np.moveaxis(out[0], 0, 1)


def test_zigzag_ring_bitwise_vs_schedule_replay():
    """The ring against the serial replay of its own fold schedule: a
    STATED TOLERANCE, not one reduction order by construction.

    Why not bitwise: the replay runs the same folds in the same order on
    the same (1, H, Lh, D) operands through the same ``_blockwise_update``,
    but as one straight-line program a device where the ring is a
    ``fori_loop`` body inside ``shard_map``.  XLA's CPU backend picks a
    dot's blocking, and whether ``a * b + c`` becomes one FMA, from the
    fusion it lands in, so the two read one float32 rounding apart in a
    few entries of a hundred on some machines and bit-equal on others;
    the ring's kernel is a closure the test cannot re-enter, so the same
    compiled dots cannot be had from here.

    What can be guaranteed of two float32 runs of ONE operation sequence
    that differ only in the rounding inside an operation, per output
    entry, in units of ``eps * attention(|v|)`` (eps = 2**-23 covers both
    sides): ``2 (D + 1) A`` for a key's score (D products and a scale,
    ``A`` the largest ``sum_d |q||k| * scale``, entering numerator and
    denominator weights), ``2 (Lh + 1)`` for the fold's two sums the key
    sits in, 6 a fold for the correction's exp, multiply and add, and 4
    for the key's own exp and the final divide.  A wrong chunk, a missed
    or doubled fold, or a mask off by one row reads 1e-2 and more.

    Exact part: the ring is finite everywhere and a second call returns
    the same bits (one compiled program, no order left to the runtime)."""
    comm = ht.get_comm()
    if comm.size == 1:
        pytest.skip("needs a mesh")
    size = comm.size
    S, H, D = 2 * size * 8, 2, 8  # Lh = 8: xla engine (flash would not conform)
    q, k, v = (RNG.normal(size=(S, H, D)).astype(np.float32) for _ in range(3))
    qs, ks, vs = (comm.apply_sharding(jnp.asarray(x), 0) for x in (q, k, v))
    ring = np.asarray(ht.parallel.ring_attention(
        qs, ks, vs, causal=True, comm=comm, local_kernel="xla"
    ))
    replay = _zigzag_replay(q, k, v, size)
    Lh, n_folds = S // (2 * size), 2 * size
    A = float(np.max(np.einsum("qhd,khd->hqk", np.abs(q), np.abs(k)))) / np.sqrt(D)
    k_round = 2 * (D + 1) * A + 2 * (Lh + 1) + 6 * n_folds + 4
    bound = k_round * float(np.finfo(np.float32).eps) * _reference(q, k, np.abs(v))
    assert_within_bound(ring, replay, bound)
    again = np.asarray(ht.parallel.ring_attention(
        qs, ks, vs, causal=True, comm=comm, local_kernel="xla"
    ))
    np.testing.assert_array_equal(again, ring)


def test_zigzag_flash_and_xla_engines_agree():
    # both engines fold the identical zig-zag schedule with the identical
    # f32 streaming-softmax algebra — on the CPU mesh (interpreted
    # Pallas) they must agree bitwise, a much stronger check than atol
    comm = ht.get_comm()
    if comm.size == 1:
        pytest.skip("needs a mesh")
    S, H, D = 256 * comm.size, 2, 16  # Lh = 128: flash conforms
    q, k, v = (RNG.normal(size=(S, H, D)).astype(np.float32) for _ in range(3))
    qs, ks, vs = (comm.apply_sharding(jnp.asarray(x), 0) for x in (q, k, v))
    a = np.asarray(ht.parallel.ring_attention(
        qs, ks, vs, causal=True, comm=comm, local_kernel="flash"
    ))
    b = np.asarray(ht.parallel.ring_attention(
        qs, ks, vs, causal=True, comm=comm, local_kernel="xla"
    ))
    np.testing.assert_array_equal(a, b)


def test_zigzag_ring_bf16():
    comm = ht.get_comm()
    if comm.size == 1:
        pytest.skip("needs a mesh")
    S, H, D = 256 * comm.size, 2, 16
    q, k, v = (RNG.normal(size=(S, H, D)).astype(np.float32) for _ in range(3))
    qb, kb, vb = (
        comm.apply_sharding(jnp.asarray(x, jnp.bfloat16), 0) for x in (q, k, v)
    )
    out = ht.parallel.ring_attention(qb, kb, vb, causal=True, comm=comm)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), _reference(q, k, v), atol=7e-2
    )
