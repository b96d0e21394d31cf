"""Ring attention: exact blockwise attention over sequence-sharded inputs.

The long-context flagship of the parallelism toolkit.  The sequence axis is
sharded across the mesh; each device keeps its query block stationary while
key/value blocks rotate one hop per round on the ``ppermute`` ring — the
identical communication shape as the reference's pairwise-distance ring
(spatial/distance.py:261-345), upgraded with the blockwise-softmax
(running log-sum-exp) accumulation so the result is *exact* attention, not
an approximation.  Under the overlap policy
(:func:`heat_tpu.comm.overlap.set_overlap`; docs/design.md §18) the ring
bodies are double-buffered: round ``r`` issues the ``ppermute`` for the
round-``r+1`` K/V operand while the MXU folds the round-``r`` operand, so
the ICI transfer hides behind the q·kᵀ and p·v matmuls instead of
serializing with them.  The fold schedule is identical either way —
overlapped and serial programs are bitwise-equal.

No reference analog (HeAT has no attention); included because long-context
sequence parallelism is a first-class capability of this framework.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import pcast
from jax.sharding import PartitionSpec

from ..comm.overlap import overlap_enabled, timed_dispatch
from ..core._compile import jitted
from ..core.communication import XlaCommunication, get_comm
from ..core.dndarray import DNDarray

__all__ = ["ring_attention", "ring_self_attention"]


def _blockwise_update(q, k, v, m, num, den, scale, mask=None):
    """One streaming-softmax accumulation step (flash-attention algebra).

    Scores and accumulators stay in the accumulator dtype (``num.dtype``,
    f32 for f32/bf16 inputs): the einsums pin it via
    ``preferred_element_type`` so neither a bf16 input nor a wide scalar
    can move the softmax off f32 — under x64 an unpinned
    ``np.float64`` scale silently promoted the whole S×S score tensor to
    software-emulated f64, which never reaches the MXU."""
    from .flash_attention import _matmul_precision

    acc = num.dtype
    prec = _matmul_precision(q.dtype)
    scores = jnp.einsum(
        "...qd,...kd->...qk", q, k, preferred_element_type=acc, precision=prec
    ) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    m_blk = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows (all -inf): keep them neutral
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(scores - safe_m[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    correction = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    num = num * correction[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p, v, preferred_element_type=acc, precision=prec
    )
    den = den * correction + jnp.sum(p, axis=-1)
    return m_new, num, den


def ring_attention(
    q,
    k,
    v,
    causal: bool = False,
    comm: Optional[XlaCommunication] = None,
    local_kernel: str = "auto",
) -> jax.Array:
    """Exact attention over a sequence-sharded (seq, heads, dim) — or
    (batch, seq, heads, dim) — input.

    The sequence axis (axis 0, or 1 with a batch axis) must be divisible by
    the mesh size; each round rotates the K/V blocks one hop and folds them
    into the running softmax.  ``causal=True`` applies the global causal
    mask using each block's ring-origin offset.

    Causal masking is load-balanced with the ZIG-ZAG layout whenever
    S is divisible by 2*size (and, for the flash engine, the half-chunk
    S/(2*size) is a 128-multiple): the inputs are resplit in-ring
    (primitives.zigzag_split) so device ``i`` holds sequence half-chunks
    ``i`` and ``2*size-1-i``, which makes every device's per-round work
    exactly two wholly-unmasked half-chunk updates — no fully-masked
    tile is ever computed, and no device waits on a longer-diagonal
    peer.  The output is resplit back to contiguous, so the layout is
    invisible to callers.  When the zig-zag shape conditions fail, the
    contiguous layout is kept: the flash engine still skips masked work
    per-program (the triangular kernel's dynamic trip counts make
    fully-masked rounds cost zero folds) but rounds are unbalanced; the
    XLA engine masks and discards.

    ``local_kernel`` picks the per-round block engine:
    - ``"auto"``: the fused Pallas partial kernel
      (flash_attention_partial) on TPU when the local block conforms
      (flash_attention.conforms: L a multiple of 128, f32/bf16, K/V
      within the VMEM budget) — it never materializes the L×L score
      tile in HBM (rates of either engine: not measured on today's
      code; no cell) — else the XLA blockwise update;
    - ``"flash"``: force the Pallas engine (interpreted off-TPU — the
      CPU test suite's path for exercising the real ring+flash program);
    - ``"xla"``: force the jnp blockwise update.

    The compiled ring program is cached per (comm, config) through the
    op engine's keyed-jit cache: building a fresh ``jax.jit`` object per
    call would recompile the whole program on every invocation.
    """
    if local_kernel not in ("auto", "flash", "xla"):
        raise ValueError(f"local_kernel must be auto|flash|xla, got {local_kernel!r}")
    if isinstance(q, DNDarray):
        comm = comm or q.comm
        q, k, v = q.larray, k.larray, v.larray
    comm = comm or get_comm()
    size = comm.size

    batched = q.ndim == 4
    if not batched:
        q, k, v = q[None], k[None], v[None]  # (1, S, H, D)
    B, S, H, D = q.shape
    # accumulator dtype: f32 for f32/bf16 inputs (flash convention).  The
    # scale is CAST rather than left as np.sqrt's np.float64 scalar —
    # under x64 that scalar is strong-typed and promoted every score
    # tensor to f64, which the TPU emulates in software
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    scale = jnp.asarray(1.0 / np.sqrt(D), acc_dt)

    if size == 1 or S % size != 0:
        # single block.  The local_kernel contract holds here too:
        # 'flash' may not silently become XLA and vice versa
        from .flash_attention import _jnp_fallback, conforms, flash_attention

        if local_kernel == "flash" and (size > 1 or not conforms(S, D, q.dtype)):
            raise ValueError(
                "local_kernel='flash' needs a mesh-divisible sequence "
                f"(S={S}, {size} devices) and a conforming shape "
                "(128-multiple, f32/bf16, within the VMEM budget); use "
                "'auto' for the silent fallback"
            )
        if size == 1 and local_kernel != "xla":
            # flash gates its own off-TPU/VMEM fallback; only engage it
            # when nothing is sharded — a Pallas call on a GSPMD-sharded
            # global (size > 1, S not mesh-divisible) would silently
            # replicate the whole computation per device.  'flash' forces
            # the Pallas kernel (interpreted off-TPU)
            out = flash_attention(
                q, k, v, causal=causal,
                interpret=(
                    local_kernel == "flash"
                    and jax.default_backend() != "tpu"
                ),
            )
        else:
            # sharded-but-indivisible (or forced XLA): the jitted jnp
            # path, GSPMD-planned over the existing sharding (mirrors the
            # ulysses fallback branch)
            key = ("ring_attention.single_xla", causal, B, S, H, D, str(q.dtype))
            out = jitted(
                key, lambda: (lambda a, b, c: _jnp_fallback(a, b, c, causal))
            )(q, k, v)
        return out if batched else out[0]

    mesh, name = comm.mesh, comm.axis_name
    L = S // size
    Lh = L // 2
    perm = [(i, (i + 1) % size) for i in range(size)]
    spec = PartitionSpec(None, name, None, None)
    # double-buffered ring bodies under the overlap policy; part of every
    # jitted cache key via the registered policy token, so the serial
    # twin and the overlapped ring coexist as separate compiled programs
    overlapped = overlap_enabled(size)

    def run_ring(ring_fn):
        if isinstance(q, jax.core.Tracer):  # inside fuse/jit: no host timing
            return ring_fn(q, k, v)
        return timed_dispatch(
            "ring_attention", overlapped, lambda: ring_fn(q, k, v)
        )

    # Causal load balancing: under contiguous sharding device 0's queries
    # see one non-empty round while device size-1's see all of them, so
    # the ring runs at the slowest device's pace.  The zig-zag layout
    # (primitives.zigzag_split: device i holds sequence half-chunks i and
    # 2*size-1-i) gives every device exactly two wholly-unmasked
    # half-chunk attention updates per round — equal work, and no
    # fully-masked pair is ever computed (the always-masked (low-q,
    # high-k) pair is statically absent).  Needs S % (2*size) == 0.
    zigzag = causal and S % (2 * size) == 0

    on_tpu = jax.default_backend() == "tpu"
    from .flash_attention import conforms

    # the ONE conformance predicate (flash_attention.conforms): 128-aligned
    # local block, f32/bf16, visiting K/V within the VMEM residency budget
    conforming = conforms(L, D, q.dtype)
    if local_kernel == "flash" and not conforming:
        raise ValueError(
            f"local_kernel='flash' needs a conforming local block (L={L} "
            "must be a multiple of 128, dtype f32/bf16, K/V within the "
            "VMEM budget); use 'auto' for the silent fallback"
        )
    use_flash = local_kernel == "flash" or (
        local_kernel == "auto" and on_tpu and conforming
    )

    if use_flash:
        from .flash_attention import flash_attention_partial
        from .primitives import zigzag_merge, zigzag_split

        interp = not on_tpu  # CPU test suite: Pallas interpreter

        def make_flash_zigzag():
            def kernel(q_blk, k_blk, v_blk):
                qf = jnp.moveaxis(q_blk, 2, 1).reshape(B * H, L, D)
                kf = jnp.moveaxis(k_blk, 2, 1).reshape(B * H, L, D)
                vf = jnp.moveaxis(v_blk, 2, 1).reshape(B * H, L, D)
                my = jax.lax.axis_index(name)
                q_lo, q_hi = zigzag_split(qf, 1, name, size)
                k_lo, k_hi = zigzag_split(kf, 1, name, size)
                v_lo, v_hi = zigzag_split(vf, 1, name, size)
                # rotate the zig-zag pair as one buffer: rows [:Lh] are
                # the origin's low chunk j, rows [Lh:] its high mirror
                # 2*size-1-j
                kz = jnp.concatenate([k_lo, k_hi], 1)
                vz = jnp.concatenate([v_lo, v_hi], 1)
                base_lo = my * Lh
                base_hi = (2 * size - 1 - my) * Lh

                def init():
                    return (
                        pcast(jnp.full((B * H, Lh), -jnp.inf, jnp.float32),
                              (name,), to="varying"),
                        pcast(jnp.zeros((B * H, Lh), jnp.float32),
                              (name,), to="varying"),
                        pcast(jnp.zeros((B * H, Lh, D), jnp.float32),
                              (name,), to="varying"),
                    )

                def fold(qh, kseg, vseg, st, diag, q_base, k_base):
                    # diag=False pairs are wholly unmasked by layout:
                    # causal=False skips the kernel's bounds/mask logic
                    # AND keeps the (effectful, axis_index-derived) bases
                    # out of the program
                    return flash_attention_partial(
                        qh, kseg, vseg, *st,
                        q_base=q_base, k_base=k_base,
                        causal=diag, interpret=interp,
                        vma_axes=() if interp else (name,),
                    )

                if overlapped:
                    # issue hop 1 ahead of the round-0 folds: the first
                    # transfer runs behind the two diagonal tiles
                    kz1 = jax.lax.ppermute(kz, name, perm)
                    vz1 = jax.lax.ppermute(vz, name, perm)
                # round 0 — the origin is this device: the two diagonal
                # Lh-tiles (the ONLY masked folds in the whole program)
                # plus the always-full (high-q, low-k) pair
                st_lo = fold(q_lo, kz[:, :Lh], vz[:, :Lh], init(),
                             True, base_lo, base_lo)
                st_hi = fold(q_hi, kz[:, :Lh], vz[:, :Lh], init(),
                             False, 0, 0)
                st_hi = fold(q_hi, kz[:, Lh:], vz[:, Lh:], st_hi,
                             True, base_hi, base_hi)

                def round_folds(r, kz, vz, st):
                    m_lo, l_lo, a_lo, m_hi, l_hi, a_hi = st
                    j = (my - r) % size  # visiting pair's home device
                    ks, vs = kz[:, :Lh], vz[:, :Lh]  # chunk j
                    kh, vh = kz[:, Lh:], vz[:, Lh:]  # chunk 2*size-1-j
                    # (q_hi, chunk j): high-q rows are past every low
                    # chunk — always wholly unmasked
                    m_hi, l_hi, a_hi = fold(
                        q_hi, ks, vs, (m_hi, l_hi, a_hi), False, 0, 0
                    )
                    # second pair: (q_lo, chunk j) when j < my, else
                    # (q_hi, chunk 2*size-1-j) — wholly unmasked either
                    # way, so every round costs exactly two full tiles
                    sel = j < my
                    q2 = jnp.where(sel, q_lo, q_hi)
                    k2 = jnp.where(sel, ks, kh)
                    v2 = jnp.where(sel, vs, vh)
                    st2 = tuple(
                        jnp.where(sel, a, b)
                        for a, b in zip((m_lo, l_lo, a_lo), (m_hi, l_hi, a_hi))
                    )
                    m2, l2, a2 = fold(q2, k2, v2, st2, False, 0, 0)
                    m_lo, l_lo, a_lo = (
                        jnp.where(sel, n, o)
                        for n, o in zip((m2, l2, a2), (m_lo, l_lo, a_lo))
                    )
                    m_hi, l_hi, a_hi = (
                        jnp.where(sel, o, n)
                        for n, o in zip((m2, l2, a2), (m_hi, l_hi, a_hi))
                    )
                    return m_lo, l_lo, a_lo, m_hi, l_hi, a_hi

                if overlapped:
                    # double-buffered: round r issues the hop producing
                    # the round-r+1 pair while the folds consume the
                    # round-r pair — same ppermute chain, same fold
                    # schedule as the serial arm, bitwise equal
                    def body(r, carry):
                        kc, vc, ki, vi = carry[:4]
                        kn = jax.lax.ppermute(ki, name, perm)
                        vn = jax.lax.ppermute(vi, name, perm)
                        st = round_folds(r, kc, vc, carry[4:])
                        return (ki, vi, kn, vn, *st)

                    kz2 = jax.lax.ppermute(kz1, name, perm)
                    vz2 = jax.lax.ppermute(vz1, name, perm)
                    out_st = jax.lax.fori_loop(
                        1, size, body, (kz1, vz1, kz2, vz2, *st_lo, *st_hi)
                    )[4:]
                else:
                    def body(r, carry):
                        kz, vz = carry[:2]
                        st = round_folds(r, kz, vz, carry[2:])
                        kz = jax.lax.ppermute(kz, name, perm)
                        vz = jax.lax.ppermute(vz, name, perm)
                        return (kz, vz, *st)

                    kz1 = jax.lax.ppermute(kz, name, perm)
                    vz1 = jax.lax.ppermute(vz, name, perm)
                    out_st = jax.lax.fori_loop(
                        1, size, body, (kz1, vz1, *st_lo, *st_hi)
                    )[2:]
                m_lo, l_lo, a_lo, m_hi, l_hi, a_hi = out_st
                out_lo = a_lo / jnp.maximum(l_lo, 1e-30)[..., None]
                out_hi = a_hi / jnp.maximum(l_hi, 1e-30)[..., None]
                out = zigzag_merge(out_lo, out_hi, 1, name, size)
                out = jnp.moveaxis(out.reshape(B, H, L, D), 1, 2)
                return out.astype(q_blk.dtype)

            # check_vma off around pallas_call — see make_flash below
            return shard_map(
                kernel, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )

        if zigzag and conforms(Lh, D, q.dtype):
            key = ("ring_attention.flash_zz", comm, B, S, H, D, str(q.dtype))
            out = run_ring(jitted(key, make_flash_zigzag))
            return out if batched else out[0]

        # contiguous layout: non-causal, or a causal shape the zig-zag
        # halves cannot conform to (Lh not a 128-multiple).  Causal here
        # is still triangular — the partial kernel's dynamic trip counts
        # make fully-masked rounds cost zero folds — just not
        # load-balanced across the ring.
        def make_flash():
            def kernel(q_blk, k_blk, v_blk):
                # (B, L, H, D) → (B*H, L, D) once, OUTSIDE the ring loop
                # — the flattened layout rotates directly (same bytes
                # over ICI)
                qf = jnp.moveaxis(q_blk, 2, 1).reshape(B * H, L, D)
                kf = jnp.moveaxis(k_blk, 2, 1).reshape(B * H, L, D)
                vf = jnp.moveaxis(v_blk, 2, 1).reshape(B * H, L, D)
                # axis_index only when the mask offsets are real: it is
                # effectful, so jax will not DCE it when unused, and an
                # unused partition_id breaks XLA's SPMD sharding inference
                my = jax.lax.axis_index(name) if causal else 0
                # carries pcast to varying (like the XLA kernel's
                # m0/num0/den0 below)
                m0 = pcast(
                    jnp.full((B * H, L), -jnp.inf, jnp.float32), (name,), to="varying"
                )
                l0 = pcast(
                    jnp.zeros((B * H, L), jnp.float32), (name,), to="varying"
                )
                acc0 = pcast(
                    jnp.zeros((B * H, L, D), jnp.float32), (name,), to="varying"
                )

                def fold(r, kb, vb, m, l, acc):
                    origin = (my - r) % size if causal else 0
                    return flash_attention_partial(
                        qf, kb, vb, m, l, acc,
                        q_base=my * L, k_base=origin * L,
                        causal=causal, interpret=interp,
                        vma_axes=() if interp else (name,),
                    )

                if overlapped:
                    # double-buffered: issue the hop producing the
                    # round-r+1 K/V while the kernel folds round r's —
                    # same ppermute chain and fold order as the serial
                    # arm, bitwise equal (design.md §18)
                    def body(r, carry):
                        kc, vc, ki, vi, m, l, acc = carry
                        kn = jax.lax.ppermute(ki, name, perm)
                        vn = jax.lax.ppermute(vi, name, perm)
                        m, l, acc = fold(r, kc, vc, m, l, acc)
                        return ki, vi, kn, vn, m, l, acc

                    ki0 = jax.lax.ppermute(kf, name, perm)
                    vi0 = jax.lax.ppermute(vf, name, perm)
                    _, _, _, _, m, l, acc = jax.lax.fori_loop(
                        0, size, body, (kf, vf, ki0, vi0, m0, l0, acc0)
                    )
                else:
                    def body(r, carry):
                        kb, vb, m, l, acc = carry
                        m, l, acc = fold(r, kb, vb, m, l, acc)
                        kb = jax.lax.ppermute(kb, name, perm)
                        vb = jax.lax.ppermute(vb, name, perm)
                        return kb, vb, m, l, acc

                    _, _, m, l, acc = jax.lax.fori_loop(
                        0, size, body, (kf, vf, m0, l0, acc0)
                    )
                out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B*H, L, D)
                out = jnp.moveaxis(out.reshape(B, H, L, D), 1, 2)
                return out.astype(q_blk.dtype)  # (B, L, H, D)

            # check_vma must be OFF around pallas_call in this jax
            # version — verified both ways: the interpreter traces the
            # kernel body as jax ops whose internal constants are
            # unvarying, and the Mosaic path rejects the kernel's
            # lax.cond under branch-vma matching.  The program is
            # per-device-pure (carries are pcast varying, all
            # collectives are the explicit ppermutes); the XLA
            # local-kernel path below keeps validation on.
            return shard_map(
                kernel, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )

        key = ("ring_attention.flash", comm, causal, B, S, H, D, str(q.dtype))
        out = run_ring(jitted(key, make_flash))
        return out if batched else out[0]

    def make_xla_zigzag():
        from .primitives import zigzag_merge, zigzag_split

        def kernel(q_blk, k_blk, v_blk):
            my = jax.lax.axis_index(name)
            q_lo, q_hi = zigzag_split(q_blk, 1, name, size)
            k_lo, k_hi = zigzag_split(k_blk, 1, name, size)
            v_lo, v_hi = zigzag_split(v_blk, 1, name, size)
            qlo = jnp.moveaxis(q_lo, 2, 1)  # (B, H, Lh, D)
            qhi = jnp.moveaxis(q_hi, 2, 1)
            kz = jnp.concatenate(
                [jnp.moveaxis(k_lo, 2, 1), jnp.moveaxis(k_hi, 2, 1)], 2
            )
            vz = jnp.concatenate(
                [jnp.moveaxis(v_lo, 2, 1), jnp.moveaxis(v_hi, 2, 1)], 2
            )
            # the only masked tiles in the whole program: the two round-0
            # diagonal Lh-triangles (their global base offsets cancel, so
            # one static triangular mask serves both)
            tri = (jnp.arange(Lh)[:, None] >= jnp.arange(Lh)[None, :])[None, None]

            def init():
                return (
                    pcast(jnp.full((B, H, Lh), -jnp.inf, acc_dt), (name,), to="varying"),
                    pcast(jnp.zeros((B, H, Lh, D), acc_dt), (name,), to="varying"),
                    pcast(jnp.zeros((B, H, Lh), acc_dt), (name,), to="varying"),
                )

            if overlapped:
                # issue hop 1 ahead of the round-0 diagonal updates
                kz1 = jax.lax.ppermute(kz, name, perm)
                vz1 = jax.lax.ppermute(vz, name, perm)
            st_lo = _blockwise_update(
                qlo, kz[:, :, :Lh], vz[:, :, :Lh], *init(), scale, mask=tri
            )
            st_hi = _blockwise_update(
                qhi, kz[:, :, :Lh], vz[:, :, :Lh], *init(), scale
            )
            st_hi = _blockwise_update(
                qhi, kz[:, :, Lh:], vz[:, :, Lh:], *st_hi, scale, mask=tri
            )

            def round_folds(r, kz, vz, st):
                m_lo, n_lo, d_lo, m_hi, n_hi, d_hi = st
                j = (my - r) % size
                ks, vs = kz[:, :, :Lh], vz[:, :, :Lh]  # chunk j
                kh, vh = kz[:, :, Lh:], vz[:, :, Lh:]  # chunk 2*size-1-j
                m_hi, n_hi, d_hi = _blockwise_update(
                    qhi, ks, vs, m_hi, n_hi, d_hi, scale
                )
                sel = j < my
                q2 = jnp.where(sel, qlo, qhi)
                k2 = jnp.where(sel, ks, kh)
                v2 = jnp.where(sel, vs, vh)
                st2 = tuple(
                    jnp.where(sel, a, b)
                    for a, b in zip((m_lo, n_lo, d_lo), (m_hi, n_hi, d_hi))
                )
                m2, n2, d2 = _blockwise_update(q2, k2, v2, *st2, scale)
                m_lo, n_lo, d_lo = (
                    jnp.where(sel, n, o)
                    for n, o in zip((m2, n2, d2), (m_lo, n_lo, d_lo))
                )
                m_hi, n_hi, d_hi = (
                    jnp.where(sel, o, n)
                    for n, o in zip((m2, n2, d2), (m_hi, n_hi, d_hi))
                )
                return m_lo, n_lo, d_lo, m_hi, n_hi, d_hi

            if overlapped:
                # double-buffered: same ppermute chain, same fold
                # schedule as the serial arm — bitwise equal
                def body(r, carry):
                    kc, vc, ki, vi = carry[:4]
                    kn = jax.lax.ppermute(ki, name, perm)
                    vn = jax.lax.ppermute(vi, name, perm)
                    st = round_folds(r, kc, vc, carry[4:])
                    return (ki, vi, kn, vn, *st)

                kz2 = jax.lax.ppermute(kz1, name, perm)
                vz2 = jax.lax.ppermute(vz1, name, perm)
                out_st = jax.lax.fori_loop(
                    1, size, body, (kz1, vz1, kz2, vz2, *st_lo, *st_hi)
                )[4:]
            else:
                def body(r, carry):
                    kz, vz = carry[:2]
                    st = round_folds(r, kz, vz, carry[2:])
                    kz = jax.lax.ppermute(kz, name, perm)
                    vz = jax.lax.ppermute(vz, name, perm)
                    return (kz, vz, *st)

                kz1 = jax.lax.ppermute(kz, name, perm)
                vz1 = jax.lax.ppermute(vz, name, perm)
                out_st = jax.lax.fori_loop(
                    1, size, body, (kz1, vz1, *st_lo, *st_hi)
                )[2:]
            m_lo, n_lo, d_lo, m_hi, n_hi, d_hi = out_st
            out_lo = n_lo / jnp.maximum(d_lo, 1e-30)[..., None]
            out_hi = n_hi / jnp.maximum(d_hi, 1e-30)[..., None]
            out = zigzag_merge(out_lo, out_hi, 2, name, size)  # (B, H, L, D)
            return jnp.moveaxis(out, 1, 2).astype(q_blk.dtype)

        return shard_map(
            kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
        )

    if zigzag:
        key = ("ring_attention.xla_zz", comm, B, S, H, D, str(q.dtype))
        out = run_ring(jitted(key, make_xla_zigzag))
        return out if batched else out[0]

    def make_xla():
        def kernel(q_blk, k_blk, v_blk):
            # local blocks: (B, L, H, D) → (B, H, L, D)
            qb = jnp.moveaxis(q_blk, 2, 1)
            my = jax.lax.axis_index(name)
            q_pos = my * L + jnp.arange(L)

            # accumulators explicitly acc_dt: under x64, default-dtype
            # zeros/full are f64 and would drag the whole streaming
            # softmax into emulated double precision
            m0 = pcast(jnp.full((B, H, L), -jnp.inf, acc_dt), (name,), to="varying")
            num0 = pcast(jnp.zeros((B, H, L, D), acc_dt), (name,), to="varying")
            den0 = pcast(jnp.zeros((B, H, L), acc_dt), (name,), to="varying")

            def fold(r, kb, vb, m, num, den):
                origin = (my - r) % size  # this kv block's home shard
                k_pos = origin * L + jnp.arange(L)
                kbt = jnp.moveaxis(kb, 2, 1)
                vbt = jnp.moveaxis(vb, 2, 1)
                mask = (q_pos[:, None] >= k_pos[None, :]) if causal else None
                return _blockwise_update(
                    qb, kbt, vbt, m, num, den, scale,
                    mask=None if mask is None else mask[None, None],
                )

            if overlapped:
                # double-buffered: same ppermute chain, same fold order
                # as the serial arm — bitwise equal (design.md §18)
                def body(r, carry):
                    kc, vc, ki, vi, m, num, den = carry
                    kn = jax.lax.ppermute(ki, name, perm)
                    vn = jax.lax.ppermute(vi, name, perm)
                    m, num, den = fold(r, kc, vc, m, num, den)
                    return ki, vi, kn, vn, m, num, den

                ki0 = jax.lax.ppermute(k_blk, name, perm)
                vi0 = jax.lax.ppermute(v_blk, name, perm)
                _, _, _, _, m, num, den = jax.lax.fori_loop(
                    0, size, body, (k_blk, v_blk, ki0, vi0, m0, num0, den0)
                )
            else:
                def body(r, carry):
                    kb, vb, m, num, den = carry
                    m, num, den = fold(r, kb, vb, m, num, den)
                    kb = jax.lax.ppermute(kb, name, perm)
                    vb = jax.lax.ppermute(vb, name, perm)
                    return kb, vb, m, num, den

                _, _, m, num, den = jax.lax.fori_loop(
                    0, size, body, (k_blk, v_blk, m0, num0, den0)
                )
            out = num / jnp.maximum(den, 1e-30)[..., None]  # (B, H, L, D)
            return jnp.moveaxis(out, 1, 2).astype(q_blk.dtype)  # (B, L, H, D)

        return shard_map(
            kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
        )

    key = ("ring_attention.xla", comm, causal, B, S, H, D, str(q.dtype))
    out = run_ring(jitted(key, make_xla))
    return out if batched else out[0]


def ring_self_attention(x, wq, wk, wv, causal: bool = False, comm=None) -> jax.Array:
    """Convenience wrapper: project x with (wq, wk, wv) then ring-attend.
    ``x``: (S, E) or (B, S, E) sequence-sharded; weights (E, H*D) with an
    implied single head when 2-D outputs are given."""
    if isinstance(x, DNDarray):
        comm = comm or x.comm
        x = x.larray
    q = jnp.einsum("...se,ed->...sd", x, wq)
    k = jnp.einsum("...se,ed->...sd", x, wk)
    v = jnp.einsum("...se,ed->...sd", x, wv)
    # single-head layout: (…, S, D) → (…, S, 1, D)
    q, k, v = q[..., None, :], k[..., None, :], v[..., None, :]
    out = ring_attention(q, k, v, causal=causal, comm=comm)
    return out[..., 0, :]
