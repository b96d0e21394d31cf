"""Fused (flash) attention — a Pallas TPU kernel.

The plain attention path (ring_attention's single-block branch; the
reference has no fused kernel at all — its long-context story is
process-level sequence parallelism) materializes the full (H, S, S)
score tensor in HBM: at S=4096, H=16 in float32 that is 1 GB written and
read twice more through softmax and the PV matmul.  This kernel never materializes
scores: each (q-block, k-block) tile lives in VMEM, the softmax is the
streaming one-pass rescaling (same algebra as
ring_attention._blockwise_update, which IS flash attention across
devices — here applied across VMEM blocks), and only the (S, D) output
ever touches HBM.  Its rate is not measured on today's code: no cell of
the benchmark runs attention (ROADMAP Reach B7, ``attn_32k_c1``);
``chip_smoke.py`` checks its result on the chip, not its time.

Layout: grid (batch*heads, S/BQ); each program pins its q block plus the
full local K/V in VMEM and streams K/V through the running softmax in
BK-sized chunks carried in registers.  Causal attention runs on a
TRIANGULAR schedule: the k-chunk loop bounds are per-program values from
``_causal_chunk_bounds`` — chunks wholly below the diagonal fold with no
mask, the one-or-two chunks straddling it fold with the element mask,
and chunks wholly above it are never visited at all (a dynamic-bound
``fori_loop`` lowers to a plain `while` on Mosaic, so the skipped chunks
cost zero MXU work — unlike a value-level ``lax.cond``, which lowers to
compute-both-select).  Causal also clamps BK to BQ: with BK=2048 a
512-row q block's diagonal chunk is 87% masked work, while BK=BQ=512
bounds the masked fraction of visited tiles by ~1/(2n).  Alternatives
that were tried and dropped before PR 1 (their rates are in no record):
- a third k grid dimension with scratch accumulators: a scratch round
  trip and a small DMA per chunk;
- VMEM scratch accumulators instead of loop carries;
- causal tail skip via ``lax.cond`` (the pre-triangular scheme): Mosaic
  lowers the value-level cond to compute-both-select, so the masked half
  is computed and discarded.

Falls back to the jnp path (XLA-fused, HBM-bound but correct) off-TPU
unless ``interpret=True`` (used by the CPU test suite), and for local
K/V too large for VMEM residency (long single-chip sequences — the ring
path shards the sequence before this kernel sees it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_partial"]

#: per-kernel VMEM budget (bytes) the compiler may use; the guard below
#: keeps K/V residency + score tiles + double buffering under it
_VMEM_LIMIT = 100 * 1024 * 1024


def _causal_chunk_bounds(q_lo, k_lo, bq, block_k, nk):
    """Triangular trip counts for one q block against an ``nk``-chunk K
    span: chunk ``j`` covers k positions [k_lo + j*bk, k_lo + (j+1)*bk).
    Returns ``(full, total)`` with chunks [0, full) wholly unmasked
    (last k position <= q_lo, the smallest q position), [full, total)
    straddling the diagonal (element mask needed), and [total, nk) wholly
    masked — never visited.  ``full <= total`` always.  Accepts python
    ints (tests, schedule planning) or traced i32 (kernel bodies, where
    ring round offsets are runtime values); floor division keeps the
    clamps right for negative offsets (q entirely before k: total = 0).

    THE one trip-count rule — _stream_kv's loop bounds and the tile-count
    test both read it, so the kernel cannot silently regress to n^2."""
    full = jnp.clip((q_lo - k_lo + 1) // block_k, 0, nk)
    total = jnp.clip((q_lo + bq - 1 - k_lo) // block_k + 1, 0, nk)
    return full, total


def _stream_kv(q, k_ref, v_ref, m0, l0, acc0, *, scale, causal, prec,
               q_lo, k_lo, block_k):
    """Shared streaming-softmax core: fold ``block_k`` chunks of the
    VMEM-resident K/V into the running (m, l, acc), carried in registers.
    ``q_lo``/``k_lo`` are the GLOBAL positions of q row 0 / k row 0 (i32
    scalars — traced in the partial form, where ring round offsets are
    runtime values).  Causal folds run the triangular schedule: unmasked
    chunks then diagonal chunks, with per-program dynamic loop bounds
    from ``_causal_chunk_bounds`` (chunks past the diagonal are never
    visited — Mosaic lowers a dynamic-bound fori_loop to a plain while,
    NOT compute-both-select)."""
    bq = q.shape[0]
    nk = k_ref.shape[1] // block_k

    def make_fold(masked):
        def fold(j, carry):
            m, l, acc = carry
            start = j * block_k
            k_blk = k_ref[0, pl.ds(start, block_k), :]
            v_blk = v_ref[0, pl.ds(start, block_k), :]
            scores = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            ) * scale  # (BQ, BK) f32
            if masked:
                q_pos = q_lo + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 0
                )
                k_pos = k_lo + start + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 1
                )
                keep = q_pos >= k_pos
                scores = jnp.where(keep, scores, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
            safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(scores - safe_m[:, None])
            if masked:
                p = jnp.where(keep, p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
            acc = acc * corr[:, None] + jax.lax.dot_general(
                # PV rides the same MXU path as QK^T: p drops to the
                # input dtype (standard flash practice; exact for f32)
                p.astype(v_ref.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            )
            l = l * corr + jnp.sum(p, axis=-1)
            return m_new, l, acc

        return fold

    if not causal:
        return jax.lax.fori_loop(0, nk, make_fold(False), (m0, l0, acc0))
    full, total = _causal_chunk_bounds(q_lo, k_lo, bq, block_k, nk)
    carry = jax.lax.fori_loop(0, full, make_fold(False), (m0, l0, acc0))
    return jax.lax.fori_loop(full, total, make_fold(True), carry)


def _kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, q_base, block_k):
    """One q block, full softmax: stream K/V via _stream_kv and write the
    normalized output."""
    qi = pl.program_id(1)
    bq, d = q_ref.shape[1], q_ref.shape[2]
    # np.sqrt hands back a STRONG np.float64 scalar; unpinned it drags
    # every accumulator to f64 under x64 (see ring_attention)
    scale = jnp.float32(scale)
    # framework convention: see _matmul_precision — this backend's
    # DEFAULT is the bf16 MXU path (fine for bf16 inputs, a 1e-1-scale
    # score error for f32 ones).  bf16 operands feed the MXU untouched;
    # softmax/accumulation are f32.
    prec = _matmul_precision(q_ref.dtype)
    m0 = jnp.full((bq,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = _stream_kv(
        q_ref[0], k_ref, v_ref, m0, l0, acc0,
        scale=scale, causal=causal, prec=prec,
        q_lo=q_base + qi * bq, k_lo=0, block_k=block_k,
    )
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _kernel_partial(
    bases_ref, q_ref, k_ref, v_ref, m_in, l_in, acc_in,
    m_out, l_out, acc_out, *, scale, causal, block_k,
):
    """One q block, PARTIAL softmax: fold this K/V segment into the
    caller's running (m, l, acc) state.  ``bases_ref`` (SMEM, i32[2]) is
    the global position of q row 0 / k row 0 — runtime values, because
    under ring sequence-parallelism they are per-device, per-round ring
    offsets.  The caller normalizes (acc / l) after the last segment."""
    qi = pl.program_id(1)
    bq = q_ref.shape[1]
    scale = jnp.float32(scale)
    prec = _matmul_precision(q_ref.dtype)
    # m/l travel as (BH, Lq, 1): Mosaic requires the last two block dims
    # divisible by (8, 128) OR equal to the array dims — a (1, bq) block
    # of a (BH, Lq) array is neither, a (1, bq, 1) block passes
    m, l, acc = _stream_kv(
        q_ref[0], k_ref, v_ref, m_in[0, :, 0], l_in[0, :, 0], acc_in[0],
        scale=scale, causal=causal, prec=prec,
        q_lo=bases_ref[0] + qi * bq, k_lo=bases_ref[1], block_k=block_k,
    )
    m_out[0] = m[:, None]
    l_out[0] = l[:, None]
    acc_out[0] = acc


def _pick_block(s: int, target: int) -> int:
    """Largest power-of-two block <= target dividing s (s is a multiple
    of 128 when this is called)."""
    b = target
    while b > 128 and s % b:
        b //= 2
    return b if s % b == 0 else 128


def conforms(seq_len: int, d: int, dtype) -> bool:
    """True when the fused kernel accepts a local block of this shape:
    128-aligned sequence, f32/bf16 (f32 accumulator), K/V within the
    VMEM residency budget.  THE one conformance predicate — ring and
    ulysses gate their ``local_kernel`` dispatch on it, so it can never
    drift from the kernel's own fallback rule."""
    dt = jnp.dtype(dtype)
    return (
        seq_len % 128 == 0
        and dt != jnp.float64
        # floating REQUIRED: promote_types alone admits int/bool (they
        # promote to f32 weakly) and the kernel's -inf/exp algebra is
        # meaningless for them
        and jnp.issubdtype(dt, jnp.floating)
        and jnp.promote_types(dt, jnp.float32) == jnp.float32
        and 4 * seq_len * d * dt.itemsize <= _VMEM_LIMIT // 2
    )


def _matmul_precision(dtype):
    """The framework matmul convention (linalg.basics): true-f32/f64
    passes for float inputs, the native bf16 MXU path for bf16 — shared
    by flash, ring and ulysses so the policy cannot drift."""
    return (
        jax.lax.Precision.HIGHEST
        if dtype in (jnp.float32, jnp.float64)
        else jax.lax.Precision.DEFAULT
    )


def _jnp_fallback(q, k, v, causal, q_base=0):
    """Plain XLA attention on (B, S, H, D); honors ``q_base`` and
    K/V longer than Q (the sequence-sharded local-block contract)."""
    prec = _matmul_precision(q.dtype)
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)  # f64 stays f64
    # the scale lives in the ACC dtype from the start: rounding it
    # through f32 would silently degrade f64 attention
    scale = jnp.asarray(1.0 / np.sqrt(q.shape[-1]), acc_dt)
    qt, kt, vt = (jnp.moveaxis(t, 2, 1) for t in (q, k, v))
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", qt, kt,
        preferred_element_type=acc_dt, precision=prec,
    ) * scale
    if causal:
        s, sk = q.shape[1], k.shape[1]
        q_pos = q_base + jnp.arange(s)[:, None]
        scores = jnp.where(q_pos >= jnp.arange(sk)[None, :], scores, -jnp.inf)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), vt,
        preferred_element_type=acc_dt, precision=prec,
    )
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "interpret", "q_base", "block_q", "block_k")
)
def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    interpret: bool = False,
    q_base: int = 0,
    block_q: int = 512,
    block_k: int = 2048,
):
    """Fused exact attention on (B, S, H, D) or (S, H, D) inputs.

    ``q_base`` offsets the causal mask's query positions (for use as a
    local block kernel under sequence sharding — K/V may be longer than
    Q).  ``interpret`` runs the Pallas interpreter (CPU test suite).
    Matmuls follow the framework precision convention (true-f32 for f32
    inputs, native MXU bf16 for bf16); softmax and accumulation are
    always f32.
    """
    batched = q.ndim == 4
    if not batched:
        q, k, v = q[None], k[None], v[None]
    B, S, H, D = q.shape
    Sk = k.shape[1]
    scale = 1.0 / np.sqrt(D)

    on_tpu = jax.default_backend() == "tpu"
    # K/V residency estimate: both operands in VMEM, double-buffered
    kv_bytes = 4 * Sk * D * q.dtype.itemsize
    if (
        (not on_tpu and not interpret)
        or S % 128
        or Sk % 128
        or q.dtype == jnp.float64
        or not jnp.issubdtype(q.dtype, jnp.floating)  # same gate as conforms()
        or kv_bytes > _VMEM_LIMIT // 2
    ):
        out = _jnp_fallback(q, k, v, causal, q_base=q_base)
        return out if batched else out[0]

    bq = _pick_block(S, block_q)
    # causal: clamp BK to BQ so the triangular schedule's savings survive
    # the chunking — at BK >> BQ the diagonal chunk is mostly masked work
    bk = _pick_block(Sk, min(block_k, bq) if causal else block_k)

    # (B, H, S, D) so the grid can address (batch*heads, q-block)
    qt, kt, vt = (jnp.moveaxis(t, 2, 1).reshape(B * H, -1, D) for t in (q, k, v))

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, q_base=q_base, block_k=bk
    )
    # under the package's x64-on default, python-int literals in index
    # maps and grid arithmetic trace as i64, which Mosaic rejects; the
    # x64-off context makes them i32 (same guard as linalg/svd.py — the
    # operands are already-typed tracers, so only index dtypes change)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kern,
            grid=(B * H, S // bq),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
                pl.BlockSpec((1, Sk, D), lambda bh, qi: (bh, 0, 0)),
                pl.BlockSpec((1, Sk, D), lambda bh, qi: (bh, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
            out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=interpret,
        )(qt, kt, vt)
    out = jnp.moveaxis(out.reshape(B, H, S, D), 1, 2)
    return out if batched else out[0]


def flash_attention_partial(
    q, k, v, m, l, acc,
    q_base, k_base,
    causal: bool = False,
    interpret: bool = False,
    block_q: int = 512,
    block_k: int = 2048,
    vma_axes: tuple = (),
):
    """One fused PARTIAL attention update: fold the K/V segment into the
    running streaming-softmax state and return it un-normalized.

    This is the local block engine for ring sequence parallelism: each
    ring round hands the visiting K/V segment plus its global offset
    (``k_base``, a traced per-device value) to this kernel instead of
    materializing an L×L score tile in HBM.  Shapes: ``q`` (BH, Lq, D)
    in the input dtype; ``k``/``v`` (BH, Lk, D); state ``m``/``l``
    (BH, Lq) f32 and ``acc`` (BH, Lq, D) f32.  Initialize with
    ``m = -inf``, ``l = 0``, ``acc = 0``; after the final segment the
    caller computes ``acc / max(l, eps)``.

    Plain traceable function (no jit wrapper): it is designed to be
    called INSIDE shard_map/fori_loop bodies.  ``interpret`` runs the
    Pallas interpreter (CPU test suite); callers gate conformance
    (Lq/Lk multiples of 128, not f64, K/V within the VMEM budget) and
    fall back to the jnp algebra themselves — see ring_attention.
    ``vma_axes`` names the shard_map mesh axes the outputs vary over
    (required when check_vma validation is on around this call).
    """
    BH, Lq, D = q.shape
    Lk = k.shape[1]
    bq = _pick_block(Lq, block_q)
    bk = _pick_block(Lk, block_k)
    scale = 1.0 / np.sqrt(D)
    bases = jnp.stack(
        [jnp.asarray(q_base, jnp.int32), jnp.asarray(k_base, jnp.int32)]
    )

    kern = functools.partial(
        _kernel_partial, scale=scale, causal=causal, block_k=bk
    )
    state_q = lambda bh, qi: (bh, qi, 0)
    whole_k = lambda bh, qi: (bh, 0, 0)
    # x64 off for index arithmetic — see flash_attention
    with jax.enable_x64(False):
        m_o, l_o, acc = pl.pallas_call(
            kern,
            grid=(BH, Lq // bq),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, bq, D), state_q),
                pl.BlockSpec((1, Lk, D), whole_k),
                pl.BlockSpec((1, Lk, D), whole_k),
                pl.BlockSpec((1, bq, 1), state_q),
                pl.BlockSpec((1, bq, 1), state_q),
                pl.BlockSpec((1, bq, D), state_q),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, 1), state_q),
                pl.BlockSpec((1, bq, 1), state_q),
                pl.BlockSpec((1, bq, D), state_q),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, Lq, 1), jnp.float32, vma=frozenset(vma_axes)),
                jax.ShapeDtypeStruct((BH, Lq, 1), jnp.float32, vma=frozenset(vma_axes)),
                jax.ShapeDtypeStruct((BH, Lq, D), jnp.float32, vma=frozenset(vma_axes)),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=interpret,
        )(bases, q, k, v, m[..., None], l[..., None], acc)
    return m_o[..., 0], l_o[..., 0], acc
