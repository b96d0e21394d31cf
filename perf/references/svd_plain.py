"""Plain reference for the tall SVD configurations: the reduced SVD
``A = U·diag(S)·Vᵀ`` of a (rows, columns) float32 array with many more rows
than columns.

R comes from a blocked Householder TSQR: ``jax.numpy``'s QR (R only) of each
block of rows, then of the stacked R factors, in float32 with every product
at ``highest``; R's SVD is taken on the host in float64.  The columns of U
the judge needs are ``uⱼ = A·vⱼ / sⱼ``, formed a block of rows at a time.
Imports nothing of the program and is handed nothing the program made but
the three results under judgement.

:func:`judge` holds the served ``U``, ``S``, ``V`` to what an SVD is, one
number each (``NUMBERS``): the singular values against the reference's
(``sv_rel``), the orthonormality of U, all of it (``u_orth``), and of V
(``v_orth``), the factorization itself (``recon_rel``), and the 8 leading
left singular vectors against the reference's (``lead_angle``: the trailing
292 of this data lie in a cluster of nearly equal values, where a vector is
not determined to rounding, and are held by ``u_orth`` and ``recon_rel``).
:func:`svd` in a given dtype is the reference a test uses (float32) and the
control put in the program's place (bfloat16).
"""

from __future__ import annotations

import functools

import numpy as np

#: rows a block: 65 536 rows of 300 columns are 78.6 MB in float32
BLOCK_ROWS = 1 << 16
#: leading pairs whose vectors are compared
LEADING = 8

NUMBERS = ("sv_rel", "u_orth", "v_orth", "recon_rel", "lead_angle")


def _blocks(rows: int, block: int):
    """``(first row, height)`` of each block; the last may be shorter."""
    return [(lo, min(block, rows - lo)) for lo in range(0, rows, block)]


def _rounded(x, dtype):
    """``x`` rounded to ``dtype`` and held as float32 (XLA's QR has no
    bfloat16 form: a factorization in bfloat16 runs on rounded data)."""
    import jax.numpy as jnp

    return x.astype(dtype).astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _block_r(height: int, dtype):
    """The jitted R of ``height`` rows of ``x`` from row ``lo``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block_r(x, lo):
        with jax.default_matmul_precision("highest"):
            rows = _rounded(jax.lax.dynamic_slice_in_dim(x, lo, height, axis=0), dtype)
            return jnp.linalg.qr(rows, mode="r")

    return block_r


def r_factor(x, dtype, block: int = BLOCK_ROWS):
    """R of ``x`` (float32, (columns, columns)) by blocked Householder TSQR:
    the blocks' R factors, stacked, factored once more."""
    import jax
    import jax.numpy as jnp

    rs = [_block_r(height, dtype)(x, lo) for lo, height in _blocks(int(x.shape[0]), block)]
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda s: jnp.linalg.qr(s, mode="r"))(jnp.concatenate(rs, axis=0))


def spectrum(x, dtype, block: int = BLOCK_ROWS):
    """``(S, V)`` of ``x`` as float64 host arrays: R's SVD in float64."""
    r = np.asarray(r_factor(x, dtype, block), dtype=np.float64)
    _, s, vt = np.linalg.svd(r)
    return s, vt.T


@functools.lru_cache(maxsize=None)
def _product(dtype):
    """``A·W`` in ``dtype``: operands rounded to it, one product at
    ``highest`` (float32) or one bfloat16 pass, float32 out."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def product(x, w):
        return jnp.matmul(x.astype(dtype), w.astype(dtype), precision="highest", preferred_element_type=jnp.float32)

    return product


def svd(x, dtype, block: int = BLOCK_ROWS) -> dict:
    """``{"U", "S", "V"}`` of ``x`` in ``dtype``, float32 arrays: U whole, as
    ``A·V·diag(S)⁻¹``."""
    import jax.numpy as jnp

    s, v = spectrum(x, dtype, block)
    u = _product(dtype)(x, jnp.asarray(v / s[None, :], jnp.float32))
    return {"U": u, "S": jnp.asarray(s, jnp.float32), "V": jnp.asarray(v, jnp.float32)}


@functools.lru_cache(maxsize=None)
def _block_sums(height: int, lead: int):
    """The jitted sums of one block of ``height`` rows from row ``lo``: Uᵀ U,
    ``|A·V - U·diag(S)|²``, ``|A|²``, and ``A·v_refⱼ``'s squares and products
    with ``uⱼ`` for the ``lead`` leading j (their norms and signs)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sums(x, u, s, v, v_lead, lo):
        with jax.default_matmul_precision("highest"):
            a = jax.lax.dynamic_slice_in_dim(x, lo, height, axis=0)
            ub = jax.lax.dynamic_slice_in_dim(u, lo, height, axis=0)
            resid = a @ v - ub * s[None, :]
            ref = a @ v_lead
            return (ub.T @ ub, jnp.sum(resid * resid), jnp.sum(a * a),
                    jnp.sum(ub[:, :lead] * ref, axis=0), jnp.sum(ref * ref, axis=0))

    return sums


@functools.lru_cache(maxsize=None)
def _block_gaps(height: int, lead: int):
    """The jitted ``|uⱼ/|uⱼ| - A·v_refⱼ/|A·v_refⱼ||²`` of one block, the
    second taken with the sign of their product: the distance of two unit
    vectors, summed from squares, so that the angle of two aligned vectors is
    read to rounding (``1 - |cos|`` from a product and two norms cancels to
    the rounding of the three sums, about 5e-6 on the chip)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps(x, u, v_lead, u_scale, ref_scale, lo):
        with jax.default_matmul_precision("highest"):
            a = jax.lax.dynamic_slice_in_dim(x, lo, height, axis=0)
            ub = jax.lax.dynamic_slice_in_dim(u, lo, height, axis=0)[:, :lead]
            d = ub * u_scale[None, :] - (a @ v_lead) * ref_scale[None, :]
            return jnp.sum(d * d, axis=0)

    return gaps


def judge(x, outputs: dict, seed: int, block: int = BLOCK_ROWS) -> dict:
    import jax.numpy as jnp

    u, s, v = outputs["U"], outputs["S"], outputs["V"]
    m, n = int(x.shape[0]), int(x.shape[1])
    if tuple(u.shape) != (m, n) or tuple(s.shape) != (n,) or tuple(v.shape) != (n, n):
        return dict.fromkeys(NUMBERS, float("inf"))
    s_ref, v_ref = spectrum(x, jnp.float32, block)
    lead = min(LEADING, n)
    v_lead = jnp.asarray(v_ref[:, :lead], jnp.float32)
    s32, v32 = jnp.asarray(s, jnp.float32), jnp.asarray(v, jnp.float32)
    blocks = _blocks(m, block)
    parts = [_block_sums(height, lead)(x, u, s32, v32, v_lead, lo) for lo, height in blocks]
    gram, resid, total, cross, ref_sq = (
        np.sum([np.asarray(p[i], dtype=np.float64) for p in parts], axis=0) for i in range(5)
    )
    # each pair as two unit vectors of the same sign
    u_scale = jnp.asarray(1.0 / np.sqrt(np.diag(gram)[:lead]), jnp.float32)
    ref_scale = jnp.asarray(np.where(cross < 0, -1.0, 1.0) / np.sqrt(ref_sq), jnp.float32)
    gaps = np.sum([np.asarray(_block_gaps(height, lead)(x, u, v_lead, u_scale, ref_scale, lo), dtype=np.float64)
                   for lo, height in blocks], axis=0)
    s64, v64 = np.asarray(s, dtype=np.float64), np.asarray(v, dtype=np.float64)
    return {
        "sv_rel": _number(np.max(np.abs(s64 - s_ref) / s_ref)),
        "u_orth": _number(np.max(np.abs(gram - np.eye(n)))),
        "v_orth": _number(np.max(np.abs(v64.T @ v64 - np.eye(n)))),
        "recon_rel": _number(np.sqrt(resid / total)),
        # 1 - |cos| of two unit vectors is half their squared distance
        "lead_angle": _number(np.max(gaps) / 2.0),
    }


def _number(value) -> float:
    """A float; a NaN (a result that holds one) is over every limit."""
    value = float(value)
    return float("inf") if value != value else value
