"""Plain reference for the tall SVD configurations whose rows are split over
several chips: the reduced SVD ``A = U·diag(S)·Vᵀ`` of a (rows, columns)
float32 array with many more rows than columns, worked a shard at a time.

The mathematics is ``svd_plain.py``'s.  R comes from a blocked Householder
TSQR: ``jax.numpy``'s QR (R only) of each block of a shard's rows, the
shard's stacked R factors factored once, then the shards' R factors stacked
and factored once more, in float32 with every product at ``highest``; R's
SVD is taken on the host in float64.  The columns of U the judge needs are
``uⱼ = A·vⱼ / sⱼ``, formed a block of rows at a time.  Every program runs on
one device over that device's own rows (``addressable_shards``): nothing is
gathered, so an array that fills four chips is judged where it lies.
Imports nothing of the program and is handed nothing the program made but
the three results under judgement.

:func:`judge` returns ``svd_plain``'s numbers (``NUMBERS``), its block sums
made per shard and summed on the host in float64.  :func:`svd` in a given
dtype is the reference a test uses (float32) and the control put in the
program's place (bfloat16): U split as A is.
"""

from __future__ import annotations

import functools

import numpy as np

#: rows a block: 32 768 rows of 1 200 columns are 157 MB in float32, so that a
#: block's temporaries fit beside A and U on a chip
BLOCK_ROWS = 1 << 15
#: leading pairs whose vectors are compared
LEADING = 8

NUMBERS = ("sv_rel", "u_orth", "v_orth", "recon_rel", "lead_angle")


def _blocks(rows: int, block: int):
    """``(first row, height)`` of each block; the last may be shorter."""
    return [(lo, min(block, rows - lo)) for lo in range(0, rows, block)]


def _shards(x):
    """``[(first row, the rows on one device)]`` of ``x``'s row shards, in
    order; a shard held by several devices is counted once."""
    found = {}
    for shard in x.addressable_shards:
        found.setdefault(shard.index[0].start or 0, shard.data)
    return sorted(found.items(), key=lambda p: p[0])


def _rows_of(u, device, lo: int, rows: int):
    """``u``'s rows ``[lo, lo + rows)`` on ``device``: the shard that holds
    them there, cut where it holds more; gathered only where ``u`` is laid
    out otherwise."""
    import jax

    for shard in getattr(u, "addressable_shards", ()):
        start = shard.index[0].start or 0
        if shard.device == device and start <= lo and lo + rows <= start + shard.data.shape[0]:
            data = shard.data
            return data if (start, data.shape[0]) == (lo, rows) else data[lo - start : lo - start + rows]
    return jax.device_put(u[lo : lo + rows], device)


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


@functools.lru_cache(maxsize=None)
def _stacked_r():
    """The jitted R of a stack of R factors."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stacked_r(stack):
        with jax.default_matmul_precision("highest"):
            return jnp.linalg.qr(stack, mode="r")

    return stacked_r


def r_factor(x, dtype, block: int = BLOCK_ROWS):
    """R of ``x`` (float32, (columns, columns)) by blocked Householder TSQR:
    each shard's blocks' R factors stacked and factored on the shard's
    device, then the shards' R factors, on the first shard's device."""
    import jax
    import jax.numpy as jnp

    shards = _shards(x)
    parts = []
    for _, rows in shards:
        rs = [_block_r(height, dtype)(rows, lo) for lo, height in _blocks(int(rows.shape[0]), block)]
        parts.append(_stacked_r()(jnp.concatenate(rs, axis=0)))
    first = shards[0][1].devices().pop()
    return _stacked_r()(jnp.concatenate([jax.device_put(p, first) for p in parts], axis=0))


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
    """``{"U", "S", "V"}`` of ``x`` in ``dtype``, float32 arrays: U as
    ``A·V·diag(S)⁻¹``, each device's rows made on that device, laid out as
    ``x``."""
    import jax
    import jax.numpy as jnp

    s, v = spectrum(x, dtype, block)
    w = np.asarray(v / s[None, :], np.float32)
    parts = [_product(dtype)(shard.data, jax.device_put(w, shard.device)) for shard in x.addressable_shards]
    u = jax.make_array_from_single_device_arrays(tuple(x.shape), x.sharding, parts)
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
    vectors, summed from squares (``svd_plain._block_gaps``)."""
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


def _over_blocks(x, u, block: int, call):
    """``call(height)(rows of x, rows of u, lo, device)`` for every block of
    every shard, each on the shard's device, summed on the host in float64
    (a tuple of sums where ``call`` returns one)."""
    parts = []
    for first, rows in _shards(x):
        device = rows.devices().pop()
        us = _rows_of(u, device, first, int(rows.shape[0]))
        parts += [call(height, rows, us, lo, device) for lo, height in _blocks(int(rows.shape[0]), block)]
    if isinstance(parts[0], tuple):
        return tuple(np.sum([np.asarray(p[i], dtype=np.float64) for p in parts], axis=0) for i in range(len(parts[0])))
    return np.sum([np.asarray(p, dtype=np.float64) for p in parts], axis=0)


def judge(x, outputs: dict, seed: int, block: int = BLOCK_ROWS) -> dict:
    import jax
    import jax.numpy as jnp

    u, s, v = outputs["U"], outputs["S"], outputs["V"]
    m, n = int(x.shape[0]), int(x.shape[1])
    if tuple(u.shape) != (m, n) or tuple(s.shape) != (n,) or tuple(v.shape) != (n, n):
        return dict.fromkeys(NUMBERS, float("inf"))
    s_ref, v_ref = spectrum(x, jnp.float32, block)
    lead = min(LEADING, n)
    host = {"s": np.asarray(s, np.float32), "v": np.asarray(v, np.float32),
            "v_lead": np.asarray(v_ref[:, :lead], np.float32)}
    on = functools.lru_cache(maxsize=None)(lambda device: {k: jax.device_put(a, device) for k, a in host.items()})

    def sums(height, rows, us, lo, device):
        d = on(device)
        return _block_sums(height, lead)(rows, us, d["s"], d["v"], d["v_lead"], lo)

    gram, resid, total, cross, ref_sq = _over_blocks(x, u, block, sums)
    # each pair as two unit vectors of the same sign
    host["u_scale"] = np.asarray(1.0 / np.sqrt(np.diag(gram)[:lead]), np.float32)
    host["ref_scale"] = np.asarray(np.where(cross < 0, -1.0, 1.0) / np.sqrt(ref_sq), np.float32)
    on.cache_clear()

    def gaps(height, rows, us, lo, device):
        d = on(device)
        return _block_gaps(height, lead)(rows, us, d["v_lead"], d["u_scale"], d["ref_scale"], lo)

    gap = _over_blocks(x, u, block, gaps)
    s64, v64 = host["s"].astype(np.float64), host["v"].astype(np.float64)
    return {
        "sv_rel": _number(np.max(np.abs(s64 - s_ref) / s_ref)),
        "u_orth": _number(np.max(np.abs(gram - np.eye(n)))),
        "v_orth": _number(np.max(np.abs(v64.T @ v64 - np.eye(n)))),
        "recon_rel": _number(np.sqrt(resid / total)),
        # 1 - |cos| of two unit vectors is half their squared distance
        "lead_angle": _number(np.max(gap) / 2.0),
    }


def _number(value) -> float:
    """A float; a NaN (a result that holds one) is over every limit."""
    value = float(value)
    return float("inf") if value != value else value
