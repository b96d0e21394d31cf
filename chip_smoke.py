#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that heat_tpu still starts, compiles and
answers correctly on the attached TPU.

    python chip_smoke.py              # one chip: every phase of the table below
    python chip_smoke.py --chips 4    # one four-chip host: the sharded path only

It drives the public surface only (``import heat_tpu as ht``, ``ht.serve``),
makes all data on the device from ``--seed`` with ``ht.random``, and checks
every phase against a plain numpy / float32 ``jax.numpy`` reference with the
tolerance written next to the check.  Each phase prints one JSON line: sizes,
any cut taken, each check's value against its limit, the dtypes of the arrays
live on the device, cold wall time, and how many of its compiled programs came
from the persistent cache.  The first failed check exits non-zero; nothing
catches a failure.  The last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Process layout: a chip belongs to one process.  This script's parent never
initialises a backend.  It runs the compute phases in one child (``--worker``),
which holds the chip and exits; then it runs the fleet phase itself, so the
replica process ``ProcFleet`` spawns is the only holder of the chip while it
serves.

What the run writes goes to fixed, git-ignored places in the checkout:
``.chip_smoke/`` (registry, HDF5 file, the requests the fleet replays),
``.jax_cache/`` (compiled programs, unless ``JAX_COMPILATION_CACHE_DIR`` says
otherwise) and ``chiprun_out/chip_smoke.jsonl`` (a copy of the phase lines).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")
LINES = os.path.join(ROOT, "chiprun_out", "chip_smoke.jsonl")

#: full sizes: the reference's own harness settings (BASELINE.md) scaled to
#: fill a useful part of one 16 GB chip
FULL = {
    "moments": dict(n=8_000_000, f=32, wide=(300, 1_048_576)),
    "kmeans": dict(k=8, iters=30, n_ref=1_000_000),
    "kmedians": dict(n=300, f=6_291_456, k=8, iters=3, sample=512),
    "cdist": dict(n=40_000, f=18, block=512),
    "spectral": dict(n=8_192, f=18, k=8, m=300),
    "lasso": dict(n=10_000_000, f=32, sweeps=10),
    "qr_svd": dict(m=4_194_304, n=64),
    "attention": dict(S=4096, H=16, D=64),
    "io": dict(n=1_000_000, f=32),
    "serve": dict(n_requests=16, max_rows=4096),
}

#: four-chip sizes.  The issue asked for 32 000 000 rows; the one-device twin
#: cannot fit them: KMeans._finalize gathers centers[labels] into an (n, 32)
#: f32 buffer that the TPU tiles to 128 lanes (4x), 15.26 GB at 32M rows, and
#: the chip's compiler refuses the program (RESOURCE_EXHAUSTED, 19.07 of
#: 15.75 GB).  16M rows is the largest power-of-two-times-1M that compiles.
FULL_SHARDED = dict(n=16_000_000, f=32, k=8, iters=30, mm=8192, S=16384, H=16, D=64)
SHARDED_CUT = (
    "32000000 -> 16000000 rows: the one-device twin's KMeans._finalize pads its "
    "centers[labels] gather 4x (15.26 GB at 32M rows); the compiler refuses it"
)


# --------------------------------------------------------------------- #
# reporting                                                              #
# --------------------------------------------------------------------- #
def check(value, limit, *, at_least: bool = False) -> dict:
    """One check of a phase line: ``value`` against ``limit`` (an upper bound
    unless ``at_least``).  A non-finite value never passes."""
    value = float(value)
    ok = bool(np.isfinite(value) and (value >= limit if at_least else value <= limit))
    return {"value": value, "limit": limit, "ok": ok}


@contextlib.contextmanager
def launch_spans(*sites):
    """The launch spans the program records at ``sites`` while the block runs
    (``heat_tpu.telemetry`` on for its length): the list fills as the block
    ends.  A phase reads off them which of its program's forms ran."""
    from heat_tpu import telemetry

    was_on = telemetry.is_enabled()
    telemetry.enable()
    first = len(telemetry.events())
    spans = []
    try:
        yield spans
        spans += [
            e for e in telemetry.events()[first:] if e["type"] == "span" and e["site"] in sites
        ]
    finally:
        if not was_on:
            telemetry.disable()


def device_dtypes() -> dict:
    """Bytes live on the device by dtype, so that a 64-bit array on the chip
    is seen rather than assumed away."""
    import jax

    out: dict = {}
    for a in jax.live_arrays():
        out[str(a.dtype)] = out.get(str(a.dtype), 0) + int(a.nbytes)
    return dict(sorted(out.items()))


def emit(line: dict) -> None:
    """Print one phase line, keep a copy, and stop the run if a check failed."""
    text = json.dumps(line)
    print(text, flush=True)
    os.makedirs(os.path.dirname(LINES), exist_ok=True)
    with open(LINES, "a") as fh:
        fh.write(text + "\n")
    failed = [k for k, c in line.get("checks", {}).items() if not c["ok"]]
    if failed:
        sys.exit(f"chip_smoke: phase {line['phase']!r} failed: {failed}")


def float64_chunks(host: np.ndarray, rows: int = 1_000_000):
    """``(start, float64 copy)`` of ``host`` a million rows at a time: the
    numpy references run at full size without a second copy of the data."""
    for lo in range(0, host.shape[0], rows):
        yield lo, host[lo:lo + rows].astype(np.float64)


def compile_counts() -> tuple:
    """Programs compiled or loaded so far and how many of them the persistent
    cache served, from the program's own start-up record (jax's monitoring
    events, ``heat_tpu/core/_compile.py``); ``requests - hits`` were compiled
    afresh."""
    from heat_tpu import telemetry

    loads = [r for r in telemetry.startup() if r["site"] == "compile:backend"]
    return len(loads), sum(1 for r in loads if r["cache_hit"])


def timed(name: str, fn, *args, **kwargs):
    """Run one phase function; complete its line with the cold wall time and
    the compile counts; emit it.  Returns what the phase passes on."""
    r0, h0 = compile_counts()
    t0 = time.perf_counter()
    line, passed_on = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    r1, h1 = compile_counts()
    emit({
        "phase": name, **line,
        "cold_wall_s": round(wall, 3),
        "compiles": {"requests": r1 - r0, "from_cache": h1 - h0},
    })
    return passed_on


# --------------------------------------------------------------------- #
# one-chip phases                                                        #
# --------------------------------------------------------------------- #
def phase_moments(seed: int, n: int, f: int, wide):
    """Moments of the tall ``n x f`` array every later phase uses, and the
    deviation of a ``wide`` one (rows, columns): the operand whose variance one
    chip makes from one read (``core/_colvar.py``); the line says which form
    each took."""
    import heat_tpu as ht
    from heat_tpu.core import _colvar

    ht.random.seed(seed)
    X = ht.random.randn(n, f, split=0)
    with launch_spans("jitted:stat.moment2") as tall_spans:
        got = {
            "mean0": ht.mean(X, axis=0).numpy(), "std0": ht.std(X, axis=0).numpy(),
            "var0": ht.var(X, axis=0).numpy(),
            "mean": float(ht.mean(X)), "std": float(ht.std(X)), "var": float(ht.var(X)),
        }
    host = X.numpy()
    s1 = np.zeros(f)
    s2 = np.zeros(f)
    for _, c in float64_chunks(host):
        s1 += c.sum(0)
        s2 += (c * c).sum(0)
    mean0 = s1 / n
    var0 = s2 / n - mean0 * mean0
    mean = s1.sum() / (n * f)
    var = s2.sum() / (n * f) - mean * mean
    # a far mean on the wide operand: 100 deviations, where the raw form in
    # float32 has lost four of its seven digits
    W = ht.random.randn(*wide, split=0) + 100.0
    with launch_spans("jitted:stat.moment2") as wide_spans:
        wide_std, wide_var1 = ht.std(W, axis=0).numpy(), ht.var(W, axis=0, ddof=1).numpy()
    wide64 = W.numpy().astype(np.float64)
    del W
    # f32 sums of n standard-normal values against float64: 1e-4 absolute on a
    # mean (whose own size is ~1/sqrt(n)), 1e-4 relative on spread; the wide
    # operand's few hundred rows by the limit of tests/test_moments_reference.py
    checks = {
        "mean_axis0_abs": check(np.abs(got["mean0"] - mean0).max(), 1e-4),
        "var_axis0_rel": check(np.abs(got["var0"] / var0 - 1).max(), 1e-4),
        "std_axis0_rel": check(np.abs(got["std0"] / np.sqrt(var0) - 1).max(), 1e-4),
        "mean_abs": check(abs(got["mean"] - mean), 1e-4),
        "var_rel": check(abs(got["var"] / var - 1), 1e-4),
        "std_rel": check(abs(got["std"] / np.sqrt(var) - 1), 1e-4),
        "wide_std_axis0_rel": check(np.abs(wide_std / wide64.std(0) - 1).max(), 2e-5),
        "wide_var_ddof1_axis0_rel": check(np.abs(wide_var1 / wide64.var(0, ddof=1) - 1).max(), 4e-5),
    }
    line = {
        "sizes": {"rows": n, "features": f, "bytes": n * f * 4, "wide": list(wide), "wide_bytes": wide[0] * wide[1] * 4},
        "variance_form": {"tall": sorted({e["form"] for e in tall_spans}), "wide": sorted({e["form"] for e in wide_spans}),
                          "kernel_from_bytes": _colvar.MIN_BYTES, "kernel_from_columns": _colvar.MIN_TILE},
        "reference": "numpy float64 on the same data, full size",
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, X


def _numpy_assign(host: np.ndarray, c: np.ndarray):
    """float64 nearest centre of every row of ``host``, a chunk at a time:
    labels, per-cluster sums and counts, inertia."""
    k = c.shape[0]
    labels = np.empty(len(host), np.int64)
    sums = np.zeros_like(c)
    counts = np.zeros(k)
    inertia = 0.0
    for lo, x in float64_chunks(host):
        d2 = (x * x).sum(1)[:, None] + (c * c).sum(1)[None, :] - 2.0 * (x @ c.T)
        own = labels[lo:lo + len(x)] = d2.argmin(1)
        selected = np.eye(k)[own]
        sums += selected.T @ x
        counts += selected.sum(0)
        inertia += float(np.take_along_axis(d2, own[:, None], 1).sum())
    return labels, sums, counts, inertia


def _numpy_lloyd(host: np.ndarray, centers: np.ndarray, iters: int):
    """A plain float64 Lloyd loop: the same update rule as the estimator
    (empty clusters keep their centre), no early exit."""
    c = centers.astype(np.float64)
    for _ in range(iters):
        _, sums, counts, _ = _numpy_assign(host, c)
        c = np.where(counts[:, None] > 0, sums / np.maximum(counts, 1)[:, None], c)
    labels, _, _, inertia = _numpy_assign(host, c)
    return c, labels, inertia


def phase_kmeans(X, seed: int, k: int, iters: int, n_ref: int):
    import heat_tpu as ht

    n, f = X.shape
    ht.random.seed(seed + 1)
    init = ht.random.randn(k, f)

    def fit(data, max_iter, start=init):
        # tol=-1.0: the shift is never <= tol, so exactly `max_iter` iterations
        return ht.cluster.KMeans(
            k, init=start, max_iter=max_iter, tol=-1.0, random_state=seed).fit(data)

    host = X.numpy()
    # The tight check is one Lloyd step at full size against numpy float64
    # from the same centres.  The estimator's distance matmul runs at the
    # TPU's default precision (one bf16 pass), so a row within ~0.1 % of a
    # boundary may take the other centre: with bf16-rounded operands numpy
    # itself agrees with float64 on 99.78 % of labels and to 1.8e-3 on
    # centres, hence 99.5 % and 1e-2.  Inertia is flat at a boundary: 1e-4.
    step = fit(X, 1)
    ref_c, ref_labels, ref_inertia = _numpy_lloyd(host, init.numpy(), 1)
    # All iterations: 30 steps on data without clusters amplify one rounding,
    # so centres and labels are not held to numpy's.  What is held: the count
    # of iterations, the last assignment (numpy's nearest centre from the
    # chip's own centres), and an inertia that matches numpy's for those
    # centres, has not risen since the first step, and ends where an
    # independent float64 loop on the first n_ref rows ends (the host takes
    # minutes for the whole loop at full size).
    with launch_spans("jit:kmeans.fit_segment") as spans:
        km = fit(X, iters)
    own_labels, _, _, own_inertia = _numpy_assign(
        host, km.cluster_centers_.numpy().astype(np.float64))
    n_ref = min(n_ref, n)
    km_sub = fit(X[:n_ref], iters)
    _, _, sub_inertia = _numpy_lloyd(host[:n_ref], init.numpy(), iters)
    # the second fit, the reference harness's init: finite and no worse than
    # where k-means++ started is all a seeded draw can be held to
    km_pp = fit(X, iters, "probability_based")
    start_pp = fit(X, 0, "probability_based")
    # fit() ranks |c|^2 - 2x.c and predict() sqrt(|x|^2 + |c|^2 - 2x.c): the
    # same argmin, but |x|^2 ~ 32 coarsens the f32 grid 30x, so rows tied
    # within one rounding (6e-7 of them on the chip, none on the CPU) may
    # differ: 1e-5
    predicted = km.predict(X).numpy().reshape(-1)
    checks = {
        "one_step_centres_abs": check(
            np.abs(step.cluster_centers_.numpy() - ref_c).max(), 1e-2),
        "one_step_labels_agree": check(
            (step.labels_.numpy() == ref_labels).mean(), 0.995, at_least=True),
        "one_step_inertia_rel": check(abs(step.inertia_ / ref_inertia - 1), 1e-4),
        "iterations": check(abs(km.n_iter_ - iters), 0),
        "labels_vs_numpy_argmin_agree": check(
            (km.labels_.numpy() == own_labels).mean(), 0.995, at_least=True),
        "inertia_vs_numpy_rel": check(abs(km.inertia_ / own_inertia - 1), 1e-5),
        "inertia_vs_one_step": check(km.inertia_ / step.inertia_, 1.0),
        "subsample_inertia_vs_numpy_loop_rel": check(
            abs(km_sub.inertia_ / sub_inertia - 1), 1e-3),
        "labels_vs_predict_mismatch": check(
            (km.labels_.numpy() != predicted).mean(), 1e-5),
        "kmeanspp_inertia_vs_start": check(
            km_pp.inertia_ / start_pp.inertia_, 1.0 + 1e-6),
    }
    line = {
        "sizes": {"rows": n, "features": f, "clusters": k, "iterations": iters},
        "reference": f"numpy float64 at full size: one Lloyd step from the same "
                     f"centres, and the nearest centre and inertia from the chip's "
                     f"final centres; the whole loop in numpy on the first {n_ref} "
                     f"rows, for its inertia (subsample: the host cannot run "
                     f"{iters} iterations at full size in a minute)",
        # the layout of the sweep operand the fit's segment took
        # (``cluster/kmeans.py:_feature_layout``): one chip keeps its rows
        "routes": {"layout": [e["layout"] for e in spans]},
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, km


def phase_kmedians(seed: int, n: int, f: int, k: int, iters: int, sample: int):
    """``ht.cluster.KMedians`` at the shape of the benchmark's cell
    (``kmedians_300_c1``: 300 x 6 291 456 float32, 7.55 GB beside which a
    sorted copy does not fit): ``k`` blobs far apart, row ``i`` in blob
    ``i % k``, the fit started from one row of each, so that every sweep's
    partition is the blobs' and the served centres must be numpy's medians of
    each blob's rows, bit for bit, on ``sample`` columns read back.  The line
    names the routes the program took: the medians' (``column_select`` on one
    chip, ``rank_bisection`` on the CPU mesh), how many of the fit's
    selections the kernel made by its comparison network (every sweep's ``k``
    on the chip: a blob's rows are fewer than ``network_max``) and the L1
    sum's loop order."""
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    rows = max(b for b in range(1, n + 1) if n % b == 0 and b * f * 4 <= 1 << 28)

    @jax.jit
    def blobs(key):  # a block of rows at a time: the bits of the whole array would be its size again
        centres = 10.0 * jax.random.normal(jax.random.fold_in(key, n), (k, f), jnp.float32)

        def block(i, out):
            index = i * rows + jnp.arange(rows)
            noise = jax.vmap(lambda r: jax.random.normal(jax.random.fold_in(key, r), (f,), jnp.float32))(index)
            return jax.lax.dynamic_update_slice(out, centres[index % k] + noise, (i * rows, 0))

        return jax.lax.fori_loop(0, n // rows, block, jnp.zeros((n, f), jnp.float32))

    X = ht.array(blobs(jax.random.key(seed)), split=0, copy=False)
    start = ht.array(X.larray[:k])  # row c lies in blob c
    with launch_spans("jit:kmedians.fit", "jitted:dist.manhattan") as spans:
        km = ht.cluster.KMedians(k, init=start, max_iter=iters, tol=-1.0).fit(X)
        n_iter = km.n_iter_
        by_network = km.selections_by_network_
        to_centres = ht.spatial.manhattan(X, km.cluster_centers_).numpy()
    (fit_span,) = [e for e in spans if e["site"] == "jit:kmedians.fit"]
    (sum_span,) = [e for e in spans if e["site"] == "jitted:dist.manhattan"]
    labels = km.labels_.numpy()
    cols = np.sort(np.random.default_rng(seed).choice(f, size=min(sample, f), replace=False))
    host = np.concatenate(
        [np.asarray(jax.lax.dynamic_slice_in_dim(X.larray, int(c), 1, axis=1)) for c in cols], axis=1)
    served = np.asarray(km.cluster_centers_.larray[:, jnp.asarray(cols)])
    blob = np.arange(n) % k
    want = np.stack([np.median(host[blob == c], axis=0) for c in range(k)])
    checks = {
        "iterations": check(abs(n_iter - iters), 0),
        "labels_vs_blobs_mismatch": check((labels != blob).mean(), 0),
        "labels_vs_manhattan_argmin_mismatch": check((labels != to_centres.argmin(1)).mean(), 0),
        "medians_vs_numpy_on_sample_abs": check(np.abs(served - want).max(), 0),
    }
    stats = jax.devices()[0].memory_stats() or {}
    line = {
        "sizes": {"rows": n, "features": f, "bytes": n * f * 4, "clusters": k, "sweeps": iters, "sample_columns": len(cols)},
        "routes": {"medians": fit_span["medians"], "assign": fit_span["assign"],
                   "x_passes": fit_span.get("x_passes"), "network_max": fit_span.get("network_max"),
                   "selections_by_network": by_network, "manhattan_form": sum_span["form"]},
        "reference": "numpy median of each blob's rows on the sampled columns; the blobs' own partition",
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, None


def phase_cdist(seed: int, n: int, f: int, block: int):
    import heat_tpu as ht

    ht.random.seed(seed + 2)
    X = ht.random.randn(n, f, split=0)
    with launch_spans("jitted:dist.euclidean") as spans:
        D = ht.spatial.cdist(X, X)
    block = min(block, n)
    lo = int(np.random.default_rng(seed).integers(0, n - block + 1))
    got = D[lo:lo + block].numpy()
    host = X.numpy().astype(np.float64)
    rows = host[lo:lo + block]
    d2 = (rows * rows).sum(1)[:, None] + (host * host).sum(1)[None, :] - 2.0 * rows @ host.T
    ref = np.sqrt(np.maximum(d2, 0.0))
    # the default cdist is the exact form in f32, at 18 features unrolled over
    # them (one pass): 1e-4 absolute on distances of size ~6 (a row to itself
    # is exactly 0 on the chip)
    checks = {
        "block_abs": check(np.abs(got - ref).max(), 1e-4),
        "diagonal_abs": check(np.abs(np.diagonal(got, lo)).max(), 0.0),
    }
    line = {
        "sizes": {"rows": n, "features": f, "result_bytes": n * n * 4},
        "exact_form": sorted({e["form"] for e in spans}),
        "reference": f"numpy float64 on rows [{lo}, {lo + block}) of the result "
                     f"(seeded block: the full result is {n * n * 4 / 1e9:.1f} GB)",
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, None


def _spectral_limits() -> dict:
    """The limits of the benchmark's cell ``spectral_40k_c1`` (PERF.md
    section 2), which the smoke holds a smaller fit to."""
    with open(os.path.join(ROOT, "perf", "workloads", "spectral_40k_c1.json")) as fh:
        return json.load(fh)["limits"]


def phase_spectral(seed: int, n: int, f: int, k: int, m: int):
    """``ht.cluster.Spectral`` at a reduced n (the benchmark's cell holds the
    full 40 000 rows) against the benchmark's plain reference, by the cell's
    own five numbers and limits.  The line says which route Lanczos' matvec
    took, read off the fit's own launch spans: at 8 192 rows on one chip the
    symmetric-half kernel (``_symv.MIN_N``), on the CPU mesh the dense product."""
    import importlib.util

    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.core.linalg import _symv

    spec = importlib.util.spec_from_file_location(
        "spectral_plain", os.path.join(ROOT, "perf", "references", "spectral_plain.py")
    )
    plain = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(plain)

    rng = np.random.default_rng(seed + 9)
    centres = 0.35 * rng.standard_normal((k, f))
    host = (centres[np.arange(n) % k] + 0.15 * rng.standard_normal((n, f))).astype(np.float32)
    X = ht.array(host, split=0)
    with launch_spans("jit:lanczos.start", "jit:lanczos.segment") as spans:
        sp = ht.cluster.Spectral(n_clusters=k, gamma=1.0, n_lanczos=m).fit(X)
    routes = sorted({e["matvec"] for e in spans})
    out = {"labels": sp.labels_.larray, "embedding": sp.embedding_.larray, "eigenvalues": sp.eigenvalues_}
    numbers = plain.judge(jnp.asarray(host), out, k, 1.0, m)
    plain.forget()
    limits = _spectral_limits()
    line = {
        "sizes": {"rows": n, "features": f, "clusters": k, "n_lanczos": m, "laplacian_bytes": n * n * 4},
        "lanczos_matvec": {"route": routes, "kernel_from_rows": _symv.MIN_N,
                           "rows_under_the_kernels_threshold": n < _symv.MIN_N},
        "reference": "perf/references/spectral_plain.py (jax.numpy float32 at highest, exact-form "
                     "similarity, Python Lanczos loop), by the five numbers and limits of spectral_40k_c1",
        "checks": {name: check(numbers[name], limits[name]) for name in sorted(limits)},
        "device_dtypes": device_dtypes(),
    }
    return line, None


def _numpy_lasso_cd(gram: np.ndarray, xty: np.ndarray, lam: float, sweeps: int):
    """Cyclic coordinate descent in its covariance form, float64: with
    G = A^T A / n and b = A^T y / n the estimator's per-coordinate rule
    rho_j = mean(a_j * (resid + a_j theta_j)) is b_j - sum_{k != j} G_jk theta_k.
    Same sweeps, same order, intercept (j = 0) unpenalised."""
    theta = np.zeros(gram.shape[0])
    for _ in range(sweeps):
        for j in range(gram.shape[0]):
            rho = xty[j] - gram[j] @ theta + gram[j, j] * theta[j]
            if j == 0:
                theta[j] = rho / gram[j, j]
            else:
                theta[j] = np.sign(rho) * max(abs(rho) - lam, 0.0) / gram[j, j]
    return theta


def phase_lasso(seed: int, n: int, f: int, sweeps: int, lam: float = 0.1):
    import heat_tpu as ht

    ht.random.seed(seed + 3)
    X = ht.random.randn(n, f, split=0)
    w = ht.random.randn(f, 1)
    y = ht.matmul(X, w) + 0.5 + 0.1 * ht.random.randn(n, 1, split=0)
    est = ht.regression.Lasso(lam=lam, max_iter=sweeps, tol=-1.0).fit(X, y)
    theta = est.theta.numpy().reshape(-1)
    hx = X.numpy()
    hy = y.numpy().reshape(-1).astype(np.float64)
    gram = np.zeros((f + 1, f + 1))
    xty = np.zeros(f + 1)
    for lo, c in float64_chunks(hx):
        a = np.concatenate([np.ones((len(c), 1)), c], 1)
        gram += a.T @ a
        xty += a.T @ hy[lo:lo + len(c)]
    ref = _numpy_lasso_cd(gram / n, xty / n, lam, sweeps)
    # held to 1e-2 of the largest coefficient in case the residual's matvec
    # ran as one bf16 pass (~2e-3); on the chip it does not (1.6e-7 measured)
    checks = {
        "sweeps": check(abs(est.n_iter - sweeps), 0),
        "theta_rel_to_max": check(np.abs(theta - ref).max() / np.abs(ref).max(), 1e-2),
    }
    line = {
        "sizes": {"rows": n, "features": f, "sweeps": sweeps, "lam": lam},
        "reference": "numpy float64 coordinate descent (covariance form), same "
                     "sweeps, on the full data",
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, None


def _orthogonality_limit(m: int, n: int) -> float:
    """|Q^T Q - I|_F of an f32 Householder Q of m rows: n * sqrt(m) * 2^-24."""
    return n * float(np.sqrt(m)) * 2.0 ** -24


def phase_qr_svd(seed: int, m: int, n: int):
    import heat_tpu as ht

    ht.random.seed(seed + 4)
    A = ht.random.randn(m, n, split=0)
    norm_a = float(ht.linalg.norm(A))
    with launch_spans("jitted:linalg.qr", "jitted:linalg.svd") as spans:
        q, r = ht.linalg.qr(A)
        u, s, v = ht.linalg.svd(A)
    qr_res = float(ht.linalg.norm(ht.matmul(q, r) - A)) / norm_a
    ortho = float(ht.linalg.norm(ht.matmul(q.T, q) - ht.eye(n)))
    svd_res = float(ht.linalg.norm(ht.matmul(u * s, v.T) - A)) / norm_a
    s_ref = np.linalg.svd(r.numpy().astype(np.float64), compute_uv=False)
    # one chip takes the blocked CholeskyQR2 route (qr.tall_route: Q = A R^-1
    # and U = A R^-1 U_R, each one more pass over A, the products at
    # "highest"; orthonormal to about u * kappa(A), and a normal A's kappa is
    # near 1), elsewhere XLA's Householder QR of the whole operand: 1e-4
    # relative on what is reconstructed either way.  Q's columns are norms and
    # dots over m f32 terms, so |Q^T Q - I|_F is held to n * sqrt(m) * u,
    # u = 2^-24 (7.8e-3 at full size; 1.26e-3 read on the Householder route).
    checks = {
        "qr_residual_rel": check(qr_res, 1e-4),
        "q_orthogonality": check(ortho, _orthogonality_limit(m, n)),
        "svd_residual_rel": check(svd_res, 1e-4),
        "singular_values_rel": check(np.abs(s.numpy() / s_ref - 1).max(), 1e-4),
    }
    if A.comm.size > 1:
        checks["sharded_route"] = _sharded_route(A, spans)
    line = {
        "sizes": {"rows": m, "cols": n, "bytes": m * n * 4},
        "routes": {e["site"].split(":", 1)[1]: {"route": e["route"], "a_passes": e["a_passes"],
                                                 "col_blocks": e.get("col_blocks")} for e in spans},
        "reference": "residuals on the device; singular values against numpy "
                     "float64 on the R factor",
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, None


def _sharded_route(A, spans) -> dict:
    """Over several chips a row-split operand takes the row-sharded
    CholeskyQR2 where ``qr.rows_route`` admits it (float32, every shard at
    least n rows and 4 MB, a TPU: one ``cholqr2_rows`` program a call),
    else the TSQR chain, whose fused programs state no route: 1 where the
    spans say the route the operand should have taken."""
    import importlib

    qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
    want = "cholqr2_rows" if qr_mod.rows_route(A.shape, A.larray.dtype, A.split, A.comm) else None
    took = {e.get("route") for e in spans} or {None}
    return check(took == {want}, 1, at_least=True)


def _reference_attention(q, k, v, causal: bool):
    """Plain float32 softmax attention on (S, H, D), every matmul at
    precision "highest" so the reference itself is not a bf16 pass."""
    import jax
    import jax.numpy as jnp

    q, k, v = (jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (q, k, v))
    # the scale pinned to f32: numpy's float64 scalar would promote the scores
    scale = jnp.float32(1.0 / np.sqrt(q.shape[-1]))
    scores = jnp.einsum("hqd,hkd->hqk", q, k, precision="highest") * scale
    if causal:
        keep = jnp.arange(q.shape[1])[:, None] >= jnp.arange(k.shape[1])[None, :]
        scores = jnp.where(keep[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v, precision="highest")
    return jnp.moveaxis(out, 0, 1)


def phase_attention(seed: int, S: int, H: int, D: int):
    import jax.numpy as jnp

    import heat_tpu as ht

    ht.random.seed(seed + 5)
    q, k, v = (ht.random.randn(S, H, D, dtype=ht.bfloat16).larray for _ in range(3))
    checks = {}
    for causal in (False, True):
        name = "causal" if causal else "full"
        out = ht.parallel.flash_attention(q, k, v, causal=causal)
        ref = _reference_attention(q, k, v, causal)
        err = jnp.abs(out.astype(jnp.float32) - ref)
        # bf16 held to: inputs, the probabilities fed to the PV matmul and the
        # output are bf16 (2^-9 relative each) on values of size <= ~1
        checks[f"{name}_max_abs"] = check(err.max(), 2e-2)
        checks[f"{name}_mean_abs"] = check(err.mean(), 2e-3)
        # evidence, not hope: the program lowered for these operands must
        # carry the Pallas kernel, else the XLA fallback is what ran
        text = ht.parallel.flash_attention.lower(q, k, v, causal=causal).as_text()
        checks[f"{name}_pallas_kernel_in_program"] = check(
            "tpu_custom_call" in text, 1, at_least=True)
    line = {
        "sizes": {"S": S, "H": H, "D": D, "dtype": "bfloat16"},
        "reference": "float32 jax.numpy softmax attention at precision highest",
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, None


def phase_io(seed: int, n: int, f: int, work: str = WORK):
    import heat_tpu as ht
    from heat_tpu import native

    os.makedirs(work, exist_ok=True)
    path = os.path.join(work, "roundtrip.h5")
    ht.random.seed(seed + 6)
    A = ht.random.randn(n, f, split=0)
    ht.save_hdf5(A, path, "data")
    B = ht.load_hdf5(path, "data", split=0)
    file_bytes = os.path.getsize(path)
    os.remove(path)
    checks = {
        "bit_equal": check(np.array_equal(A.numpy(), B.numpy()), 1, at_least=True),
        "split_kept": check(B.split == 0 and B.shape == A.shape, 1, at_least=True),
    }
    line = {
        "sizes": {"rows": n, "features": f, "file_bytes": file_bytes},
        "reference": "bit equality of the round trip",
        "native_csv_scanner_loaded": bool(native.fastcsv_available()),
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, None


def _serialize_probe():
    """Why AOT export gave no bundle: ask the installed jaxlib to serialise
    one trivial executable of this backend and return its refusal."""
    import jax
    from jax.experimental import serialize_executable

    compiled = jax.jit(lambda x: x + 1).lower(jax.ShapeDtypeStruct((8,), "float32")).compile()
    try:
        serialize_executable.serialize(compiled)
    except Exception as e:  # reported on the line; the fleet phase asserts the fresh compile
        return f"{type(e).__name__}: {e}"
    return None


def phase_serve(km, seed: int, n_requests: int, max_rows: int, work: str = WORK):
    import heat_tpu as ht

    root = os.path.join(work, "registry")
    shutil.rmtree(root, ignore_errors=True)  # a rerun must publish version 1 again
    os.makedirs(root)
    f = km.cluster_centers_.shape[1]
    rng = np.random.default_rng(seed)
    rows = [1, max_rows] + [int(r) for r in rng.integers(1, max_rows + 1, n_requests - 2)]
    ht.random.seed(seed + 7)
    pool = ht.random.randn(sum(rows), f).numpy()
    requests = np.split(pool, np.cumsum(rows)[:-1])

    registry = ht.serve.ModelRegistry(root)
    version = registry.publish("smoke", "kmeans", km)
    engine = ht.serve.ServeEngine(registry, max_batch_rows=max_rows, min_bucket=8)
    try:
        bundles = engine.export_warm("smoke", "kmeans", version=version)
        aot_error = None
        if bundles:
            registry.publish_executables("smoke", "kmeans", version, bundles)
        else:
            aot_error = _serialize_probe() or "export_warm returned no bundle"
        replies = [
            np.asarray(engine.predict("smoke", "kmeans", x, version=version,
                                      request_id=f"smoke-{i}").value)
            for i, x in enumerate(requests)
        ]
    finally:
        engine.close()
    wrong = sum(
        int(np.any(rep.reshape(-1) != km.predict(ht.array(x)).numpy().reshape(-1)))
        for x, rep in zip(requests, replies)
    )
    np.savez(
        os.path.join(work, "serve_requests.npz"),
        **{f"x{i}": x for i, x in enumerate(requests)},
        **{f"y{i}": y for i, y in enumerate(replies)},
    )
    with open(os.path.join(work, "serve.json"), "w") as fh:
        json.dump({"version": version, "n_requests": n_requests, "max_rows": max_rows,
                   "bundles": len(bundles), "aot_error": aot_error}, fh)
    checks = {"replies_differing_from_km_predict": check(wrong, 0)}
    line = {
        "sizes": {"requests": n_requests, "rows": rows, "features": f},
        "reference": "km.predict on the same rows, label for label",
        "aot": {"bundles": len(bundles)} if bundles else "unsupported",
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    if aot_error:
        line["aot_error"] = aot_error
    return line, None


def phase_fleet(work: str = WORK):
    """One replica process, warm-started from the registry's sidecar, behind
    the loopback ingress.  Touches no backend: the replica holds the chip."""
    import heat_tpu as ht

    with open(os.path.join(work, "serve.json")) as fh:
        meta = json.load(fh)
    with np.load(os.path.join(work, "serve_requests.npz")) as z:
        requests = [z[f"x{i}"] for i in range(meta["n_requests"])]
        expected = [z[f"y{i}"] for i in range(meta["n_requests"])]
    fleet = ht.serve.ProcFleet(
        os.path.join(work, "registry"), n_replicas=1,
        warm_models=[("smoke", "kmeans", meta["version"])],
        max_batch_rows=meta["max_rows"], min_bucket=8, spawn_timeout_s=600.0,
    )
    try:
        (replica,) = fleet.alive()
        hello = {k: replica.hello[k] for k in
                 ("installed", "warmups", "fuse_misses", "compile_misses")}
        before = fleet.replica_stats()[0]["counters"]
        with ht.serve.Ingress(fleet) as ingress, \
                ht.serve.IngressClient("127.0.0.1", ingress.port) as client:
            replies = [
                client.predict("smoke", "kmeans", x, version=meta["version"],
                               request_id=f"smoke-{i}")
                for i, x in enumerate(requests)
            ]
        after = fleet.replica_stats()[0]["counters"]
    finally:
        fleet.close()
    traced = {
        k: int(after.get(k, 0)) - int(before.get(k, 0))
        for k in ("fuse.cache.misses", "compile.cache.misses")
    }
    wrong = sum(
        int(not np.array_equal(np.asarray(r["value"]), y))
        for r, y in zip(replies, expected)
    )
    checks = {
        "replies_differing_from_in_process": check(wrong, 0),
        "trace_ids_lost": check(
            sum(r["trace_id"] != f"smoke-{i}" for i, r in enumerate(replies)), 0),
    }
    if meta["bundles"]:
        # the warm start, from the hello frame's own counts
        checks["bundles_installed"] = check(hello["installed"], 1, at_least=True)
        checks["first_request_fuse_misses"] = check(hello["fuse_misses"], 0)
        checks["first_request_compile_misses"] = check(hello["compile_misses"], 0)
        checks["programs_traced_by_requests"] = check(sum(traced.values()), 0)
        aot = {"bundles": meta["bundles"]}
    else:
        # jaxlib cannot serialise this backend's executables: the replica must
        # then have compiled afresh, and is asserted to have
        checks["fresh_compile_on_first_request"] = check(
            hello["fuse_misses"] + hello["compile_misses"], 1, at_least=True)
        aot = "unsupported"
    line = {
        "sizes": {"replicas": 1, "requests": len(requests)},
        "reference": "the in-process ServeEngine's replies, byte for byte",
        "aot": aot, "hello": hello, "traced_by_requests": traced,
        "checks": checks, "device_dtypes": "held by the replica process",
    }
    if meta["aot_error"]:
        line["aot_error"] = meta["aot_error"]
    return line, None


# --------------------------------------------------------------------- #
# the sharded path (--chips 4)                                           #
# --------------------------------------------------------------------- #
def _spread(x, comm) -> dict:
    """Every array of the sharded path must have its shards on all of the
    communicator's devices, each a different one."""
    devices = {s.device for s in x.larray.addressable_shards}
    return check(len(devices), comm.size, at_least=True)


def _twins(comm, make):
    """The same seeded arrays on ``comm`` and on a one-device communicator."""
    import heat_tpu as ht

    one = ht.XlaCommunication(comm.devices[:1])
    return make(comm), make(one)


def sharded_moments(seed: int, comm, n: int, f: int):
    import heat_tpu as ht

    def make(c):
        ht.random.seed(seed)
        return ht.random.randn(n, f, split=0, comm=c)

    X, X1 = _twins(comm, make)
    # two f32 reductions of the same data in different orders: 1e-5
    checks = {"shards_on_every_device": _spread(X, comm)}
    for name, op in (("mean", ht.mean), ("std", ht.std), ("var", ht.var)):
        got, ref = op(X, axis=0).numpy(), op(X1, axis=0).numpy()
        checks[f"{name}_axis0_abs_vs_one_device"] = check(np.abs(got - ref).max(), 1e-5)
    line = {
        "sizes": {"rows": n, "features": f, "bytes": n * f * 4, "devices": comm.size},
        "reference": "the same calls on a one-device communicator, same seeded data",
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, (X, X1)


def sharded_kmeans(XX, seed: int, comm, k: int, iters: int):
    import heat_tpu as ht

    X, X1 = XX

    def fit_both(max_iter):
        fits = []
        for data in (X, X1):
            ht.random.seed(seed + 1)
            init = ht.random.randn(k, X.shape[1], comm=data.comm)
            fits.append(
                ht.cluster.KMeans(k, init=init, max_iter=max_iter, tol=-1.0).fit(data))
        return fits

    def centres_abs(a, b):
        return np.abs(a.cluster_centers_.numpy() - b.cluster_centers_.numpy()).max()

    def labels_agree(a, b):
        return (a.labels_.numpy() == b.labels_.numpy()).mean()

    step, step1 = fit_both(1)
    with launch_spans("jit:kmeans.fit_segment") as spans:
        km, km1 = fit_both(iters)
    # One Lloyd step from the same centres is the same arithmetic with the
    # (k, f) partial sums combined across devices in another order.  An f32
    # sum of n/k rows carries about 2^-24 * sqrt(n/k) of its mean (8e-5 at 16M
    # rows; 8.1e-6 measured): 1e-4 on centres, 99.999% of labels.  All
    # iterations amplify that one rounding on data without clusters (4.3e-3
    # on centres, 99.68% of labels, measured on four chips), so they are held
    # to the inertia alone: the twins' agree, and it has not risen since the
    # first step.
    checks = {
        "labels_on_every_device": _spread(km.labels_, comm),
        "one_step_centres_abs_vs_one_device": check(centres_abs(step, step1), 1e-4),
        "one_step_labels_agree_vs_one_device": check(
            labels_agree(step, step1), 0.99999, at_least=True),
        "iterations": check(abs(km.n_iter_ - iters), 0),
        "inertia_rel_vs_one_device": check(abs(km.inertia_ / km1.inertia_ - 1), 1e-4),
        "inertia_vs_one_step": check(km.inertia_ / step.inertia_, 1.0),
    }
    line = {
        "sizes": {"rows": X.shape[0], "clusters": k, "iterations": iters},
        "reference": "the same fit on a one-device communicator, after one "
                     "iteration and after all",
        # the sweep operand's layout, the sharded fit's then its twin's: tall,
        # narrow rows keep the row layout (``cluster/kmeans.py:_feature_layout``)
        "routes": {"layout": [e["layout"] for e in spans]},
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, None


def sharded_resplit(XX, seed: int, comm, block: int = 4096):
    import heat_tpu as ht

    X, X1 = XX
    Y = ht.resplit(X, 1)
    lo = int(np.random.default_rng(seed).integers(0, X.shape[0] - block + 1))
    checks = {
        "shards_on_every_device": _spread(Y, comm),
        "split_is_1": check(Y.split == 1, 1, at_least=True),
        "block_bit_equal": check(
            np.array_equal(Y[lo:lo + block].numpy(), X1[lo:lo + block].numpy()),
            1, at_least=True),
        "column_sums_abs_vs_one_device": check(
            np.abs(ht.sum(Y, axis=0).numpy() - ht.sum(X1, axis=0).numpy()).max()
            / np.sqrt(X.shape[0]), 1e-4),
    }
    line = {
        "sizes": {"rows": X.shape[0], "features": X.shape[1], "from_split": 0, "to_split": 1},
        "reference": f"rows [{lo}, {lo + block}) bit for bit and the column sums "
                     f"against the one-device twin",
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, None


def sharded_matmul(seed: int, comm, mm: int):
    import heat_tpu as ht

    def make(c):
        ht.random.seed(seed + 2)
        return (ht.random.randn(mm, mm, split=0, comm=c),
                ht.random.randn(mm, mm, split=0, comm=c))

    (A, B), (A1, B1) = _twins(comm, make)
    C, C1 = ht.matmul(A, B), ht.matmul(A1, B1)
    ref = C1.numpy()
    # precision "highest" on both sides, the k-sum in ring order on one:
    # 1e-5 of the largest entry (entries are ~sqrt(mm))
    checks = {
        "shards_on_every_device": _spread(C, comm),
        "abs_vs_one_device_rel_to_max": check(
            np.abs(C.numpy() - ref).max() / np.abs(ref).max(), 1e-5),
    }
    line = {
        "sizes": {"m": mm, "k": mm, "n": mm, "splits": [0, 0]},
        "reference": "the same matmul on a one-device communicator",
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, None


def sharded_qr(XX, comm):
    import heat_tpu as ht

    X, X1 = XX
    n = X.shape[1]
    with launch_spans("jitted:linalg.qr") as spans:
        q, r = ht.linalg.qr(X)
    r1 = ht.linalg.qr(X1).R.numpy()
    rn = r.numpy()
    # R is unique up to the sign of each row: compare with the diagonals' signs
    # aligned.  Householder in f32 through two levels against one: 1e-4.
    rn = rn * np.sign(np.diagonal(rn))[:, None] * np.sign(np.diagonal(r1))[:, None]
    norm_x = float(ht.linalg.norm(X))
    checks = {
        "q_on_every_device": _spread(q, comm),
        "residual_rel": check(float(ht.linalg.norm(ht.matmul(q, r) - X)) / norm_x, 1e-4),
        "q_orthogonality": check(
            float(ht.linalg.norm(ht.matmul(q.T, q) - ht.eye(n, comm=comm))),
            _orthogonality_limit(X.shape[0], n)),
        "r_vs_one_device_rel_to_max": check(np.abs(rn - r1).max() / np.abs(r1).max(), 1e-4),
        "sharded_route": _sharded_route(X, spans),
    }
    line = {
        "sizes": {"rows": X.shape[0], "cols": n},
        "routes": sorted({e["route"] for e in spans}),
        "reference": "R of the same call on a one-device communicator; residuals on the device",
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, None


def sharded_ring_attention(seed: int, comm, S: int, H: int, D: int):
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    def make(c):
        ht.random.seed(seed + 5)
        return tuple(ht.random.randn(S, H, D, dtype=ht.bfloat16, split=0, comm=c)
                     for _ in range(3))

    (q, k, v), (q1, k1, v1) = _twins(comm, make)
    out = ht.parallel.ring_attention(q, k, v, causal=True, local_kernel="auto")
    out1 = ht.parallel.ring_attention(q1, k1, v1, causal=True, local_kernel="auto")
    err = np.abs(np.asarray(out.astype(jnp.float32)) - np.asarray(out1.astype(jnp.float32)))
    text = jax.jit(
        lambda a, b, c: ht.parallel.ring_attention(
            a, b, c, causal=True, comm=comm, local_kernel="auto")
    ).lower(q.larray, k.larray, v.larray).as_text()
    # both sides are bf16 flash kernels folding the keys in different block
    # orders: 2e-2 absolute, as the one-chip attention phase
    checks = {
        "q_on_every_device": _spread(q, comm),
        "out_on_every_device": check(
            len({s.device for s in out.addressable_shards}), comm.size, at_least=True),
        "max_abs_vs_one_device": check(err.max(), 2e-2),
        "mean_abs_vs_one_device": check(err.mean(), 2e-3),
        "pallas_kernel_in_program": check("tpu_custom_call" in text, 1, at_least=True),
        "ring_permute_in_program": check("collective_permute" in text, 1, at_least=True),
    }
    line = {
        "sizes": {"S": S, "H": H, "D": D, "dtype": "bfloat16", "causal": True,
                  "local_kernel": "auto"},
        "reference": "the same call on a one-device communicator",
        "checks": checks, "device_dtypes": device_dtypes(),
    }
    return line, None


# --------------------------------------------------------------------- #
# drivers                                                                #
# --------------------------------------------------------------------- #
def run_one_chip(seed: int) -> None:
    X = timed("moments", phase_moments, seed, **FULL["moments"])
    km = timed("kmeans", phase_kmeans, X, seed, **FULL["kmeans"])
    del X
    for name, fn in (("kmedians", phase_kmedians), ("cdist", phase_cdist), ("spectral", phase_spectral), ("lasso", phase_lasso),
                     ("qr_svd", phase_qr_svd), ("attention", phase_attention),
                     ("io", phase_io)):
        timed(name, fn, seed, **FULL[name])
    timed("serve", phase_serve, km, seed, **FULL["serve"])


def run_sharded(seed: int, comm, n, f, k, iters, mm, S, H, D, cut=None) -> None:
    XX = timed("sharded_moments", sharded_moments, seed, comm, n, f)
    timed("sharded_kmeans", sharded_kmeans, XX, seed, comm, k, iters)
    timed("resplit", sharded_resplit, XX, seed, comm)
    timed("tsqr", sharded_qr, XX, comm)
    del XX
    timed("ring_summa_matmul", sharded_matmul, seed, comm, mm)
    timed("ring_attention", sharded_ring_attention, seed, comm, S, H, D)
    if cut:
        emit({"phase": "sharded_cut", "cut": cut})


def worker(args) -> int:
    """The child that holds the chip."""
    import jax

    from heat_tpu.core._compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: jax found no TPU (platform {dev.platform!r}); "
                 "this run proves nothing off the chip")
    count = len(jax.devices())
    if args.chips == 4 and count != 4:
        sys.exit(f"chip_smoke: --chips 4 needs four devices, jax found {count}")
    emit({"phase": "device", "platform": dev.platform, "kind": dev.device_kind,
          "count": count, "compile_cache_dir": cache_dir,
          "compile_cache_entries_at_start":
              len(os.listdir(cache_dir)) if os.path.isdir(cache_dir or "") else 0})
    if args.chips == 4:
        import heat_tpu as ht

        run_sharded(args.seed, ht.get_comm(), **FULL_SHARDED, cut=SHARDED_CUT)
    else:
        run_one_chip(args.seed)
    return 0


def parent(args) -> int:
    """Never touches a backend: runs the worker, then the fleet phase."""
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           "--seed", str(args.seed), "--chips", str(args.chips)]
    device = None
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, cwd=ROOT)
    try:
        for text in proc.stdout:
            print(text, end="", flush=True)
            if text.startswith("{"):
                line = json.loads(text)
                if line.get("phase") == "device":
                    device = {k: line[k] for k in ("platform", "kind", "count")}
        rc = proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        return rc
    if device is None:
        sys.exit("chip_smoke: the worker reported no device")
    if args.chips == 1:
        t0 = time.perf_counter()
        line, _ = phase_fleet()
        emit({"phase": "fleet", **line, "cold_wall_s": round(time.perf_counter() - t0, 3)})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="seed of all data (default 0)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path and its one-device twins")
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    return worker(args) if args.worker else parent(args)


if __name__ == "__main__":
    sys.exit(main())
