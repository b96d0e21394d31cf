"""Lasso: L1-regularized linear regression by coordinate descent.

Reference: heat/regression/lasso.py:4-170 — cyclic coordinate descent with
a distributed matvec per coordinate (rho via ht ops + mean), the soft
threshold operator (:74), and an unregularized intercept (:104-156).
"""

from __future__ import annotations

from typing import Optional, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core import factories, types
from ..core._split_semantics import split_semantics as _split_semantics
from ..core.base import BaseEstimator, RegressionMixin
from ..core.dndarray import DNDarray
from ..core.fuse import fuse
from ..core.sanitation import sanitize_in, sanitize_predict_in
from ..telemetry import _core as _tel

__all__ = ["Lasso"]


def _lasso_predict_program(x: DNDarray, theta: DNDarray) -> DNDarray:
    """ŷ = [1, X] θ as ONE fused program (matmul + layout commit), so a
    warm predict — the serve engine's replay path — is a single device
    dispatch, matching the other estimators' predict discipline."""
    n = x.shape[0]
    arr = jnp.concatenate(
        [jnp.ones((n, 1), dtype=jnp.float32), x.larray.astype(jnp.float32)], axis=1
    )
    pred = arr @ theta.larray.reshape(-1)
    split = x.split if x.split == 0 else None
    pred = x.comm.apply_sharding(pred.reshape(-1, 1), split)
    return DNDarray(pred, (n, 1), types.float32, split, x.device, x.comm, True)


_fused_lasso_predict = fuse(_lasso_predict_program)


class Lasso(RegressionMixin, BaseEstimator):
    """Lasso estimator (reference lasso.py:4-73).

    Parameters
    ----------
    lam : float — L1 penalty weight (reference's ``lam``).
    max_iter : int — coordinate-descent sweeps (or gradient steps).
    tol : float — convergence threshold on coefficient change.
    solver : str — ``"cd"`` (default): cyclic coordinate descent, the
        reference algorithm.  ``"gd"``: proximal gradient (ISTA) with a
        power-iteration step size — same minimizer, and its row-partial
        gradient combine rides the compressed collective ring with an
        error-feedback accumulator when the collective-precision policy
        (:func:`heat_tpu.comm.set_collective_precision`) asks for it, so
        quantization error does not bias convergence.
    checkpoint_every : int — snapshot the fit-loop carry every N
        iterations (0, the default, disables checkpointing).  The loop
        runs in segments of N iterations of the SAME compiled program, so
        a fit killed at a segment boundary and restarted with
        ``fit(..., resume=True)`` replays the identical float trajectory
        — bitwise-equal to never having been interrupted.  For the
        quantized-ring gd solver the snapshot includes the error-feedback
        residual.
    checkpoint_path : str or None — HDF5 snapshot target (atomic writes;
        required when ``checkpoint_every > 0``).
    mini_batch : int or None — rows per chunk for the out-of-core
        streaming fit (gd solver only; docs/design.md §24).  When set —
        or when ``fit`` receives :class:`heat_tpu.io.stream.StreamSource`
        inputs — the fit runs proximal-gradient chunk sweeps over
        :func:`heat_tpu.io.stream.stream_chunks`: each chunk is one
        segment of ONE compiled program with the stream position in the
        explicit carry, ``max_iter`` counts epochs over a fixed chunk
        schedule (``tol`` early exit disabled — determinism), and the
        ISTA step size comes from a power iteration on the first chunk.
    """

    def __init__(
        self,
        lam: float = 0.1,
        max_iter: int = 100,
        tol: float = 1e-6,
        solver: str = "cd",
        checkpoint_every: int = 0,
        checkpoint_path: Optional[str] = None,
        mini_batch: Optional[int] = None,
    ):
        if solver not in ("cd", "gd"):
            raise ValueError(f"solver must be 'cd' or 'gd', got {solver!r}")
        if mini_batch is not None:
            if solver != "gd":
                raise ValueError(
                    "mini_batch streaming requires solver='gd' (coordinate "
                    "descent sweeps every column over all rows at once)"
                )
            if int(mini_batch) < 1:
                raise ValueError(f"mini_batch must be >= 1, got {mini_batch}")
        self.mini_batch = None if mini_batch is None else int(mini_batch)
        self.__lam = lam
        self.max_iter = max_iter
        self.tol = tol
        self.solver = solver
        self.checkpoint_every = checkpoint_every
        self.checkpoint_path = checkpoint_path
        self.__theta = None
        self.n_iter = None

    def _checkpoint_attrs(self):
        # fitted state is the name-mangled theta plus the sweep count
        return ["_Lasso__theta", "n_iter"]

    @property
    def lam(self) -> float:
        return self.__lam

    @lam.setter
    def lam(self, arg: float):
        self.__lam = arg

    @property
    def coef_(self) -> Optional[DNDarray]:
        return None if self.__theta is None else self.__theta[1:]

    @property
    def intercept_(self) -> Optional[DNDarray]:
        return None if self.__theta is None else self.__theta[0]

    @property
    def theta(self):
        return self.__theta

    @staticmethod
    def soft_threshold(rho, lam):
        """S(ρ, λ) shrinkage operator (reference lasso.py:74-90)."""
        return jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam, 0.0)

    def rmse(self, gt: DNDarray, yest: DNDarray) -> float:
        """Root-mean-square error (reference lasso.py:91-103)."""
        diff = gt.larray.reshape(-1) - yest.larray.reshape(-1)
        return float(jnp.sqrt(jnp.mean(diff * diff)))

    def _checkpointer(self, algo: str, meta: dict, comm=None, splits=None):
        """The segmentation driver for this fit configuration."""
        from ..resilience.resume import LoopCheckpointer

        return LoopCheckpointer(
            self.checkpoint_path, self.checkpoint_every, algo, meta,
            comm=comm, splits=splits,
        )

    @_split_semantics("entry_fit")
    def fit(self, x: DNDarray, y: DNDarray,
            resume: Union[bool, str] = False,
            comm=None, device=None) -> "Lasso":
        """Cyclic coordinate descent (reference lasso.py:104-156).

        The per-coordinate update loop is expressed as ``lax.fori_loop``
        over columns so one XLA computation performs a full sweep on the
        sharded data (the reference launches a distributed matvec + mean
        per coordinate).

        With ``checkpoint_every=N`` the sweep loop runs in N-iteration
        segments of the same compiled program, snapshotting the carry
        between segments; ``resume=True`` restarts from the snapshot and
        finishes bitwise-identical to an uninterrupted fit.
        ``resume="elastic"`` additionally accepts a snapshot taken at a
        *different* mesh size — the sharded carry entries migrate to the
        current mesh through the planned-redistribution pipeline (device
        loss: shrink the mesh, rebuild the inputs, resume).

        With ``mini_batch=`` set — or stream-source inputs — the gd fit
        streams chunks out-of-core instead (same resume/elastic
        contract); ``comm``/``device`` pick the mesh for stream inputs
        (DNDarray inputs supply their own).
        """
        from ..io import stream as _stream

        if (
            isinstance(x, _stream.StreamSource)
            or isinstance(y, _stream.StreamSource)
            or self.mini_batch is not None
        ):
            return self._fit_minibatch_gd(x, y, resume, comm=comm, device=device)
        sanitize_in(x)
        sanitize_in(y)
        if x.ndim != 2:
            raise ValueError(f"x needs to be 2D, but was {x.ndim}D")
        if y.ndim > 2 or (y.ndim == 2 and y.shape[1] != 1):
            raise ValueError("y needs to be 1D or a single column")

        n = x.shape[0]
        arr = jnp.concatenate(
            [jnp.ones((n, 1), dtype=jnp.float32), x.larray.astype(jnp.float32)], axis=1
        )  # leading intercept column (reference lasso.py:110-118)
        yv = y.larray.reshape(-1).astype(jnp.float32)

        if self.solver == "gd":
            theta, n_iter = self._fit_gd(x, arr, yv, resume)
        else:
            theta, n_iter = self._fit_cd(arr, yv, resume, comm=x.comm)
        self.n_iter = int(n_iter)
        self.__theta = factories.array(
            np.asarray(theta).reshape(-1, 1), dtype=types.float32, device=x.device, comm=x.comm
        )
        return self

    def _fit_cd(self, arr, yv, resume, comm=None):
        """Segment-driven coordinate descent: the plain fit is one
        segment with ``stop = max_iter``, a checkpointed fit re-enters
        the same compiled program every ``checkpoint_every`` sweeps."""
        from ..resilience import elastic as _elastic

        m = int(arr.shape[1])
        ckpt = self._checkpointer(
            "lasso-cd",
            {
                "n": int(arr.shape[0]), "m": m, "lam": float(self.__lam),
                "tol": float(self.tol), "max_iter": int(self.max_iter),
            },
            comm=comm,
            splits={"it": None, "theta": None, "delta": None},
        )
        if resume:
            state, _ = ckpt.load(elastic=resume == "elastic")
            carry = (
                jnp.int32(state["it"]),
                jnp.asarray(state["theta"], jnp.float32),
                jnp.asarray(state["delta"], jnp.float32),
            )
        else:
            carry = (jnp.int32(0), jnp.zeros((m,), jnp.float32), jnp.float32(jnp.inf))
        lam, tol = jnp.float32(self.__lam), jnp.float32(self.tol)
        while True:
            it0 = int(carry[0])
            stop = ckpt.stop(it0, self.max_iter)
            with _elastic.dispatch_guard("lasso.cd", comm):
                carry = Lasso._fit_segment(arr, yv, lam, tol, jnp.int32(stop), carry)
            it = int(carry[0])
            if it >= self.max_iter or it < stop:
                # out of iterations, or converged before the boundary
                break
            ckpt.tick(it, {"it": carry[0], "theta": carry[1], "delta": carry[2]})
        return carry[1], carry[0]

    @staticmethod
    @jax.jit
    def _fit_segment(arr, yv, lam, tol, stop, carry):
        """Cyclic coordinate descent as ONE compiled program (reference
        lasso.py:104-156 runs a distributed matvec + mean per coordinate
        and a host convergence check per sweep), re-enterable: the carry
        ``(it, theta, delta)`` comes in explicitly and sweeps run while
        ``it < stop`` — the whole fit is one segment with
        ``stop = max_iter``; checkpointed fits replay THIS program
        segment by segment, which is what makes resume bitwise-exact.

        Two structural changes vs the reference, both value-preserving:
        - the residual vector is maintained incrementally across
          coordinates (when θ_j moves by Δ, resid -= x_j Δ), so a full
          sweep costs O(n·m) instead of the reference's O(n·m²) fresh
          matvec per coordinate;
        - sweeps run under ``lax.while_loop`` with the tol check on
          device, so the host syncs once per segment, not once per sweep.
        """
        m = arr.shape[1]
        z = jnp.maximum(jnp.mean(arr * arr, axis=0), 1e-12)  # loop-invariant

        def body_sweep(state):
            it, th, _ = state

            resid = yv - arr @ th

            def body(j, s):
                th, resid = s
                xj = arr[:, j]
                rho = jnp.mean(xj * (resid + xj * th[j]))
                # intercept (j == 0) is unregularized (reference :137-146)
                new = jnp.where(
                    j == 0, rho / z[j], Lasso.soft_threshold(rho, lam) / z[j]
                )
                resid = resid - xj * (new - th[j])
                return th.at[j].set(new), resid

            th2, _ = lax.fori_loop(0, m, body, (th, resid))
            delta = jnp.max(jnp.abs(th2 - th))
            return it + 1, th2, delta

        def cond(state):
            it, _, delta = state
            return jnp.logical_and(it < stop, delta > tol)

        return lax.while_loop(cond, body_sweep, carry)

    def _fit_gd(self, x: DNDarray, arr, yv, resume=False):
        """Proximal-gradient (ISTA) fit: θ ← prox_{sλ}(θ − s∇f(θ)) with
        step ``s = 1/L`` from power iteration.  When the
        collective-precision policy compresses and the rows split
        canonically, the per-shard gradient partials ``A_pᵀ r_p`` combine
        on the block-scaled quantized ring with an error-feedback
        accumulator carried in the loop state — otherwise one exact
        compiled program.  Both forms run segment-by-segment under
        ``checkpoint_every`` (the quantized form snapshots the EF
        residual as part of the carry)."""
        from ..resilience import elastic as _elastic

        n, m = int(arr.shape[0]), int(arr.shape[1])
        step = jnp.float32(1.0) / Lasso._lipschitz(arr)
        lam = jnp.float32(self.__lam)
        tol = jnp.float32(self.tol)
        comm = x.comm
        meta = {
            "n": n, "m": m, "lam": float(self.__lam), "tol": float(self.tol),
            "max_iter": int(self.max_iter),
        }
        elastic = resume == "elastic"
        if x.split == 0 and comm.size > 1 and n % comm.size == 0:
            from ..comm import compressed as _cq

            mode = _cq.reduce_mode(jnp.float32, m * 4)
            if mode is not None:
                ckpt = self._checkpointer(
                    "lasso-gd-q", {**meta, "mode": mode}, comm=comm,
                    splits={"it": None, "theta": None, "delta": None,
                            "error": "mesh"},
                )
                if resume:
                    state, _ = ckpt.load(elastic=elastic)
                    carry = (
                        jnp.int32(state["it"]),
                        jnp.asarray(state["theta"], jnp.float32),
                        jnp.asarray(state["delta"], jnp.float32),
                        jnp.asarray(state["error"], jnp.float32),
                    )
                else:
                    carry = (
                        jnp.int32(0),
                        jnp.zeros((m,), jnp.float32),
                        jnp.float32(jnp.inf),
                        jnp.zeros((comm.size, m), jnp.float32),
                    )
                while True:
                    it0 = int(carry[0])
                    stop = ckpt.stop(it0, self.max_iter)
                    with _elastic.dispatch_guard("lasso.gd_q", comm):
                        carry = _gd_segment_q(
                            arr, yv, lam, tol, jnp.int32(stop), step, carry,
                            comm=comm, mode=mode,
                        )
                    it = int(carry[0])
                    if _tel.enabled and it > it0:
                        # the quantized gradient combine runs INSIDE the
                        # compiled segment (one ring of m f32 per ISTA
                        # step), so the fit driver credits the wire-byte
                        # ledger per iteration here
                        _cq._account_wire(
                            "allreduce", mode, m, comm.size, reps=it - it0
                        )
                    if it >= self.max_iter or it < stop:
                        break
                    ckpt.tick(
                        it,
                        {"it": carry[0], "theta": carry[1], "delta": carry[2],
                         "error": carry[3]},
                    )
                return carry[1], carry[0]
        ckpt = self._checkpointer(
            "lasso-gd", meta, comm=comm,
            splits={"it": None, "theta": None, "delta": None},
        )
        if resume:
            state, _ = ckpt.load(elastic=elastic)
            carry = (
                jnp.int32(state["it"]),
                jnp.asarray(state["theta"], jnp.float32),
                jnp.asarray(state["delta"], jnp.float32),
            )
        else:
            carry = (jnp.int32(0), jnp.zeros((m,), jnp.float32), jnp.float32(jnp.inf))
        while True:
            it0 = int(carry[0])
            stop = ckpt.stop(it0, self.max_iter)
            with _elastic.dispatch_guard("lasso.gd", comm):
                carry = Lasso._gd_segment(arr, yv, lam, tol, jnp.int32(stop), step, carry)
            it = int(carry[0])
            if it >= self.max_iter or it < stop:
                break
            ckpt.tick(it, {"it": carry[0], "theta": carry[1], "delta": carry[2]})
        return carry[1], carry[0]

    def _fit_minibatch_gd(self, x, y, resume=False, comm=None, device=None) -> "Lasso":
        """Out-of-core proximal-gradient fit: ``max_iter`` epochs of ISTA
        chunk sweeps over :func:`heat_tpu.io.stream.stream_chunks`, each
        chunk ONE dispatch of one compiled segment with the stream
        position in the explicit ``(it, theta, delta)`` carry.

        The step size is ``1/L`` from a power iteration over the FIRST
        chunk's design matrix — recomputed deterministically on every
        (re)entry, so it never needs to live in the snapshot.  The
        segment replicates the chunk and computes on the mesh-independent
        ``(mb, m)`` slice with the valid-count mask doubling as the
        intercept column, so pad rows of X *and* y contribute exactly
        zero to the gradient and the trajectory is a pure function of the
        byte stream — the elastic resume gate (4→8, 8→4 bitwise) follows."""
        if self.mini_batch is None:
            raise ValueError(
                "streaming fit requires Lasso(solver='gd', mini_batch=...)"
            )
        from ..core import devices as _devices
        from ..core.communication import comm_for_device, sanitize_comm
        from ..io import stream as _stream
        from ..resilience import elastic as _elastic

        for d in (x, y):
            if isinstance(d, DNDarray):
                device = d.device if device is None else device
                comm = d.comm if comm is None else comm
        device = _devices.sanitize_device(device)
        comm = comm_for_device(device.platform) if comm is None else sanitize_comm(comm)
        srcx = _stream.as_source(x)
        srcy = _stream.as_source(y)
        if len(srcx.shape) != 2:
            raise ValueError(f"x needs to be 2D, but was {len(srcx.shape)}D")
        ynd = len(srcy.shape)
        if ynd > 2 or (ynd == 2 and srcy.shape[1] != 1):
            raise ValueError("y needs to be 1D or a single column")

        n, f = srcx.shape
        m = f + 1
        mb = self.mini_batch
        h = max(1, -(-n // mb))
        total = int(self.max_iter) * h

        nv0 = min(mb, n)
        x0 = np.asarray(srcx.read(0, nv0), dtype=np.float32)
        a0 = np.concatenate([np.ones((nv0, 1), np.float32), x0], axis=1)
        step = jnp.float32(1.0) / Lasso._lipschitz(jnp.asarray(a0))
        lam = jnp.float32(self.__lam)

        meta = {
            "n": n, "m": m, "lam": float(self.__lam), "mb": mb,
            "max_iter": int(self.max_iter),
        }
        ckpt = self._checkpointer(
            "lasso-mb", meta, comm=comm,
            splits={"it": None, "theta": None, "delta": None},
        )
        if resume:
            state, _ = ckpt.load(elastic=resume == "elastic")
            carry = (
                jnp.int32(state["it"]),
                jnp.asarray(state["theta"], jnp.float32),
                jnp.asarray(state["delta"], jnp.float32),
            )
        else:
            carry = (jnp.int32(0), jnp.zeros((m,), jnp.float32), jnp.float32(jnp.inf))

        fn = _lasso_mb_segment(comm, mb, f, ynd)
        while True:
            it0 = int(carry[0])
            stop = ckpt.stop(it0, total)
            with _elastic.dispatch_guard("lasso.mb", comm):
                for (xc, yc), nv in _stream.stream_chunks(
                    (srcx, srcy), mb, it0, stop, comm=comm, device=device
                ):
                    carry = fn(xc, yc, jnp.int32(nv), lam, step, *carry)
            it = int(carry[0])
            if it >= total or it < stop:
                break
            ckpt.tick(it, {"it": carry[0], "theta": carry[1], "delta": carry[2]})

        self.n_iter = int(carry[0])
        self.__theta = factories.array(
            np.asarray(carry[1]).reshape(-1, 1), dtype=types.float32,
            device=device, comm=comm,
        )
        return self

    @staticmethod
    @jax.jit
    def _lipschitz(arr):
        """λmax(AᵀA)/n by power iteration — the ISTA step is 1/L."""
        n = arr.shape[0]
        g = (arr.T @ arr) / jnp.float32(n)

        def body(_, v):
            w = g @ v
            return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

        v = lax.fori_loop(0, 50, body, jnp.ones((arr.shape[1],), jnp.float32))
        return jnp.maximum(v @ (g @ v), 1e-12)

    @staticmethod
    @jax.jit
    def _gd_segment(arr, yv, lam, tol, stop, step, carry):
        """Exact ISTA under one ``lax.while_loop`` (GSPMD inserts the
        gradient all-reduce on sharded rows), re-enterable via the
        explicit ``(it, theta, delta)`` carry and dynamic ``stop`` — see
        :meth:`_fit_segment` for the segmentation contract."""
        n = arr.shape[0]

        def body(state):
            it, th, _ = state
            grad = arr.T @ (arr @ th - yv) / jnp.float32(n)
            t2 = th - step * grad
            new = jnp.concatenate([t2[:1], Lasso.soft_threshold(t2[1:], step * lam)])
            return it + 1, new, jnp.max(jnp.abs(new - th))

        def cond(state):
            it, _, delta = state
            return jnp.logical_and(it < stop, delta > tol)

        return lax.while_loop(cond, body, carry)

    @_split_semantics("entry_split0")
    def predict(self, x: DNDarray) -> DNDarray:
        """ŷ = [1, X] θ (reference lasso.py:157-170), one fused dispatch."""
        if self.__theta is None:
            raise RuntimeError("fit() must be called before predict()")
        x = sanitize_predict_in(
            x, n_features=int(self.__theta.shape[0]) - 1, op="Lasso.predict"
        )
        return _fused_lasso_predict(x, self.__theta)


def _lasso_mb_segment(comm, mb, f, ynd):
    """ONE compiled chunk-sweep program for the mini-batch gd fit:
    ``(xc, yc, nvalid, lam, step, it, theta, delta) ->
    (it+1, theta', delta')``.

    The chunks arrive row-sharded and zero-padded; the program replicates
    them and computes on the mesh-independent ``[:mb]`` slice (see
    :func:`heat_tpu.cluster.kmeans._kmeans_mb_segment` for why that is
    the elastic-bitwise move).  The ``arange(mb) < nvalid`` row mask IS
    the design matrix's intercept column: valid rows get the usual
    leading 1, pad rows are all-zero in A *and* in the padded y, so they
    contribute exactly zero to ``Aᵀ(Aθ − y)`` — the ragged final chunk
    needs no special case.  Keyed on ``(comm, mb, f, ynd)``: one compile
    for the whole stream, one dispatch per chunk."""
    from ..core._compile import jitted

    rep2 = comm.sharding(2, None)
    repy = comm.sharding(ynd, None)

    def make():
        def seg(xc, yc, nvalid, lam, step, it, th, delta):
            x = jax.lax.with_sharding_constraint(xc, rep2)[:mb]
            yv = jnp.reshape(
                jax.lax.with_sharding_constraint(yc, repy)[:mb], (mb,)
            )
            w = (jnp.arange(mb) < nvalid).astype(jnp.float32)
            a = jnp.concatenate([w[:, None], x], axis=1)
            grad = a.T @ (a @ th - yv) / nvalid.astype(jnp.float32)
            t2 = th - step * grad
            new = jnp.concatenate([t2[:1], Lasso.soft_threshold(t2[1:], step * lam)])
            return it + 1, new, jnp.max(jnp.abs(new - th))

        return seg

    return jitted(("lasso.mb_seg", comm, mb, f, ynd), make)


def _gd_segment_q(arr, yv, lam, tol, stop, step, carry, *, comm, mode):
    """ISTA with the cross-shard gradient combine on the compressed ring.

    Each segment is ONE compiled ``shard_map`` program: every device
    holds a row shard, computes its gradient partial ``A_pᵀ (A_p θ −
    y_p)``, and the partials sum over the block-scaled quantized ring
    with an error-feedback accumulator carried in the ``while_loop``
    state — the untransmitted quantization residual re-enters the next
    step's gradient, so compression adds noise but no bias to the
    iterates.

    The carry is ``(it, theta, delta, error)`` with ``error`` in its
    host-visible stacked form ``(p, m)`` — one EF residual row per mesh
    position, sharded in and out over the mesh axis — precisely so the
    checkpointing driver can snapshot it between segments and a resumed
    fit replays the identical quantized trajectory.
    """
    from jax.sharding import PartitionSpec

    from ..comm.compressed import ring_allreduce_q_ef
    from ..core._compile import jitted
    from jax import shard_map

    n, m = int(arr.shape[0]), int(arr.shape[1])
    p = comm.size
    mesh, name = comm._mesh, comm.axis_name

    def make():
        def kernel(a, y0, lam_, tol_, stop_, step_, it0, th0, delta0, e0):
            def body(state):
                it, th, _, e = state
                g_part = a.T @ (a @ th - y0)
                g, e2 = ring_allreduce_q_ef(g_part, e, name, size=p, mode=mode)
                t2 = th - step_ * (g / jnp.float32(n))
                new = jnp.concatenate(
                    [t2[:1], Lasso.soft_threshold(t2[1:], step_ * lam_)]
                )
                return it + 1, new, jnp.max(jnp.abs(new - th)), e2

            def cond(state):
                it, _, delta, _ = state
                return jnp.logical_and(it < stop_, delta > tol_)

            init = (it0, th0, delta0, jnp.squeeze(e0, axis=0))
            it, th, delta, e = lax.while_loop(cond, body, init)
            return it, th, delta, e[None]

        rep = PartitionSpec()

        def _f(a, y0, lam_, tol_, stop_, step_, it0, th0, delta0, e0):
            return shard_map(
                kernel,
                mesh=mesh,
                in_specs=(
                    comm.spec(2, 0), comm.spec(1, 0), rep, rep, rep, rep,
                    rep, rep, rep, comm.spec(2, 0),
                ),
                out_specs=(rep, rep, rep, PartitionSpec(name)),
                check_vma=False,
            )(a, y0, lam_, tol_, stop_, step_, it0, th0, delta0, e0)

        return _f

    fn = jitted(("lasso.gd_q", comm, mode, n, m), make)
    it0, th0, delta0, e0 = carry
    return fn(arr, yv, lam, tol, stop, step, it0, th0, delta0, e0)
