"""K-Means clustering (Lloyd's algorithm).

Reference: heat/cluster/kmeans.py:5-121 — assignment via
``cdist(quadratic_expansion=True)`` and centroid update via the
selection-matrix trick (masked sums / counts, :58-86), with convergence on
the centroid-shift inertia.

TPU formulation: the update's masked sums are written as
``one_hot(labels).T @ X`` — a single MXU matmul — and the whole
assign+update step is one fused XLA computation.  The per-cluster Allreduce
pairs of the reference (2k collectives per epoch, kmeans.py:58-86) become,
in the layout the compiled segment picks for its sweep operand
(:func:`_feature_layout`): over rows split across the mesh, one all-reduce
of the (k, f) partial sums a sweep; over feature columns (wide, short data,
where that moves fewer bytes: the rows go to columns in one all-to-all a
segment), one all-reduce of the (n, k) distance partials a sweep.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..core._compile import launch
from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in
from ..spatial import distance
from ..telemetry import _core as _tel
from ._kcluster import _KCluster, _quadratic_cdist, _rows_evenly_sharded

__all__ = ["KMeans"]


class KMeans(_KCluster):
    """K-Means estimator (reference kmeans.py:5-56).

    Parameters
    ----------
    n_clusters : int
    init : 'random' | 'probability_based' (k-means++) | DNDarray of centroids
    max_iter : int
    tol : float — convergence threshold on centroid shift
    random_state : int or None
    checkpoint_every : int — snapshot the Lloyd-loop carry every N
        iterations (0, the default, disables checkpointing).  The loop
        runs in N-iteration segments of the SAME compiled program, so a
        fit killed at a segment boundary and restarted with
        ``fit(..., resume=True)`` replays the identical float trajectory
        — centers bitwise-equal to never having been interrupted.  The
        quantized-ring form snapshots the error-feedback residual too.
    checkpoint_path : str or None — HDF5 snapshot target (atomic writes;
        required when ``checkpoint_every > 0``).
    mini_batch : int or None — rows per chunk for the out-of-core
        streaming fit (docs/design.md §24).  When set (or when ``fit``
        receives a :class:`heat_tpu.io.stream.StreamSource`), the fit
        runs mini-batch incremental-center updates over
        :func:`heat_tpu.io.stream.stream_chunks`: each chunk is one
        segment of ONE compiled program with the stream position in the
        explicit carry, ``max_iter`` counts epochs, and ``tol`` early
        exit is disabled (a fixed schedule is what keeps resumed and
        elastic replays bitwise-identical).  The centers after chunk
        ``t`` move by the running-mean rule
        ``c += (batch_sum − batch_count·c) / total_count`` (the
        sklearn/Sculley mini-batch update), so a fit over an
        :class:`~heat_tpu.io.stream.ArraySource` of in-memory rows is
        the bitwise twin of the same fit streamed from disk.
    """

    _init_plus_plus_alias = "kmeans++"

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
        checkpoint_every: int = 0,
        checkpoint_path: Optional[str] = None,
        mini_batch: Optional[int] = None,
    ):
        super().__init__(
            metric=_quadratic_cdist,  # module-level: fused-assign cache hit
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
            checkpoint_every=checkpoint_every,
            checkpoint_path=checkpoint_path,
        )
        if mini_batch is not None and int(mini_batch) < 1:
            raise ValueError(f"mini_batch must be >= 1, got {mini_batch}")
        self.mini_batch = None if mini_batch is None else int(mini_batch)

    @staticmethod
    def _fit_segment(arr, tol, stop, carry):
        """One segment of the fit, issued at ``jit:kmeans.fit_segment``: the
        sweeps ``carry[0] .. stop`` of the compiled program
        :func:`_fit_segment`, in the layout :func:`_feature_layout` picks
        for this operand, mesh and sweep count, which the span's ``layout``
        field names.  ``stop`` and ``carry[0]`` are read on the host, where
        ``fit`` holds both already (``np.int32(stop)``, ``sync:kmeans.it0``);
        the carry ``(it, centers, shift)`` is replicated in and out in
        either layout."""
        cols = _feature_layout(arr, carry[1].shape[0], int(stop) - int(carry[0]))
        return launch(
            "jit:kmeans.fit_segment", _fit_segment, (arr, tol, stop, carry), {"cols": cols},
            layout="rows" if cols is None else "features",
        )

    @staticmethod
    @jax.jit
    def _finalize(arr, centers):
        """Final labels + inertia for the converged centers — the tail of
        the fit, split out of the loop program so segments stay cheap."""
        with jax.named_scope("kmeans.finalize"):
            c2 = jnp.sum(centers * centers, axis=1)[None, :]
            labels = jnp.argmin(c2 - 2.0 * jnp.matmul(arr, centers.T), axis=1)
            inertia = jnp.sum((arr - centers[labels]) ** 2)
        return labels, inertia

    def fit(self, x: DNDarray, resume=False, comm=None, device=None) -> "KMeans":
        """Lloyd iterations until centroid shift ≤ tol (reference
        kmeans.py:87-120), as a single on-device loop.

        With ``checkpoint_every=N`` the loop runs in N-iteration segments
        of the same compiled program, snapshotting the carry between
        segments; ``resume=True`` restarts from the snapshot (skipping
        center initialization) and finishes bitwise-identical to an
        uninterrupted fit.  ``resume="elastic"`` additionally accepts a
        snapshot taken at a different mesh size, migrating the stacked
        error-feedback residual to the current mesh (device loss: shrink
        the mesh, rebuild the inputs, resume).

        With ``mini_batch=`` set — or ``x`` a
        :class:`heat_tpu.io.stream.StreamSource` — the fit streams chunks
        out-of-core instead (same resume/elastic contract, ``max_iter``
        epochs over a fixed chunk schedule); ``comm``/``device`` pick the
        mesh for stream inputs (a DNDarray input supplies its own).
        """
        from ..io import stream as _stream

        if isinstance(x, _stream.StreamSource) or self.mini_batch is not None:
            return self._fit_minibatch(x, resume, comm=comm, device=device)
        sanitize_in(x)
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2D, but was {x.ndim}D")
        arr = x.larray.astype(jnp.float32)
        comm = x.comm
        n, f = int(x.shape[0]), int(x.shape[1])
        k = self.n_clusters

        mode = None
        if _rows_evenly_sharded(x):
            from ..comm import compressed as _cq

            # collective-precision policy: the per-iteration (k, f)
            # centroid-partial combine rides the quantized ring with an
            # error-feedback accumulator in the loop carry
            mode = _cq.reduce_mode(jnp.float32, k * f * 4)
        use_q = mode is not None

        from ..resilience import elastic as _elastic

        meta = {
            "n": n, "f": f, "k": k, "tol": float(self.tol),
            "max_iter": int(self.max_iter),
        }
        splits = {"it": None, "centers": None, "shift": None}
        if use_q:
            meta.update(mode=mode)
            splits["error"] = "mesh"
        ckpt = self._checkpointer(
            "kmeans-q" if use_q else "kmeans", meta, comm=comm, splits=splits
        )

        if resume:
            state, _ = ckpt.load(elastic=resume == "elastic")
            carry = (
                jnp.int32(state["it"]),
                jnp.asarray(state["centers"], jnp.float32),
                jnp.asarray(state["shift"], jnp.float32),
            )
            if use_q:
                carry = carry + (jnp.asarray(state["error"], jnp.float32),)
        else:
            _tel.spanned("kmeans:init", "other", self._initialize_cluster_centers, x)
            centers0 = self._cluster_centers.larray.astype(jnp.float32)
            carry = (jnp.int32(0), centers0, jnp.float32(jnp.inf))
            if use_q:
                carry = carry + (jnp.zeros((comm.size, k * f), jnp.float32),)

        tol = jnp.float32(self.tol)
        while True:
            it0 = _tel.host_read("sync:kmeans.it0", carry[0], int)
            stop = ckpt.stop(it0, self.max_iter)
            with _elastic.dispatch_guard(
                "kmeans.seg_q" if use_q else "kmeans.seg", comm
            ):
                if use_q:
                    carry = _kmeans_segment_q(
                        arr, tol, jnp.int32(stop), carry, comm=comm, mode=mode
                    )
                else:
                    carry = KMeans._fit_segment(arr, tol, np.int32(stop), carry)
            it = _tel.host_read("sync:kmeans.it", carry[0], int)
            if use_q and _tel.enabled and it > it0:
                from ..comm import compressed as _cq

                # the quantized centroid-partial combine runs INSIDE the
                # compiled segment (one ring of k*f f32 per Lloyd step) —
                # invisible to the host-level accounting in allreduce_q,
                # so the fit driver credits the ledger per iteration here
                _cq._account_wire("allreduce", mode, k * f, comm.size, reps=it - it0)
            if it >= self.max_iter or it < stop:
                # out of iterations, or converged before the boundary
                break
            snap = {"it": carry[0], "centers": carry[1], "shift": carry[2]}
            if use_q:
                snap["error"] = carry[3]
            ckpt.tick(it, snap)

        centers = carry[1]
        if use_q:
            labels, inertia = _kmeans_finalize_q(arr, centers, comm=comm)
        else:
            labels, inertia = launch("jit:kmeans.finalize", KMeans._finalize, (arr, centers))
        _tel.spanned("kmeans:wrap", "other", self._finalize_fit, x, centers, labels, carry[0])
        # device scalar; inertia_ property syncs lazily on access
        self._inertia = inertia
        return self

    def _fit_minibatch(self, x, resume=False, comm=None, device=None) -> "KMeans":
        """Out-of-core mini-batch fit: ``max_iter`` epochs of incremental
        center updates over :func:`heat_tpu.io.stream.stream_chunks`,
        each chunk ONE dispatch of one compiled segment program with the
        stream position in the explicit ``(it, centers, counts)`` carry
        (``it // h`` is the epoch, ``it % h`` the chunk — see
        :func:`heat_tpu.resilience.resume.stream_position`).

        The segment replicates the (small) chunk and computes on the
        mesh-independent ``(mb, f)`` slice, so the center trajectory is a
        pure function of the byte stream — the same snapshot resumes on a
        grown or shrunk mesh (``resume="elastic"``) bitwise-identical to
        an uninterrupted fit, and an :class:`ArraySource` twin of on-disk
        data reproduces the streamed fit exactly."""
        import numpy as np

        from ..core import devices as _devices, types
        from ..core.communication import comm_for_device, sanitize_comm
        from ..io import stream as _stream
        from ..resilience import elastic as _elastic

        src = _stream.as_source(x)
        if isinstance(x, DNDarray):
            device = x.device if device is None else device
            comm = x.comm if comm is None else comm
        device = _devices.sanitize_device(device)
        comm = comm_for_device(device.platform) if comm is None else sanitize_comm(comm)
        if len(src.shape) != 2:
            raise ValueError(f"input needs to be 2D, but was {len(src.shape)}D")
        if self.mini_batch is None:
            raise ValueError(
                "streaming fit requires KMeans(mini_batch=<rows per chunk>)"
            )
        n, f = src.shape
        k = self.n_clusters
        mb = self.mini_batch
        h = max(1, -(-n // mb))
        total = int(self.max_iter) * h

        meta = {"n": n, "f": f, "k": k, "mb": mb, "max_iter": int(self.max_iter)}
        splits = {"it": None, "centers": None, "counts": None}
        ckpt = self._checkpointer("kmeans-mb", meta, comm=comm, splits=splits)

        if resume:
            state, _ = ckpt.load(elastic=resume == "elastic")
            carry = (
                jnp.int32(state["it"]),
                jnp.asarray(state["centers"], jnp.float32),
                jnp.asarray(state["counts"], jnp.float32),
            )
        else:
            centers0 = self._init_minibatch_centers(src, n, f, k, mb)
            carry = (jnp.int32(0), jnp.asarray(centers0, jnp.float32),
                     jnp.zeros((k, 1), jnp.float32))

        fn = _kmeans_mb_segment(comm, mb, f, k)
        while True:
            it0 = int(carry[0])
            stop = ckpt.stop(it0, total)
            with _elastic.dispatch_guard("kmeans.mb", comm):
                for arrs, nv in _stream.stream_chunks(
                    src, mb, it0, stop, comm=comm, device=device
                ):
                    carry = fn(arrs[0], jnp.int32(nv), *carry)
            it = int(carry[0])
            if it >= total or it < stop:
                break
            ckpt.tick(it, {"it": carry[0], "centers": carry[1], "counts": carry[2]})

        centers = carry[1]
        self._n_iter = carry[0]
        self._cluster_centers = DNDarray(
            comm.apply_sharding(centers.astype(types.float32.jax_type()), None),
            (k, f), types.float32, None, device, comm, True,
        )
        # labels_/inertia_ stay None: the dataset never materializes in
        # memory, so the assignment pass is the caller's predict() choice
        self._labels = None
        self._inertia = None
        return self

    def _init_minibatch_centers(self, src, n, f, k, mb):
        """Initial centers for a streaming fit: a DNDarray of centroids
        passes through; ``"random"`` draws k distinct rows of the FIRST
        chunk with a host-side seeded rng — deterministic given
        ``random_state``, independent of mesh size (the device rng is
        comm-coupled), and readable without touching the rest of the
        stream."""
        import numpy as np

        if isinstance(self.init, DNDarray):
            if tuple(self.init.shape) != (k, f):
                raise ValueError(
                    "passed centroids do not match cluster count or data shape"
                )
            return np.asarray(self.init.resplit(None).larray, dtype=np.float32)
        if self.init == "random":
            nv0 = min(mb, n)
            if k > nv0:
                raise ValueError(
                    f"n_clusters={k} exceeds the first chunk's {nv0} rows; "
                    "raise mini_batch or pass explicit centroids"
                )
            rng = np.random.default_rng(
                0 if self.random_state is None else int(self.random_state)
            )
            idx = np.sort(rng.choice(nv0, size=k, replace=False))
            block = np.asarray(src.read(0, nv0), dtype=np.float32)
            return block[idx]
        raise ValueError(
            "mini-batch/streaming fits support init='random' or an explicit "
            f"DNDarray of centroids, got {self.init!r}"
        )


def _sweep_dtype(mesh):
    """The dtype the sweeps' products read X in: bfloat16 on the TPU at the
    default matmul precision, where XLA makes every float32 product one bf16
    pass on the MXU (and the row program a bf16 copy of X, ``convert.3``),
    so the copy the feature layout exchanges rounds nothing new; float32
    elsewhere."""
    tpu = mesh.devices.flat[0].platform == "tpu"
    return jnp.bfloat16 if tpu and jax.config.jax_default_matmul_precision is None else jnp.float32


def _feature_layout(arr, k: int, sweeps: int):
    """The feature layout's sharding of X, ``P(None, axis)``, where the
    segment's ``sweeps`` should read X split by features; ``None`` for the
    row layout, which every other operand keeps.

    Only for rows lying evenly over a mesh axis of ``p > 1`` devices and at
    least ``p`` features.  Then the bytes a chip puts on the ICI decide
    (the common ``(p-1)/p`` left out): the row layout all-reduces the
    ``(k, f)`` float32 sums every sweep (``2·k·f·4`` a sweep, ring); the
    feature layout exchanges its rows of the sweep copy once (``n/p`` rows
    of the ``f`` columns padded to ``p·w``), all-gathers the ``(k, f)``
    centres at the end and all-reduces an ``(n, k)`` distance partial every
    sweep.  The feature layout is taken where it puts at most half the row
    layout's bytes on the ICI: the margin pays for the second copy of X the
    exchange holds in HBM while it runs.  At the 4-chip cell (448 x 6 291 456,
    k 8, 30 sweeps) that is 1.06 GB a chip against 9.1 GB; a tall operand,
    n above about 2·k·sweeps·p (k·sweeps·p where the copy is float32), keeps
    the rows."""
    sh = getattr(arr, "sharding", None)
    if not isinstance(sh, NamedSharding):
        return None
    spec = tuple(sh.spec) + (None, None)
    axis = spec[0]
    if not isinstance(axis, str) or spec[1] is not None:
        return None
    p = sh.mesh.shape[axis]
    n, f = arr.shape
    if p < 2 or n % p or f < p:
        return None
    fp = -(-f // p) * p
    rows = sweeps * 2 * k * f * 4
    cols = (n // p) * fp * jnp.dtype(_sweep_dtype(sh.mesh)).itemsize + k * fp * 4 + sweeps * 2 * n * k * 4
    return NamedSharding(sh.mesh, PartitionSpec(None, axis)) if 2 * cols <= rows else None


def _sweep(a, c):
    """One Lloyd step of the centres ``c`` over the operand ``a``.  The |x|²
    row norms are left out of the assignment: they are constant across the
    k candidates, so ``argmin_k(|x|² + |c|² − 2x·c) == argmin_k(|c|² −
    2x·c)`` exactly, which saves a pass over ``a``."""
    with jax.named_scope("kmeans.sweep.assign"):
        c2 = jnp.sum(c * c, axis=1)[None, :]  # (1, k)
        d2 = c2 - 2.0 * jnp.matmul(a, c.T.astype(a.dtype), preferred_element_type=jnp.float32)
        labels = jnp.argmin(d2, axis=1)
    with jax.named_scope("kmeans.sweep.update"):
        sel = jax.nn.one_hot(labels, c.shape[0], dtype=a.dtype)
        sums = jnp.matmul(sel.T, a, preferred_element_type=jnp.float32)  # (k, f) masked sum on the MXU
        counts = jnp.sum(sel, axis=0, dtype=jnp.float32)[:, None]
        return jnp.where(counts > 0, sums / jnp.maximum(counts, 1), c)


def _lloyd(a, tol, stop, carry):
    """The Lloyd ``while_loop`` over the carry ``(it, centers, shift)``:
    sweeps run while ``it < stop`` and the last shift exceeds ``tol``."""

    def cond(state):
        it, _, shift = state
        return jnp.logical_and(it < stop, shift > tol)

    def body(state):
        it, c, _ = state
        nc = _sweep(a, c)
        shift = jnp.sum((nc - c) ** 2)
        return it + 1, nc, shift

    return jax.lax.while_loop(cond, body, carry)


@partial(jax.jit, static_argnames=("cols",))
def _fit_segment(arr, tol, stop, carry, cols=None):
    """Lloyd iterations as ONE compiled ``lax.while_loop`` program,
    re-enterable: the carry ``(it, centers, shift)`` comes in explicitly
    and steps run while ``it < stop`` — the whole fit is one segment with
    ``stop = max_iter``; checkpointed fits replay THIS program segment by
    segment, which is what makes resume bitwise-exact.  One dispatch, zero
    host syncs per segment — the host never sees intermediate state (the
    reference's per-epoch convergence check, kmeans.py:106-118, costs a
    device round trip per iteration).

    One loop (:func:`_lloyd`) over either of two layouts of X; ``cols`` is
    :func:`_feature_layout`'s answer, and GSPMD places the collectives.

    * Rows (``cols=None``): the loop runs on X as it lies.  On one chip a
      sweep is TWO passes over a bf16 copy of X: XLA emits the distance
      matmul with its argmin and the one-hot masked-sum matmul as two
      fusions, 160 + 169 ms of a 496.6 ms job at 300 x 6 291 456
      (``roofline_pct`` 75.5; ledger, PR 29, ``kmeans_300_c1``; breakdown
      in ``PERF.md`` §5); the one-pass row-blocked sweep is ROADMAP
      Speed 2, open.  Over rows split across chips the masked sum contracts
      the row axis, so every sweep ends in an all-reduce of the (k, f)
      float32 sums and each chip repeats the whole update.
    * Features (``cols = P(None, axis)``): X, cast once to the sweeps'
      dtype (:func:`_sweep_dtype`) and its columns zero-padded to a
      multiple of the mesh (they add nothing to any sum), goes from rows to
      feature columns in ONE all-to-all before the loop, and so do the
      centres (a slice of a replicated array).  Each chip sweeps all n rows
      of its columns: the distance product contracts the features, so
      only its (n, k) partial is all-reduced; the argmin and one-hot are
      replicated; the masked sums, counts and update are the chip's own;
      the shift is a scalar all-reduce.  The centres are all-gathered at
      the end."""
    if cols is None:
        return _lloyd(arr, tol, stop, carry)
    f = arr.shape[1]
    pad = ((0, 0), (0, -f % cols.mesh.shape[cols.spec[1]]))

    def by_features(x):
        return jax.lax.with_sharding_constraint(jnp.pad(x, pad), cols)

    it, c, shift = carry
    it, c, shift = _lloyd(by_features(arr.astype(_sweep_dtype(cols.mesh))), tol, stop, (it, by_features(c), shift))
    return it, jax.lax.with_sharding_constraint(c[:, :f], NamedSharding(cols.mesh, PartitionSpec())), shift


def _kmeans_mb_segment(comm, mb, f, k):
    """ONE compiled chunk-update program for the mini-batch fit:
    ``(chunk, nvalid, it, centers, counts) -> (it+1, centers', counts')``.

    The chunk arrives row-sharded and zero-padded to ``ceil(mb/p)·p``
    rows; the program replicates it and computes on the static ``[:mb]``
    slice — a mesh-INDEPENDENT shape, so the center trajectory is
    bitwise-identical across mesh sizes (the elastic resume gate) at the
    cost of one small allgather per chunk.  Pad rows and the ragged final
    chunk are masked by the ``arange(mb) < nvalid`` valid-count row mask
    (the PR 4 pad discipline): a padded row contributes zero to every
    batch sum and count.  Keyed on ``(comm, mb, f, k)`` — one compile for
    the whole stream, every chunk one dispatch of this program."""
    from ..core._compile import jitted

    rep = comm.sharding(2, None)

    def make():
        def seg(chunk, nvalid, it, centers, counts):
            x = jax.lax.with_sharding_constraint(chunk, rep)[:mb]
            w = (jnp.arange(mb) < nvalid).astype(x.dtype)
            c2 = jnp.sum(centers * centers, axis=1)[None, :]
            labels = jnp.argmin(c2 - 2.0 * jnp.matmul(x, centers.T), axis=1)
            sel = jax.nn.one_hot(labels, k, dtype=x.dtype) * w[:, None]
            bsums = jnp.matmul(sel.T, x)  # (k, f) masked batch sum
            bcounts = jnp.sum(sel, axis=0)[:, None]  # (k, 1)
            counts2 = counts + bcounts
            # running-mean pull toward the batch mean, weighted by each
            # center's LIFETIME count: c += (bsum − bcount·c) / total
            nc = jnp.where(
                bcounts > 0.0,
                centers + (bsums - bcounts * centers) / jnp.maximum(counts2, 1.0),
                centers,
            )
            return it + 1, nc, counts2

        return seg

    return jitted(("kmeans.mb_seg", comm, mb, f, k), make)


def _kmeans_segment_q(arr, tol, stop, carry, *, comm, mode):
    """Lloyd's algorithm with the centroid-partial combine on the
    compressed ring: each segment is ONE compiled ``shard_map`` program
    over the row shards.  Each step's ``(k, f)`` masked sums ride the
    quantized ring while the ``(k,)`` counts stay exact (they divide the
    sums); the error-feedback residual is part of the ``while_loop``
    carry, so quantization noise on the partials does not bias the
    centroid trajectory.

    The carry is ``(it, centers, shift, error)`` with ``error`` in its
    host-visible stacked form ``(p, k*f)`` — one EF residual row per mesh
    position, sharded in and out over the mesh axis — precisely so the
    checkpointing driver can snapshot it between segments and a resumed
    fit replays the identical quantized trajectory."""
    from jax.sharding import PartitionSpec

    from ..comm.compressed import ring_allreduce_q_ef
    from ..core._compile import jitted
    from jax import shard_map

    n, f = int(arr.shape[0]), int(arr.shape[1])
    k = int(carry[1].shape[0])
    p = comm.size
    mesh, name = comm._mesh, comm.axis_name

    def make():
        def kernel(a, tol_, stop_, it0, c0, shift0, e0):
            def body(state):
                it, c, _, e = state
                c2 = jnp.sum(c * c, axis=1)[None, :]
                labels = jnp.argmin(c2 - 2.0 * jnp.matmul(a, c.T), axis=1)
                sel = jax.nn.one_hot(labels, k, dtype=a.dtype)
                sums = jnp.matmul(sel.T, a)  # (k, f) local partial
                # counts stay EXACT (they divide the centroid sums); only
                # the (k, f) sums ride the quantized ring, with the EF
                # residual carried in the loop state
                gcounts = jax.lax.psum(jnp.sum(sel, axis=0), name)[:, None]
                red, e2 = ring_allreduce_q_ef(
                    sums.reshape(-1), e, name, size=p, mode=mode
                )
                gsums = red.reshape(k, f)
                nc = jnp.where(gcounts > 0.5, gsums / jnp.maximum(gcounts, 1.0), c)
                return it + 1, nc, jnp.sum((nc - c) ** 2), e2

            def cond(state):
                it, _, shift, _ = state
                return jnp.logical_and(it < stop_, shift > tol_)

            init = (it0, c0, shift0, jnp.squeeze(e0, axis=0))
            it, c, shift, e = jax.lax.while_loop(cond, body, init)
            return it, c, shift, e[None]

        rep = PartitionSpec()

        def _f(a, tol_, stop_, it0, c0, shift0, e0):
            return shard_map(
                kernel,
                mesh=mesh,
                in_specs=(comm.spec(2, 0), rep, rep, rep, rep, rep, comm.spec(2, 0)),
                out_specs=(rep, rep, rep, PartitionSpec(name)),
                check_vma=False,
            )(a, tol_, stop_, it0, c0, shift0, e0)

        return _f

    fn = jitted(("kmeans.seg_q", comm, mode, n, f, k), make)
    it0, c0, shift0, e0 = carry
    return fn(arr, tol, stop, it0, c0, shift0, e0)


def _kmeans_finalize_q(arr, centers, *, comm):
    """Row-sharded labels + exact-psum inertia for the converged centers
    (the tail of the quantized fit, split out of the segment program)."""
    from jax.sharding import PartitionSpec

    from ..core._compile import jitted
    from jax import shard_map

    n, f = int(arr.shape[0]), int(arr.shape[1])
    k = int(centers.shape[0])
    mesh, name = comm._mesh, comm.axis_name

    def make():
        def kernel(a, c):
            c2 = jnp.sum(c * c, axis=1)[None, :]
            labels = jnp.argmin(c2 - 2.0 * jnp.matmul(a, c.T), axis=1)
            inertia = jax.lax.psum(jnp.sum((a - c[labels]) ** 2), name)
            return labels, inertia

        rep = PartitionSpec()

        def _f(a, c):
            return shard_map(
                kernel,
                mesh=mesh,
                in_specs=(comm.spec(2, 0), rep),
                out_specs=(PartitionSpec(name), rep),
                check_vma=False,
            )(a, c)

        return _f

    fn = jitted(("kmeans.fin_q", comm, n, f, k), make)
    return fn(arr, centers)
