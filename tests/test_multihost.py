"""2-process multihost integration test (VERDICT r1 #6).

Boots a real ``jax.distributed`` cluster of two CPU processes (4 virtual
devices each, gloo cross-process collectives) and drives the public API
end-to-end through ``init_multihost``: sharded factory → reduction →
resplit → mixed-split matmul → fused KMeans fit → HDF5 save/load — the
flow the reference runs under ``mpirun -n 2``
(reference heat/core/tests/test_communication.py + test_io.py).

Each worker also asserts HONEST per-process metadata: ``comm.rank`` is the
process index, and ``lshape`` comes from the calling process's first mesh
position, not position 0.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRELUDE = r"""
import os, sys
pid = int(sys.argv[1]); port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
# overrides the device count inherited from the test session's XLA_FLAGS
os.environ["JAX_NUM_CPU_DEVICES"] = "4"
os.environ["HEAT_TPU_DISABLE_X64"] = "1"
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
sys.path.insert(0, {repo!r})
import heat_tpu as ht
comm = ht.init_multihost(f"127.0.0.1:{{port}}", num_processes=2, process_id=pid)
"""

WORKER = PRELUDE + r"""
import numpy as np
assert comm.size == 8, comm.size
assert jax.process_count() == 2
# honest multihost metadata
assert comm.rank == pid, (comm.rank, pid)
assert comm.local_position() == pid * 4, comm.local_position()
X = ht.arange(24, dtype=ht.float32, split=0)
assert float(X.sum()) == 276.0
assert X.lshape == (3,), X.lshape  # 24 rows / 8 devices, caller's shard
Y = X.reshape((4, 6)).resplit(1)
assert abs(float(Y.mean()) - 11.5) < 1e-5
# mixed-split matmul crosses process boundaries
A = ht.random.randn(16, 8, split=0)
B = ht.random.randn(8, 16, split=1)
n = float(ht.linalg.norm(A @ B))
assert np.isfinite(n) and n > 0
# fused estimator fit on a process-spanning mesh
data = np.random.default_rng(0).normal(size=(64, 4)).astype(np.float32)
km = ht.cluster.KMeans(n_clusters=3, random_state=0).fit(ht.array(data, split=0))
assert km.n_iter_ >= 1
# save/load round-trip: process 0 writes slabs, barrier, all read shards
p = sys.argv[3]
ht.save_hdf5(X.reshape((4, 6)), p, "var")
Z = ht.load_hdf5(p, "var", split=0)
assert float(Z.sum()) == 276.0
lmap = Z.lshape_map[:, 0].tolist()
assert lmap == [1, 1, 1, 1, 0, 0, 0, 0], lmap  # ceil-division of 4 over 8
# r4: ragged padded-at-rest storage spanning both processes — elementwise
# chain, masked reduction, split-axis cumsum, and the distributed sort all
# run on the padded buffers with the cluster in lockstep
R = ht.arange(19, dtype=ht.float32, split=0)  # 19 over 8 devices: ragged
assert R.padshape == (24,), R.padshape
assert float(R.sum()) == 171.0
assert float((R * 2.0 + 1.0).sum()) == 2.0 * 171.0 + 19.0
assert abs(float(R.mean()) - 9.0) < 1e-5  # pad rows excluded
cs = R.cumsum(0)
assert float(cs.max()) == 171.0
v, idx = ht.sort(-1.0 * R)
assert float(v.sum()) == -171.0 and float(v.min()) == -18.0
# r4: ring take/put fancy indexing across the process boundary
from heat_tpu.core import dndarray as _dnd
_dnd._RING_INDEX_MIN = 0
perm = np.random.default_rng(1).permutation(19)
taken = R[perm]
assert float(taken.sum()) == 171.0
back = ht.zeros_like(R)
back[perm] = taken
assert float(abs(back - R).sum()) == 0.0
# r4: estimator checkpoint across processes — ONE writer barrier for all
# datasets + manifest, every process loads the restored layout
ckpt = sys.argv[3] + ".est.h5"
km.save(ckpt)
km2 = ht.load_estimator(ckpt)
assert type(km2).__name__ == "KMeans"
assert km2.labels_.split == 0
assert float(abs(km2.cluster_centers_ - km.cluster_centers_).sum()) < 1e-5
print(f"proc {{pid}} OK", flush=True)
"""


def test_two_process_cluster(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER.format(repo=REPO))
    h5 = str(tmp_path / "mh.h5")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), str(port), h5],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-4000:]}"
        assert f"proc {i} OK" in out


FAIL_WORKER = PRELUDE + r"""
X = ht.arange(24, dtype=ht.float32, split=0)
# the save target is an unwritable path: the WRITER (process 0) fails to
# open it; the error flag must reach process 1 too (ADVICE r2: before the
# fix only process 0 raised and the cluster diverged)
failed = False
try:
    ht.save_hdf5(X, sys.argv[3], "var")
except Exception:
    failed = True
assert failed, f"proc {{pid}} did not see the writer failure"
# the cluster is still in lockstep: a collective completes afterwards
assert float(X.sum()) == 276.0
print(f"proc {{pid}} SAWFAIL", flush=True)
"""


def test_writer_failure_raises_on_every_process(tmp_path):
    """A failed save must raise on ALL processes, not just the writer."""
    worker = tmp_path / "failworker.py"
    worker.write_text(FAIL_WORKER.format(repo=REPO))
    bad = str(tmp_path / "no_such_dir" / "out.h5")  # parent doesn't exist
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), str(port), bad],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-4000:]}"
        assert f"proc {i} SAWFAIL" in out
