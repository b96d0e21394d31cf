"""Tour of the DNDarray: sharding, resplit, reductions, linalg, IO.

    python examples/distributed_arrays.py --devices 8
"""

import argparse
import tempfile

parser = argparse.ArgumentParser()
parser.add_argument("--devices", type=int, default=None)
args = parser.parse_args()
if args.devices:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", args.devices)

import os, sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import heat_tpu as ht

# --- construction & sharding ------------------------------------------------
x = ht.arange(4 * 10**6, dtype=ht.float32, split=0)  # sharded over the mesh
print("x:", x.shape, "split:", x.split, "shards:", x.lshape_map[:, 0].tolist())

# --- elementwise + reductions: XLA inserts the collectives ------------------
y = ht.sin(x) ** 2 + ht.cos(x) ** 2
print("sin²+cos² mean:", float(y.mean()))  # == 1.0, via a cross-shard all-reduce

# --- resharding (the reference's resplit_, one XLA collective) --------------
m = ht.random.randn(512, 512, split=0)
mt = m.resplit(1)  # row-split → column-split: an all-to-all on the mesh
print("resplit:", m.split, "→", mt.split)

# --- distributed linalg -----------------------------------------------------
a = ht.random.randn(4096, 64, split=0)
q, r = ht.linalg.qr(a)  # TSQR over shards
print("qr residual:", float(ht.linalg.norm(q @ r - a)))
u, s, v = ht.linalg.svd(ht.random.randn(2048, 32, split=0))
print("top singular value:", float(s[0].item()))

# --- parallel IO ------------------------------------------------------------
with tempfile.TemporaryDirectory() as d:
    path = f"{d}/demo.h5"
    ht.save(m, path, "matrix")
    loaded = ht.load(path, "matrix", split=1)  # per-shard slab reads
    print("roundtrip max err:", float(ht.max(ht.abs(loaded - mt))))
