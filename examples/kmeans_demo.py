"""KMeans on sharded synthetic blobs — the 60-second tour.

Run anywhere:
    python examples/kmeans_demo.py              # real accelerator (or 1 CPU)
    python examples/kmeans_demo.py --devices 8  # virtual 8-device CPU mesh
"""

import argparse

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

print(f"mesh: {ht.core.communication.get_comm()!r}")

# 200k samples, row-sharded (data parallel) across the mesh
rng = np.random.default_rng(0)
centers = rng.normal(scale=10, size=(4, 8)).astype(np.float32)
data = np.concatenate([c + rng.normal(size=(50_000, 8)).astype(np.float32) for c in centers])
X = ht.array(data, split=0)
print(f"X: shape={X.shape} split={X.split} dtype={X.dtype.__name__}")

km = ht.cluster.KMeans(n_clusters=4, init="probability_based", random_state=0)
km.fit(X)
print(f"converged in {km.n_iter_} iterations, inertia={km.inertia_:.1f}")
print("recovered centers (rounded):")
print(np.round(km.cluster_centers_.numpy(), 1))
