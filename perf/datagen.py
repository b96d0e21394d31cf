"""The one general data generator: a configuration's ``data`` block in, one
device array out, made on the device in one jitted call from the seed.

Kinds (a later configuration picks one by name and brings only numbers):

- ``blobs``: ``rows x features`` float32; row ``i`` is centre ``i % centres``
  plus ``noise`` x standard normal; the centres are ``centre_scale`` x standard
  normal, drawn from the same seed.  (The reference harness's synthetic
  stand-in for its H5 file: ``benchmarks/kmeans/heat_tpu_bench.py``; rows are
  interleaved, not concatenated, so every shard holds every blob.)
- ``normal``: ``rows x features`` float32 standard normal.

The array is laid out over ``devices`` by rows (one device: unsharded), each
device drawing its own rows.  The same seed gives the same array on any
number of devices.
"""

from __future__ import annotations

import numpy as np


def seed_key(seed: int):
    """A jax PRNG key from any non-negative whole number (the driver's seeds
    pass 2**31): the low 31 bits seed the key, the rest is folded in."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def row_sharding(devices):
    """Rows over ``devices`` (a 1-D mesh named ``rows``)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(devices), ("rows",))
    return NamedSharding(mesh, PartitionSpec("rows", None))


#: a block of rows is drawn at a time, so that beside the array itself only
#: this much is live (the random bits of a whole 7.5 GB array are 7.5 GB more)
BLOCK_BYTES = 1 << 28


def make(data: dict, seed: int, devices):
    """The configuration's array, on ``devices``, from ``seed``."""
    return generator(data, devices)(seed_key(seed))


def generator(data: dict, devices):
    """The jitted program that makes the array from a key.  Row ``i`` has a
    key of its own (the key with ``i`` folded in), so the array does not
    depend on how many devices hold it or on the block size."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    kind = data["kind"]
    n, f = int(data["rows"]), int(data["features"])
    if n % len(devices):
        raise ValueError(f"{n} rows do not divide over {len(devices)} devices")
    local = n // len(devices)
    block = max(b for b in range(1, local + 1) if local % b == 0 and (b == 1 or b * f * 4 <= BLOCK_BYTES))

    def draw(key, index):  # a row of standard normals for each index, row i from the key with i folded in
        return jax.vmap(lambda i: jax.random.normal(jax.random.fold_in(key, i), (f,), jnp.float32))(index)

    if kind == "blobs":
        k = int(data["centres"])
        scale, noise = float(data["centre_scale"]), float(data["noise"])

        def table(key):
            return scale * jax.random.normal(jax.random.fold_in(key, n), (k, f), jnp.float32)

        def rows(key, centres, index):  # row i: centre i % k plus noise
            return centres[index % k] + noise * draw(key, index)

    elif kind == "normal":

        def table(key):
            return None

        def rows(key, _, index):
            return draw(key, index)

    else:
        raise ValueError(f"unknown data kind {kind!r} (known: blobs, normal)")

    sharding = row_sharding(devices)

    def shard(key):
        first = jax.lax.axis_index("rows") * local
        shared = table(key)
        if block == local:
            return rows(key, shared, first + jnp.arange(local))

        def step(i, out):
            part = rows(key, shared, first + i * block + jnp.arange(block))
            return jax.lax.dynamic_update_slice(out, part, (i * block, 0))

        return jax.lax.fori_loop(0, local // block, step, jnp.zeros((local, f), jnp.float32))

    gen = jax.shard_map(
        shard, mesh=sharding.mesh, in_specs=PartitionSpec(), out_specs=sharding.spec, check_vma=False
    )
    return jax.jit(gen, out_shardings=sharding)
