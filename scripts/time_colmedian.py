"""Time ``core/_colmedian.py:group_medians`` alone on the chip, cluster size
by cluster size and method by method: the measurement behind
``_NETWORK_MAX``.

    <chip tool> --chips 1 -- python scripts/time_colmedian.py [--network-max 48 56]

The operand is the benchmark cell's, made on the device from ``--seed``:
300 x 6 291 456 float32 (7.55 GB, a tile of 1 024 lanes a row).  For each
split of its rows into clusters (:data:`SPLITS`), the pass with every cluster
on the counting passes (``_NETWORK_MAX`` patched to 1), as the module stands,
and with each ``--network-max`` given; every result is held to numpy's medians
on sampled columns, bit for bit.  One JSON line a timing, a copy in
``chiprun_out/time_colmedian.jsonl``.  Refuses to run without a TPU: a time
from the CPU says nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

ROWS, COLUMNS, CLUSTERS = 300, 6_291_456, 8
#: members of each cluster: the cell's blobs one to a cluster, what a random
#: start often settles on (blobs merged and split), and equal clusters of 50,
#: 75, 150 and all rows
SPLITS = {
    "8_blobs": (38, 38, 38, 38, 37, 37, 37, 37),
    "merged_and_split": (113, 38, 38, 37, 37, 14, 14, 9),
    "two_merged": (75, 75, 38, 37, 37, 23, 8, 7),
    "6x50": (50,) * 6,
    "4x75": (75,) * 4,
    "2x150": (150,) * 2,
    "1x300": (300,),
}
SAMPLE = 64
REPEATS = 5


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--network-max", type=int, nargs="*", default=[], help="also time these _NETWORK_MAX")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from heat_tpu.core import _colmedian
    from heat_tpu.core._compile_cache import place_compile_cache

    if jax.default_backend() != "tpu":
        print("no TPU: nothing timed", file=sys.stderr)
        return 1
    place_compile_cache()
    os.makedirs("chiprun_out", exist_ok=True)
    out = open(os.path.join("chiprun_out", "time_colmedian.jsonl"), "a")

    def say(**line):
        text = json.dumps(line)
        print(text, flush=True)
        out.write(text + "\n")
        out.flush()

    block = max(b for b in range(1, ROWS + 1) if ROWS % b == 0 and b * COLUMNS * 4 <= 1 << 28)

    @jax.jit
    def normal(key):  # a block of rows at a time: the bits of the whole array would be its size again
        def fill(i, x):
            part = jax.random.normal(jax.random.fold_in(key, i), (block, COLUMNS), jnp.float32)
            return jax.lax.dynamic_update_slice(x, part, (i * block, 0))

        return jax.lax.fori_loop(0, ROWS // block, fill, jnp.zeros((ROWS, COLUMNS), jnp.float32))

    x = normal(jax.random.key(args.seed))
    x.block_until_ready()
    rng = np.random.default_rng(args.seed)
    cols = np.sort(rng.choice(COLUMNS, size=SAMPLE, replace=False))
    host = np.concatenate([np.asarray(jax.lax.dynamic_slice_in_dim(x, int(c), 1, axis=1)) for c in cols], axis=1)
    stands = _colmedian._NETWORK_MAX
    for network_max in [1, stands, *args.network_max]:
        _colmedian._NETWORK_MAX = network_max  # read when the kernel is traced: a program of its own
        t0 = time.perf_counter()
        lowered = jax.jit(lambda a, lab: _colmedian.group_medians.__wrapped__(a, lab, CLUSTERS)).lower(
            x, jnp.zeros((ROWS,), jnp.int32)
        )
        t1 = time.perf_counter()
        run = lowered.compile()
        t2 = time.perf_counter()
        for name, sizes in SPLITS.items():
            labels = rng.permutation(np.repeat(np.arange(len(sizes)), sizes))
            lab = jnp.asarray(labels, jnp.int32)
            run(x, lab)[0].block_until_ready()
            times = []
            for _ in range(REPEATS):
                t = time.perf_counter()
                med, counts = run(x, lab)
                med.block_until_ready()
                times.append((time.perf_counter() - t) * 1e3)
            served = np.asarray(med[:, jnp.asarray(cols)])
            want = np.stack([np.median(host[labels == c], axis=0) for c in range(len(sizes))])
            say(
                split=name, members=list(sizes), network_max=network_max,
                pass_ms=sorted(times)[len(times) // 2], pass_ms_all=times,
                by_network=int(_colmedian.by_network(counts)),
                bitwise_equal_numpy=bool(np.array_equal(served[: len(sizes)], want)),
                trace_lower_s=t1 - t0, compile_s=t2 - t1, device=jax.devices()[0].device_kind,
            )
        del run, lowered
    _colmedian._NETWORK_MAX = stands
    return 0


if __name__ == "__main__":
    sys.exit(main())
