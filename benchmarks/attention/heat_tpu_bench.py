"""Sequence-parallel attention script, in the style of the reference's
harness scripts.

No reference analog (HeAT has no attention; SURVEY.md §5.7 maps its
communication mechanisms onto this toolkit).  Times exact causal/full
attention through the public ring formulation: on one TPU chip
the ring degenerates to the fused Pallas flash kernel; on a multi-device
mesh each ring round runs the flash partial update per device while K/V
blocks rotate on the ICI ring (``--local-kernel xla`` times the
GSPMD/XLA formulation instead).  No cell of the benchmark runs attention:
its rate on the chip is not measured.  Under ``--devices N`` (a virtual CPU
mesh) what this prints checks the code path and is no rate.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from _common import bootstrap


def main():
    parser = argparse.ArgumentParser(description="heat_tpu attention benchmark")
    parser.add_argument("--seq", type=int, default=4096)
    parser.add_argument("--heads", type=int, default=16)
    parser.add_argument("--dim", type=int, default=64)
    parser.add_argument("--trials", type=int, default=5)
    parser.add_argument("--causal", action="store_true")
    parser.add_argument(
        "--local-kernel", default="auto", choices=["auto", "flash", "xla"],
        help="per-device block engine (see ring_attention)",
    )
    parser.add_argument(
        "--dtype", default=None, choices=[None, "float32", "bfloat16"],
        help="default: bfloat16 on TPU, float32 elsewhere",
    )
    args = bootstrap(parser)

    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    S, H, D = args.seq, args.heads, args.dim
    dtype = args.dtype or ("bfloat16" if jax.default_backend() == "tpu" else "float32")
    rng = np.random.default_rng(0)
    comm = ht.get_comm()
    q, k, v = (
        comm.apply_sharding(
            jnp.asarray(rng.normal(size=(S, H, D)).astype(np.float32), dtype=dtype), 0
        )
        for _ in range(3)
    )

    def run():
        out = ht.parallel.ring_attention(
            q, k, v, causal=args.causal, comm=comm, local_kernel=args.local_kernel
        )
        jax.block_until_ready(out)  # attention is async like everything else

    run()  # warmup: compiles the ring/flash program
    times = []
    for _ in range(args.trials):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    best = min(times)
    flops = 4 * S * S * H * D / (2 if args.causal else 1)
    print(
        f"attention: S={S} H={H} D={D} dtype={dtype} causal={args.causal} "
        f"kernel={args.local_kernel} best={best:.4f}s "
        f"→ {S / best:.0f} tokens/s ({flops / best / 1e12:.1f} TFLOP/s)"
    )


if __name__ == "__main__":
    main()
