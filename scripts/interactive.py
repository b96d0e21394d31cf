"""Interactive heat_tpu session.

Reference: scripts/interactive.py:13-34 — an MPI-synchronized REPL where
rank 0 reads input and broadcasts it to all ranks.  Single-controller SPMD
needs no input broadcast (one Python process drives the mesh), so this
reduces to a REPL with the framework pre-imported and the mesh reported.

Usage:  python scripts/interactive.py [--devices N]
        (--devices forces an N-device virtual CPU mesh for experimenting
        with sharding on a laptop)
"""

from __future__ import annotations

import argparse
import code
import os
import sys


def main():
    parser = argparse.ArgumentParser(description="interactive heat_tpu REPL")
    parser.add_argument("--devices", type=int, default=None,
                        help="virtual CPU device count (development mesh)")
    args = parser.parse_args()

    if args.devices:
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.devices)

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import heat_tpu as ht

    comm = ht.core.communication.get_comm()
    banner = (
        f"heat_tpu {ht.__version__} interactive session\n"
        f"mesh: {comm!r}\n"
        f"namespace: ht (the heat_tpu package)"
    )
    code.interact(banner=banner, local={"ht": ht})


if __name__ == "__main__":
    main()
