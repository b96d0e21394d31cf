"""Shared helpers for the ports of the reference's harness scripts."""

from __future__ import annotations

import os
import sys


def bootstrap(parser):
    """Add the --devices flag, parse, configure a virtual CPU mesh when
    requested, and make the repo root importable.  Returns parsed args.

    The env-var route (JAX_PLATFORMS / --xla_force_host_platform_device_count)
    is not used because profile-level settings override inline env vars in
    some environments; jax.config.update before import always works.
    """
    parser.add_argument(
        "--devices", type=int, default=None,
        help="virtual CPU device count (development mesh)",
    )
    args = parser.parse_args()
    if args.devices:
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.devices)
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from heat_tpu.core._compile_cache import place_compile_cache

    place_compile_cache()
    return args
