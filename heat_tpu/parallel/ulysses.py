"""Ulysses (DeepSpeed-style) sequence parallelism: all-to-all attention.

Where ring attention rotates K/V blocks, Ulysses re-shards: the input
arrives sequence-sharded, an all-to-all swaps the sharded axis from
sequence to heads, every device then computes *full-sequence* attention
for its own heads with zero communication, and a second all-to-all swaps
back.  The sharded-axis swap is exactly the framework's ``resplit``
(reference dndarray.py:2801-2921 — the Alltoallv axis swap, SURVEY.md §5.7);
expressed on global arrays it is two sharding constraints and GSPMD emits
the all-to-alls over ICI.

No reference analog (HeAT has no attention); included because long-context
sequence parallelism is a first-class capability of this framework.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import pcast
from jax.sharding import NamedSharding, PartitionSpec

from ..core._compile import jitted
from ..core.communication import XlaCommunication, get_comm
from ..core.dndarray import DNDarray

__all__ = ["ulysses_attention"]


def _attention(q, k, v, causal: bool):
    """Plain exact attention on (B, S, H, D) with full sequence visible —
    one shared implementation (flash_attention's XLA path) carrying the
    f32-accumulator and matmul-precision conventions."""
    from .flash_attention import _jnp_fallback

    return _jnp_fallback(q, k, v, causal)


def ulysses_attention(
    q,
    k,
    v,
    causal: bool = False,
    comm: Optional[XlaCommunication] = None,
    local_kernel: str = "auto",
) -> jax.Array:
    """Exact attention over sequence-sharded (seq, heads, dim) — or
    (batch, seq, heads, dim) — inputs via the head↔sequence all-to-all.

    Requires ``heads`` divisible by the mesh size (the Ulysses constraint);
    falls back to plain attention (GSPMD-planned) otherwise.  The sequence
    axis need not be divisible — the all-to-all path additionally needs it
    to be, else the fallback also applies.

    ``local_kernel`` picks the comm-free full-sequence engine each device
    runs after the head swap (mirrors ring_attention):
    - ``"auto"``: the fused Pallas flash kernel on TPU when the full
      sequence conforms (S a multiple of 128, f32/bf16, K/V within the
      VMEM budget) — via an explicit shard_map whose two
      ``lax.all_to_all``s do the head↔sequence swap; else the GSPMD
      two-constraint formulation with the XLA attention;
    - ``"flash"``: force the shard_map+Pallas program (interpreted
      off-TPU — the CPU suite's path);
    - ``"xla"``: force the GSPMD formulation.
    """
    if local_kernel not in ("auto", "flash", "xla"):
        raise ValueError(f"local_kernel must be auto|flash|xla, got {local_kernel!r}")
    if isinstance(q, DNDarray):
        comm = comm or q.comm
        q, k, v = q.larray, k.larray, v.larray
    comm = comm or get_comm()
    size = comm.size

    batched = q.ndim == 4
    if not batched:
        q, k, v = q[None], k[None], v[None]  # (1, S, H, D)
    B, S, H, D = q.shape

    mesh, name = comm.mesh, comm.axis_name
    seq_sh = NamedSharding(mesh, PartitionSpec(None, name, None, None))
    head_sh = NamedSharding(mesh, PartitionSpec(None, None, name, None))

    from .flash_attention import conforms, flash_attention

    if size == 1 or H % size != 0 or S % size != 0:
        # single device or non-Ulysses shapes.  The local_kernel contract
        # holds here too: 'flash' may not silently become XLA
        if local_kernel == "flash" and (
            size > 1 or not conforms(S, D, q.dtype)
        ):
            raise ValueError(
                "local_kernel='flash' needs heads and sequence divisible "
                f"by the mesh (H={H}, S={S}, {size} devices) and a "
                "conforming sequence (128-multiple, f32/bf16, within the "
                "VMEM budget); use 'auto' for the silent fallback"
            )
        if size == 1 and local_kernel != "xla":
            # flash gates its own off-TPU/VMEM fallback; only engage it
            # when nothing is sharded (a Pallas call on a GSPMD-sharded
            # global would force a gather).  'flash' forces the Pallas
            # kernel (interpreted off-TPU) per the documented contract
            out = flash_attention(
                q, k, v, causal=causal,
                interpret=(
                    local_kernel == "flash"
                    and jax.default_backend() != "tpu"
                ),
            )
        else:
            # cached: a fresh jax.jit object per call would recompile
            key = ("ulysses.fallback", causal, B, S, H, D, str(q.dtype))
            out = jitted(
                key, lambda: (lambda a, b, c: _attention(a, b, c, causal))
            )(q, k, v)
        return out if batched else out[0]

    on_tpu = jax.default_backend() == "tpu"

    conforming = conforms(S, D, q.dtype)
    if local_kernel == "flash" and not conforming:
        raise ValueError(
            f"local_kernel='flash' needs a conforming sequence (S={S} must "
            "be a multiple of 128, dtype f32/bf16, K/V within the VMEM "
            "budget); use 'auto' for the silent fallback"
        )
    use_flash = local_kernel == "flash" or (
        local_kernel == "auto" and on_tpu and conforming
    )

    if use_flash:
        interp = not on_tpu  # CPU test suite: Pallas interpreter
        spec = PartitionSpec(None, name, None, None)

        def make_flash():
            def kern(qb, kb, vb):  # local (B, L, H, D)
                # seq→head swap as ONE explicit all-to-all per operand
                # (the same collective GSPMD emits for the
                # two-constraint form)
                qh, kh, vh = (
                    jax.lax.all_to_all(
                        t, name, split_axis=2, concat_axis=1, tiled=True
                    )
                    for t in (qb, kb, vb)
                )  # (B, S, H/p, D): full sequence per device
                # causal rides the triangular-schedule kernel: each
                # q-block program folds only k-chunks at or below its
                # diagonal, so causal costs ~half of full attention here
                out = flash_attention(qh, kh, vh, causal=causal, interpret=interp)
                # head→seq swap back to the caller's layout
                return jax.lax.all_to_all(
                    out, name, split_axis=1, concat_axis=2, tiled=True
                )

            # check_vma=False: pallas_call under shard_map — see the
            # identical note in ring_attention
            return shard_map(
                kern, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )

        # cached per config (a fresh jax.jit object per call would
        # recompile the whole program on every invocation)
        key = ("ulysses.flash", comm, causal, B, S, H, D, str(q.dtype))
        out = jitted(key, make_flash)(
            *(jax.device_put(t, seq_sh) for t in (q, k, v))
        )
        return out if batched else out[0]

    def make_xla():
        def kernel(q, k, v):
            # seq-sharded → head-sharded: GSPMD emits one all-to-all
            # per operand
            q_h, k_h, v_h = (
                jax.lax.with_sharding_constraint(t, head_sh) for t in (q, k, v)
            )
            out = _attention(q_h, k_h, v_h, causal)  # full seq per head
            # back to the caller's sequence sharding
            return jax.lax.with_sharding_constraint(out, seq_sh)

        return kernel

    key = ("ulysses.xla", comm, causal, B, S, H, D, str(q.dtype))
    q, k, v = (jax.device_put(t, seq_sh) for t in (q, k, v))
    out = jitted(key, make_xla)(q, k, v)
    return out if batched else out[0]
