"""Where operands live: the one fact the Pallas gates cannot trace.

A Mosaic kernel is not partitioned by GSPMD: lowering a ``pallas_call``
inside a jit whose operands span more than one device raises
``NotImplementedError: Mosaic kernels cannot be automatically
partitioned. Please wrap the call in a shard_map.`` The kernel gates
(``ops/{newton_kernel,segment_reduce}.kernel_supported``) run at trace
time on tracers, which carry no placement, so the caller that still
holds the CONCRETE arrays asks here and passes the answer down as a
static ``spmd`` argument (static, so a one-device and a mesh call of the
same shapes never share a trace).
"""

from __future__ import annotations

import jax


def spans_devices(tree) -> bool:
    """True when any concrete array leaf of ``tree`` is placed on more
    than one device (a mesh-sharded or mesh-replicated operand)."""
    return any(
        isinstance(leaf, jax.Array)
        and not isinstance(leaf, jax.core.Tracer)
        and len(leaf.sharding.device_set) > 1
        for leaf in jax.tree_util.tree_leaves(tree)
    )
