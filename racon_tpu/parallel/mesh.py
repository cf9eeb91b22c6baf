"""Mesh construction and batch-dim sharding for the device kernels.

The consensus and alignment workloads are embarrassingly parallel across
windows/overlap pairs, so the natural mesh is 1-D: every kernel input/output
carries a leading batch axis sharded over the `windows` mesh axis; XLA
partitions the program with zero collectives and results gather back to host
in order (the stitch loop is strictly ordered — reference:
src/polisher.cpp:510-537).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "windows"

def device_mesh(devices: Optional[Sequence] = None) -> Mesh:
    devs = list(devices if devices is not None else jax.devices())
    import numpy as np
    return Mesh(np.array(devs), (AXIS,))


def shard_batch_kernel(fn, mesh: Mesh, n_in: int):
    """jit `fn` with every one of its `n_in` array inputs (and all outputs)
    sharded on the leading batch dimension over the mesh."""
    batch = NamedSharding(mesh, P(AXIS))
    return jax.jit(fn, in_shardings=(batch,) * n_in,
                   out_shardings=batch)


def divisible_batch(n_devices: int, b: int) -> int:
    """Largest batch size <= max(b, n_devices) that divides evenly over the
    mesh.  LEGACY round-DOWN: remainder windows spilled to the slow path.
    The drivers now round UP via ``partitioner.Partitioner.pad_rows`` and
    count the padding in stats; kept for callers that need the old
    semantics (and for the regression test pinning the difference)."""
    return max(1, b // n_devices) * n_devices
