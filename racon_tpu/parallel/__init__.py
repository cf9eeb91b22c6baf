"""Multi-chip parallelism: window batches are sharded data-parallel over a
`jax.sharding.Mesh` (windows are independent POA problems — the reference's
multi-GPU batch striping, src/cuda/cudapolisher.cpp:165-180,228-240, maps to
batch-dim sharding over ICI; multi-host scales by sharding contigs/windows
over DCN with an ordered host gather, no collectives needed).

Layout: ``axes`` holds the logical-axis rule registry
(windows/query/depth/lane -> mesh axes), ``partitioner`` the mesh
discovery + Partitioner that wraps kernels via pjit/shard_map, ``mesh``
the legacy 1-D helpers."""

from .mesh import (  # noqa: F401
    device_mesh, divisible_batch, shard_batch_kernel)
from .partitioner import (  # noqa: F401
    Partitioner, build_mesh, get_partitioner, mesh_shape,
    reset_partitioner)
