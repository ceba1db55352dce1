"""Multi-chip parallelism: device meshes for lane-sharded verification.

The reference's only scaling dimension is validator-set size N — O(N)
sequential signature verifies per phase (SURVEY.md §5 "long-context").
Here that dimension is laid out over a ``jax.sharding.Mesh`` whose ``dp``
axis shards the message lanes (the batch axis) across chips
(``verify/mesh_batch.py``); ``vp`` is kept in the mesh's shape for a
validator table sharded across chips, which no program uses at present.
"""

from .mesh import (
    make_mesh,
    mesh_context,
)

__all__ = [
    "make_mesh",
    "mesh_context",
]
