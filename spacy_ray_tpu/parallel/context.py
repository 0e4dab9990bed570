"""Active-mesh context: lets model code apply TP/CP sharding constraints.

The reference has no tensor/sequence parallelism at all (SURVEY.md §2.2
rows TP/SP: "NO"); here they are first-class mesh axes. Model code can't
take a mesh argument through the generic Model.apply signature, so the
train-step builder installs the mesh here and layers consult it:

* ``tp_active()``  — "model" axis > 1: shard attention heads + FFN dim
* ``context_parallel_active()`` — "context" axis > 1: ring attention +
  sequence-dim sharding
"""

from __future__ import annotations

import contextvars
from contextlib import contextmanager
from typing import Iterator, Optional

from jax.sharding import Mesh

# context-local (not process-global): concurrent traces over different
# meshes must not see each other's mesh
_MESH: "contextvars.ContextVar[Optional[Mesh]]" = contextvars.ContextVar(
    "spacy_ray_tpu_mesh", default=None
)


def current_mesh() -> Optional[Mesh]:
    return _MESH.get()


@contextmanager
def use_mesh(mesh: Optional[Mesh]) -> Iterator[None]:
    token = _MESH.set(mesh)
    try:
        yield
    finally:
        _MESH.reset(token)


def _axis_size(name: str) -> int:
    mesh = _MESH.get()
    if mesh is None:
        return 1
    return int(mesh.shape.get(name, 1))


def single_device() -> bool:
    """No mesh, or a mesh of one device: the only place a bare pallas_call
    may sit. A kernel has no GSPMD partitioning rule (the TPU lowering
    refuses it under a multi-device jit), so under a larger mesh a kernel
    either runs per shard inside a shard_map or yields to its XLA path."""
    mesh = _MESH.get()
    return mesh is None or int(mesh.size) == 1


def tp_active() -> bool:
    return _axis_size("model") > 1


def context_parallel_active() -> bool:
    return _axis_size("context") > 1


def pipeline_active() -> bool:
    return _axis_size("pipe") > 1
