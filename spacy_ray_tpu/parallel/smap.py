"""shard_map, and the one rule for where a manual region sits.

Partial-manual shard_map (manual over only the axes in ``axis_names``,
every other mesh axis left to GSPMD) is what lets sharding constraints
keep working inside a manual region and lets regions nest over disjoint
axis sets: the pipeline (`pipe`), ring attention (`context`) and the
per-shard flash kernel (every axis) all build their region through
:func:`manual_region`, so they cannot drift apart on how nesting works.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from jax import shard_map
from jax.sharding import Mesh, get_abstract_mesh

__all__ = ["shard_map", "manual_region"]


def manual_region(mesh: Mesh, axes: Iterable[str]) -> Tuple[object, frozenset]:
    """``(mesh, axis_names)`` to hand ``shard_map`` for a region that must
    end up manual over ``axes``. At top level that is the concrete mesh and
    the axes as given. Inside an enclosing manual region, shard_map must
    receive the AMBIENT abstract mesh (whose enclosing axes are marked
    Manual), and only the axes that region left automatic."""
    ambient = get_abstract_mesh()
    if set(mesh.axis_names) <= set(ambient.axis_names):
        return ambient, frozenset(axes) - frozenset(ambient.manual_axes)
    return mesh, frozenset(axes)
