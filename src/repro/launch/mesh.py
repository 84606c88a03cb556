"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never
touches jax device state): 16×16 = 256 chips per pod on ("data",
"model"); the multi-pod variant adds a leading "pod" axis (2×16×16 =
512 chips).  DP runs over ("pod", "data"); TP/EP over "model"; the pod
axis is the slow (DCN-ish) dimension — only DP gradient reductions
cross it.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "dp_axes", "MODEL_AXIS"]

MODEL_AXIS = "model"


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the partitioner places what sharding constraints leave
    # open (jax 0.9 defaults to Explicit, which with_sharding_constraint
    # refuses)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """The data-parallel axes of a production mesh."""
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)
