"""Production mesh construction.

A function (not module-level constant) so importing never touches jax
device state.  Shapes per the brief: single pod = (16, 16) (data, model)
= 256 chips; multi-pod = (2, 16, 16) (pod, data, model) = 512 chips.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the LM steps place activations with
    # ``with_sharding_constraint``, which refuses Explicit mesh axes.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
