"""Mesh construction for the one JAX the repo runs on (0.9).

``jax.make_mesh`` defaults every axis to ``Explicit`` sharding; the
engines' ``shard_map`` programs are written for ``Auto`` axes, so every
mesh in the repo is built here.
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes: tuple[int, ...], axis_names: tuple[str, ...],
              devices=None):
    """An Auto-typed mesh over every device, or over ``devices``.

    ``devices`` selects an explicit device subset (e.g. meshes of 1/2/4
    devices on an 8-device host for device-count scaling benchmarks) —
    ``jax.make_mesh`` requires the product of ``axis_shapes`` to cover
    every addressable device, so subsets build a plain ``Mesh``."""
    if devices is not None:
        import numpy as np
        return jax.sharding.Mesh(
            np.asarray(devices).reshape(axis_shapes), axis_names)
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
