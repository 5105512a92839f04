"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
All mesh construction in this repo (production, tests, the SPMD engine)
builds ``jax.make_mesh`` meshes with explicit ``Auto`` axis types.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_production_mesh",
    "make_partition_mesh",
    "data_axes_of",
    "model_axis_of",
]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_partition_mesh(num_parts: int, axis_name: str = "parts"):
    """1-D mesh over ``num_parts`` devices for the SPMD engine's shard_map
    path: one graph partition per device.  Requires at least ``num_parts``
    visible devices; callers should use the stacked vmap path otherwise."""
    devices = jax.devices()
    if len(devices) < num_parts:
        raise ValueError(
            f"need {num_parts} devices for the partition mesh, "
            f"have {len(devices)}"
        )
    return jax.make_mesh((num_parts,), (axis_name,),
                         axis_types=(AxisType.Auto,),
                         devices=devices[:num_parts])


def data_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def model_axis_of(mesh) -> str | None:
    return "model" if "model" in mesh.axis_names else None
