"""The two mesh spellings shared by every plane (mesh serving in ``core/``,
training substrate in ``launch/``/``train/``, models), in the installed
jax's form.

Kept dependency-free (imports only jax) so no plane picks up another
plane's modules just to spell ``shard_map``.
"""

from __future__ import annotations

import jax


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the replication check (the mesh programs
    return per-device state under ``P(all_axes)`` specs)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def make_mesh_compat(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``-typed, over ``devices``
    (all devices present when None)."""
    return jax.make_mesh(
        axis_shapes,
        axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices,
    )
