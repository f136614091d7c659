"""Mesh and ``shard_map`` helpers shared by every multi-device call site.

Written against the installed JAX line (0.9): one place fixes the
defaults the repo wants everywhere —

  * ``make_mesh`` builds meshes whose axes are all ``AxisType.Auto``;
  * ``shard_map`` runs with replication checks off (``check_vma=False``)
    and takes ``axis_names`` as the set of MANUAL axes;
  * ``use_mesh`` / ``current_mesh`` / ``mesh_axis_names`` /
    ``mesh_shape`` read and install the ambient mesh.

float64 scoping needs no helper: ``jax.enable_x64()`` is a thread-local
context manager.
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kw)


def shard_map(fn, *, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` with replication checks off.

    ``axis_names``: the MANUAL axes (None → all mesh axes manual).
    """
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kw)


def use_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.sharding.set_mesh(mesh)


def current_mesh():
    """The ambient (abstract) mesh, or None outside any mesh context."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or not m.axis_names:
        return None
    return m


def mesh_axis_names(auto_only: bool = False) -> tuple:
    """Names of the ambient mesh axes; ``auto_only`` drops manual axes."""
    m = current_mesh()
    if m is None:
        return ()
    names = tuple(m.axis_names)
    if not auto_only:
        return names
    auto = jax.sharding.AxisType.Auto
    return tuple(n for n, t in zip(names, m.axis_types) if t == auto)


def mesh_shape() -> dict:
    """{axis: size} of the ambient mesh ({} when there is none)."""
    m = current_mesh()
    return {} if m is None else dict(m.shape)
