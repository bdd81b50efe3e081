"""The jax mesh/sharding spellings the model/launch code uses, in one place.

Written against the installed jax (0.9): ``jax.make_mesh`` with explicit
``axis_types=``, ``jax.set_mesh`` and ``jax.sharding.get_abstract_mesh``.
Call sites import these names from here rather than from jax so that
the next rename is fixed in one module.
"""
from __future__ import annotations

import jax


def axis_type_auto():
    """``jax.sharding.AxisType.Auto``."""
    return jax.sharding.AxisType.Auto


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis typed Auto."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(axis_type_auto(),) * len(axis_names),
                         **kwargs)


def set_mesh(mesh):
    """Context manager activating ``mesh`` for the enclosed block."""
    return jax.set_mesh(mesh)


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a flat dict."""
    return compiled.cost_analysis()


def get_abstract_mesh():
    """The active abstract mesh. Callers treat a mesh whose ``.empty``
    is True (and None) as "no mesh active"."""
    return jax.sharding.get_abstract_mesh()
