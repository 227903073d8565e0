"""Thin wrappers over the JAX APIs the sharded paths share.

The repo targets the installed JAX (0.9).  Every shard_map, mesh and
cost-analysis call goes through this module, so a later change of those
APIs is made in one place.
"""
from __future__ import annotations

from typing import Any, Callable, Iterable, Sequence

import jax

__all__ = [
    "shard_map",
    "abstract_mesh",
    "make_mesh",
    "mesh_axis_sizes",
    "cost_analysis",
]


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------

def shard_map(f: Callable, mesh: Any, in_specs: Any, out_specs: Any, *,
              axis_names: Iterable[str] | None = None,
              check: bool = False) -> Callable:
    """Partial-manual shard_map over ``axis_names`` (all mesh axes if None).

    ``check`` maps to ``check_vma``.  Axes not in ``axis_names`` stay
    automatic.
    """
    manual = (set(axis_names) if axis_names is not None
              else set(mesh.axis_names))
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=check)


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------

def abstract_mesh(axis_shapes: Sequence[int],
                  axis_names: Sequence[str]) -> Any:
    """``AbstractMesh`` from parallel ``(sizes, names)`` tuples."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(int(s) for s in axis_shapes), tuple(axis_names))


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              **kwargs: Any) -> Any:
    """``jax.make_mesh`` with every axis automatic unless ``axis_types``
    says otherwise."""
    axis_names = tuple(axis_names)
    kwargs.setdefault("axis_types",
                      (jax.sharding.AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(tuple(int(s) for s in axis_shapes), axis_names,
                         **kwargs)


def mesh_axis_sizes(mesh: Any) -> dict[str, int]:
    """{axis name: size} for ``Mesh`` and ``AbstractMesh`` alike."""
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def cost_analysis(compiled: Any) -> dict:
    """``compiled.cost_analysis()`` as a plain dict."""
    return dict(compiled.cost_analysis())
