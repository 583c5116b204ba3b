"""The one seam for jax APIs that have moved or changed behaviour.

The repo supports exactly the jax pinned in ``requirements-test.txt``
(0.9.0).  Every API below changed shape across recent releases and once
broke a whole test suite at call time; funneling it through this module
(RL003 enforces that) keeps the next change a one-line fix here instead of
a sweep across the tree.

* ``shard_map`` — now ``jax.shard_map`` with the ``check_vma`` kwarg.
* ``axis_size`` — ``jax.lax.axis_size`` inside ``shard_map``.
* ``tree_flatten_with_path`` / ``tree_map_with_path`` — the
  ``jax.tree_util`` spellings.
* ``Compiled.cost_analysis()`` — ``cost_analysis_dict`` returns a dict
  (``{}`` where a backend reports nothing).
* ``make_mesh`` — ``jax.make_mesh`` now defaults to ``Explicit`` axes,
  under which ``with_sharding_constraint`` and un-annotated reshapes of
  sharded arrays raise; this repo's code is written for ``Auto`` axes.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax

__all__ = [
    "shard_map",
    "axis_size",
    "tree_flatten_with_path",
    "tree_map_with_path",
    "cost_analysis_dict",
    "make_mesh",
]


def shard_map(f: Callable, mesh, in_specs, out_specs,
              check_vma: bool = True) -> Callable:
    """``jax.shard_map`` (``check_vma=False`` disables the varying-mesh-axes
    check, which the hand-written collectives here do not annotate)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(name) -> int:
    """Static size of a named mesh axis inside ``shard_map``."""
    return jax.lax.axis_size(name)


tree_flatten_with_path = jax.tree_util.tree_flatten_with_path
tree_map_with_path = jax.tree_util.tree_map_with_path


def cost_analysis_dict(compiled: Any) -> Dict[str, Any]:
    """``compiled.cost_analysis()`` as a flat dict (empty where the backend
    returns None)."""
    return compiled.cost_analysis() or {}


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence[Any]] = None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    auto = (jax.sharding.AxisType.Auto,) * len(shape)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=auto,
                         devices=devices)
