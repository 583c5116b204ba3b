"""Production mesh construction.

A *function*, not a module-level constant: importing this module never
touches jax device state (required so smoke tests see 1 device while the
dry-run sees 512 placeholder devices via XLA_FLAGS).

Mesh layout (TPU v5e pods):
    single pod : (16, 16)      -> ('data', 'model')      = 256 chips
    multi pod  : (2, 16, 16)   -> ('pod', 'data', 'model') = 512 chips

'model' is the tensor/expert-parallel axis (fast ICI dimension); 'data' is
data/FSDP; 'pod' is pure-DP across the slower inter-pod links (gradient
all-reduce only, overlappable with the backward pass).
"""
from __future__ import annotations

import jax
import numpy as np

from repro import compat


def make_production_mesh(*, multi_pod: bool = False, shape=None):
    """Default production mesh is (16,16) / (2,16,16).  ``shape`` overrides
    the logical split over the same chips (perf experiments, e.g. (64,4)
    for sub-3B models where TP=16 is collective-bound — see §Perf)."""
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model")[-len(shape):]
    return compat.make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Tiny mesh over whatever devices exist (tests / CPU smoke training)."""
    n = jax.device_count()
    data = n // model_axis
    return compat.make_mesh((data, model_axis), ("data", "model"))


def data_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh ('pod' folds into DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))


def tp_size(mesh) -> int:
    return int(mesh.shape.get("model", 1))
