"""SIRT — Simultaneous Iterative Reconstruction Technique.

x_{k+1} = x_k + lam * C (.) A^T [ R (.) (y - A x_k) ]

with R = 1/row-sums(A), C = 1/col-sums(A) computed matrix-free by projecting
constant images (the paper's memory-footprint point: the system matrix is
never materialized).  Relies on the *matched* A/A^T pair for convergence
stability over 1000+ iterations (paper §2.1).

Accepts a ``ProjectorSpec``, a ``Projector`` or a
:class:`~repro.core.distributed.DistributedProjector`; leading batch dims on
``y`` are reconstructed jointly (every update is elementwise or routed
through the batch-aware projector), which is what the serving layer packs
onto the lane axis.  Under a distributed projector the loop runs unbatched
on the mesh: the per-sample residual reductions are over *global* (sharded)
sinogram axes, so XLA inserts the cross-shard reduction and the history
matches the single-device run.  Returns a
:class:`~repro.recon.result.ReconResult`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.recon.result import ReconResult, as_projector

_EPS = 1e-6

_IMG_AXES = (-3, -2, -1)


def _res_norm(r):
    """Per-sample data-residual norm over the 3 sinogram axes."""
    return jnp.sqrt(jnp.sum(jnp.square(r), axis=_IMG_AXES))


def sirt(spec_or_projector, y, n_iters: int = 50, x0=None, lam: float = 1.0,
         nonneg: bool = True, mask=None) -> ReconResult:
    """Reconstruct from sinogram ``y``.  ``mask`` (optional, broadcastable to
    y) restricts the data term to measured rays (limited-angle / few-view)."""
    projector = as_projector(spec_or_projector)
    geom = projector.geom
    batch_dims = y.shape[:-3]
    ones_v = jnp.ones(geom.vol.shape, y.dtype)
    ones_s = jnp.ones(geom.sino_shape, y.dtype) if mask is None else mask
    row = projector(ones_v)                       # A 1
    col = projector.T(ones_s)                     # A^T 1 (masked)
    rinv = jnp.where(row > _EPS, 1.0 / jnp.maximum(row, _EPS), 0.0)
    cinv = jnp.where(col > _EPS, 1.0 / jnp.maximum(col, _EPS), 0.0)
    if mask is not None:
        rinv = rinv * mask
    x = (jnp.zeros(batch_dims + geom.vol.shape, y.dtype)
         if x0 is None else x0)

    def body(x, _):
        with jax.named_scope("sirt_iter"):
            r = y - projector(x)
            if mask is not None:
                r = r * mask
            x = x + lam * cinv * projector.T(rinv * r)
            if nonneg:
                x = jnp.maximum(x, 0.0)
            return x, _res_norm(r)

    x, hist = jax.lax.scan(body, x, None, length=n_iters)
    return ReconResult(image=x, iterations=n_iters,
                       residual_history=jnp.moveaxis(hist, 0, -1))
