"""CGLS — conjugate gradient on the normal equations A^T A x = A^T y.

Mathematically requires the backprojector to be the *exact* adjoint of the
forward projector; with unmatched pairs CG diverges (Zeng & Gullberg 2000) —
this is exactly the paper's argument for matched pairs.  Supports Tikhonov
damping: min ||Ax - y||^2 + damp ||x||^2.

Accepts a ``ProjectorSpec``, a ``Projector`` or a
:class:`~repro.core.distributed.DistributedProjector`.  Leading batch dims
on ``y`` run independent CG iterations side by side: every inner product
reduces over the trailing image/sinogram axes only (keepdims, so the
per-sample step sizes broadcast), which keeps a packed serving batch
mathematically identical to solving each request alone.  The same
reductions stay correct under a distributed projector — they run on global
(sharded) arrays, so the CG scalars are mesh-wide inner products, exactly
as CG requires.  Returns a :class:`~repro.recon.result.ReconResult`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.recon.result import ReconResult, as_projector

_IMG_AXES = (-3, -2, -1)


def _dot(a, b):
    """Per-sample inner product over the 3 trailing axes, kept broadcastable."""
    return jnp.sum(a * b, axis=_IMG_AXES, keepdims=True)


def cgls(spec_or_projector, y, n_iters: int = 30, x0=None,
         damp: float = 0.0, mask=None) -> ReconResult:
    projector = as_projector(spec_or_projector)
    A = (lambda x: projector(x) * mask) if mask is not None else projector
    AT = (lambda r: projector.T(r * mask)) if mask is not None else projector.T

    batch_dims = y.shape[:-3]
    x = (jnp.zeros(batch_dims + projector.vol_shape(), y.dtype)
         if x0 is None else x0)
    r = y - A(x)
    if mask is not None:
        r = r * mask
    s = AT(r) - damp * x
    p = s
    gamma = _dot(s, s)

    def body(carry, _):
        with jax.named_scope("cgls_iter"):
            x, r, p, gamma = carry
            q = A(p)
            delta = _dot(q, q) + damp * _dot(p, p)
            alpha = gamma / jnp.maximum(delta, 1e-30)
            x = x + alpha * p
            r = r - alpha * q
            s = AT(r) - damp * x
            gamma_new = _dot(s, s)
            beta = gamma_new / jnp.maximum(gamma, 1e-30)
            p = s + beta * p
            res = jnp.sqrt(jnp.sum(jnp.square(r), axis=_IMG_AXES))
            return (x, r, p, gamma_new), res

    (x, _, _, _), hist = jax.lax.scan(body, (x, r, p, gamma), None,
                                      length=n_iters)
    return ReconResult(image=x, iterations=n_iters,
                       residual_history=jnp.moveaxis(hist, 0, -1))
