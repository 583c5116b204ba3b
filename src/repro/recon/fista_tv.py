"""FISTA with total-variation regularization:

    min_x  0.5 ||A x - y||^2 + beta * TV(x)

Gradient step through the matched pair (the gradient of the data term is
exactly A^T(Ax - y)); TV proximal step via the dual (Chambolle-style)
projection, a fixed small number of inner iterations.  The Lipschitz constant
of A^T A is estimated matrix-free by power iteration.

Accepts a ``ProjectorSpec`` or a ``Projector``.  All TV operators address
the trailing (nx, ny, nz) axes, so leading batch dims on ``y`` solve a
packed batch of independent problems (the momentum schedule t_k is
data-independent and shared).  Returns a
:class:`~repro.recon.result.ReconResult`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.recon.result import ReconResult, as_projector

_IMG_AXES = (-3, -2, -1)


def _pad_spec(ndim, axis, before, after):
    spec = [(0, 0)] * ndim
    spec[axis] = (before, after)
    return spec


def tv_norm(x):
    """Anisotropic TV over the trailing volume axes (per-sample for batches)."""
    dx = jnp.diff(x, axis=-3)
    dy = jnp.diff(x, axis=-2)
    out = (jnp.abs(dx).sum(axis=_IMG_AXES)
           + jnp.abs(dy).sum(axis=_IMG_AXES))
    if x.shape[-1] > 1:
        out = out + jnp.abs(jnp.diff(x, axis=-1)).sum(axis=_IMG_AXES)
    return out


def _grad_op(x):
    gx = jnp.pad(jnp.diff(x, axis=-3), _pad_spec(x.ndim, -3, 0, 1))
    gy = jnp.pad(jnp.diff(x, axis=-2), _pad_spec(x.ndim, -2, 0, 1))
    return gx, gy


def _div_op(px, py):
    dx = px - jnp.pad(px[..., :-1, :, :], _pad_spec(px.ndim, -3, 1, 0))
    dy = py - jnp.pad(py[..., :, :-1, :], _pad_spec(py.ndim, -2, 1, 0))
    return dx + dy


def tv_prox(x, weight, n_inner: int = 10):
    """prox_{weight * TV}(x) via dual projection (2D TV applied per z-slice)."""
    tau = 0.25

    def body(carry, _):
        px, py = carry
        gx, gy = _grad_op(_div_op(px, py) * weight - x / jnp.maximum(weight, 1e-12))
        # normalize dual step
        px = px - tau * gx
        py = py - tau * gy
        mag = jnp.maximum(1.0, jnp.sqrt(px ** 2 + py ** 2))
        return (px / mag, py / mag), 0

    p0 = (jnp.zeros_like(x), jnp.zeros_like(x))
    (px, py), _ = jax.lax.scan(body, p0, None, length=n_inner)
    return x - weight * _div_op(px, py)


def power_iteration(spec_or_projector, n_iters: int = 10, seed: int = 0):
    """Largest eigenvalue of A^T A (matrix-free)."""
    projector = as_projector(spec_or_projector)
    x = jax.random.normal(jax.random.PRNGKey(seed), projector.vol_shape())

    def body(x, _):
        z = projector.T(projector(x))
        nrm = jnp.linalg.norm(z.ravel())
        return z / jnp.maximum(nrm, 1e-30), nrm

    x, hist = jax.lax.scan(body, x, None, length=n_iters)
    return hist[-1]


def fista_tv(spec_or_projector, y, n_iters: int = 50, beta: float = 1e-3,
             x0=None, mask=None, L=None, nonneg: bool = True,
             tv_inner: int = 10) -> ReconResult:
    projector = as_projector(spec_or_projector)
    if L is None:
        # The Lipschitz constant of A^T A is a property of the operator, not
        # the data — one unbatched power iteration covers a packed batch.
        L = power_iteration(projector) * 1.05
    step = 1.0 / L
    batch_dims = y.shape[:-3]
    x = (jnp.zeros(batch_dims + projector.vol_shape(), y.dtype)
         if x0 is None else x0)
    z, t = x, jnp.asarray(1.0, y.dtype)

    def body(carry, _):
        x, z, t = carry
        with jax.named_scope("fista_tv_iter"):
            r = projector(z) - y
            if mask is not None:
                r = r * mask
            g = projector.T(r)
            xn = tv_prox(z - step * g, beta * step, tv_inner)
            if nonneg:
                xn = jnp.maximum(xn, 0.0)
            tn = (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)) / 2.0
            zn = xn + ((t - 1.0) / tn) * (xn - x)
            res = jnp.sqrt(jnp.sum(jnp.square(r), axis=_IMG_AXES))
            return (xn, zn, tn), res

    (x, _, _), hist = jax.lax.scan(body, (x, z, t), None, length=n_iters)
    return ReconResult(image=x, iterations=n_iters,
                       residual_history=jnp.moveaxis(hist, 0, -1))
