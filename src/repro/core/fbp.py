"""Filtered backprojection (parallel + fan beam) and FDK (cone beam).

The backprojection used here is the *textbook interpolation backprojector*
(sample the filtered projection at each voxel's detector coordinate), which
gives quantitatively correct values in 1/mm.  It is implemented as its own
vectorized jnp routine rather than reusing the adjoint A^T: the adjoint of
the SF/Joseph forward model carries path-length weights that are correct for
gradients but not for the FBP inversion formula.

For non-equispaced angles the per-view quadrature weight is half the angular
distance between its neighbours (trapezoid rule), matching the paper's
"non-equispaced projection angles" support.

Building the host tables (coordinates, interpolation matrix, quadrature
weights) is the span ``fbp.tables``.  On the device, ``fbp_parallel``'s
stages run under the scopes ``fbp_filter`` (the ramp filter), and in each
view of the scan ``fbp_interp`` (the two gathers and the lerp),
``fbp_zcontract`` (the detector-row to z contraction) and ``fbp_accum``.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.filters import filter_sinogram
from repro.core.geometry import CTGeometry
from repro.runtime import obs

_EPS = 1e-9


def _angle_weights(angles: np.ndarray, full_range: float) -> np.ndarray:
    """Trapezoid quadrature weights d_phi for (possibly) non-equispaced views."""
    n = len(angles)
    if n == 1:
        return np.asarray([full_range], dtype=np.float32)
    order = np.argsort(angles)
    srt = np.asarray(angles)[order]
    gaps = np.diff(srt)
    w = np.empty(n)
    w[0] = gaps[0] / 2 + (full_range - (srt[-1] - srt[0])) / 2
    w[-1] = gaps[-1] / 2 + (full_range - (srt[-1] - srt[0])) / 2
    w[1:-1] = (gaps[:-1] + gaps[1:]) / 2
    out = np.empty(n)
    out[order] = w
    return out.astype(np.float32)


def _lerp_matrix(src_coords: np.ndarray, dst_coords: np.ndarray) -> np.ndarray:
    """(n_src, n_dst) dense linear-interpolation matrix (zero outside range)."""
    n_src = len(src_coords)
    d = src_coords[1] - src_coords[0] if n_src > 1 else 1.0
    pos = (dst_coords - src_coords[0]) / d
    j = np.floor(pos).astype(int)
    w = pos - j
    M = np.zeros((n_src, len(dst_coords)), dtype=np.float32)
    for k, (jj, ww) in enumerate(zip(j, w)):
        if 0 <= jj < n_src:
            M[jj, k] += 1 - ww
        if 0 <= jj + 1 < n_src:
            M[jj + 1, k] += ww
    return M


def fbp_parallel(sino, geom: CTGeometry, filter_name: str = "ramp"):
    v = geom.vol
    nx, ny, nz = v.shape
    nu, nv = geom.n_cols, geom.n_rows
    with jax.named_scope("fbp_filter"):
        q = filter_sinogram(sino, geom.pixel_width, filter_name)  # (na, nv, nu)
    with obs.span("fbp.tables"):
        X = jnp.asarray(np.repeat(v.x_coords(), ny))             # (nxy,)
        Y = jnp.asarray(np.tile(v.y_coords(), nx))
        u0, du = float(geom.u_coords()[0]), geom.pixel_width
        Lz = jnp.asarray(_lerp_matrix(geom.v_coords(), v.z_coords()))
        wts = jnp.asarray(_angle_weights(geom.angles_array(), np.pi))
        angs = jnp.asarray(geom.angles_array())

    def one(acc, inp):
        ang, w, qa = inp                                         # qa (nv, nu)
        with jax.named_scope("fbp_interp"):
            c, s = jnp.cos(ang), jnp.sin(ang)
            ui = (Y * c - X * s - u0) / du                       # (nxy,)
            j = jnp.floor(ui).astype(jnp.int32)
            t = ui - j
            ok0 = (j >= 0) & (j < nu)
            ok1 = (j + 1 >= 0) & (j + 1 < nu)
            g0 = jnp.take(qa, jnp.clip(j, 0, nu - 1), axis=1)    # (nv, nxy)
            g1 = jnp.take(qa, jnp.clip(j + 1, 0, nu - 1), axis=1)
            S = g0 * jnp.where(ok0, 1 - t, 0.0) + g1 * jnp.where(ok1, t, 0.0)
        with jax.named_scope("fbp_zcontract"):
            vol = jnp.einsum("vq,vz->qz", S, Lz).reshape(nx, ny, nz)
        with jax.named_scope("fbp_accum"):
            return acc + w * vol, 0

    acc0 = jnp.zeros(v.shape, sino.dtype)
    acc, _ = jax.lax.scan(one, acc0, (angs, wts, q))
    return acc


def _fan_gamma(geom: CTGeometry) -> np.ndarray:
    """Fan angle of each detector column (rad)."""
    us = geom.u_coords()
    if geom.detector_type == "curved":
        return us / geom.sdd
    return np.arctan2(us, geom.sdd)


def parker_weights(geom: CTGeometry) -> np.ndarray:
    """Parker (1982) short-scan weights, shape (n_angles, n_cols).

    Smoothly splits the weight of each conjugate ray pair so a
    ``pi + 2*delta`` scan (delta = half fan angle) integrates like a full
    scan.  Views are referenced to the smallest angle; ranges beyond the
    exact short-scan window are clamped to [0, 1]."""
    gamma = _fan_gamma(geom).astype(np.float64)
    delta = float(np.abs(gamma).max())
    ang = np.asarray(geom.angles_array(), np.float64)
    beta = (ang - ang.min())[:, None]                # (na, 1)
    G = gamma[None, :]                               # (1, nu)
    eps = 1e-6
    w = np.ones_like(beta * G)
    # Conjugate of (beta, gamma) is (beta + pi - 2*gamma, -gamma); the ramp
    # arguments below are complementary for such a pair, so w + w_conj = 1.
    r1 = beta < 2.0 * (delta + G)                    # ramp-up region
    a1 = beta / np.maximum(2.0 * (delta + G), eps)
    w = np.where(r1, np.sin(np.pi / 2.0 * np.clip(a1, 0.0, 1.0)) ** 2, w)
    r3 = beta > np.pi + 2.0 * G                      # ramp-down region
    a3 = (np.pi + 2.0 * delta - beta) / np.maximum(2.0 * (delta - G), eps)
    w = np.where(r3, np.sin(np.pi / 2.0 * np.clip(a3, 0.0, 1.0)) ** 2, w)
    return np.clip(w, 0.0, 1.0).astype(np.float32)


def fbp_fan(sino, geom: CTGeometry, filter_name: str = "ramp",
            short_scan: Optional[bool] = None):
    """Fan-beam FBP (flat = equispaced, curved = equiangular columns).

    Weighting chain (Kak & Slaney ch. 3): cosine pre-weight ``cos(gamma)``,
    ramp filter (with the ``(gamma/sin gamma)^2`` kernel correction for
    curved detectors), then distance-weighted backprojection —
    ``sod^2/ell^2`` at flat-detector scale, ``sod*sdd/L^2`` equiangular.
    ``short_scan=None`` auto-detects: an angular span under ~2*pi enables
    Parker weights (and drops the full-scan double-coverage 1/2)."""
    v = geom.vol
    nx, ny, nz = v.shape
    nu, nv = geom.n_cols, geom.n_rows
    sod, sdd = geom.sod, geom.sdd
    curved = geom.detector_type == "curved"
    with obs.span("fbp.tables"):
        gamma = _fan_gamma(geom)
        cw = jnp.asarray(np.cos(gamma).astype(np.float32))   # cosine pre-weight

    ang = np.asarray(geom.angles_array(), np.float64)
    n = len(ang)
    span = float(ang.max() - ang.min()) * (n / max(n - 1, 1))
    if short_scan is None:
        short_scan = span < 2.0 * np.pi * 0.99
    if short_scan:
        pw = jnp.asarray(parker_weights(geom))               # (na, nu)
        pre = sino * cw[None, None, :] * pw[:, None, :]
        wts = jnp.asarray(_angle_weights(geom.angles_array(), span))
    else:
        pre = sino * cw[None, None, :]
        wts = jnp.asarray(_angle_weights(geom.angles_array(), 2 * np.pi)) / 2.0

    q = filter_sinogram(pre, geom.pixel_width, filter_name,
                        equiangular_sdd=sdd if curved else 0.0)
    if not curved:
        # The ramp acts at detector scale; isocenter frequencies are higher
        # by the magnification sdd/sod (same rescale as FDK).
        q = q * (sdd / sod)

    with obs.span("fbp.tables"):
        X = jnp.asarray(np.repeat(v.x_coords(), ny))         # (nxy,)
        Y = jnp.asarray(np.tile(v.y_coords(), nx))
        u0, du = float(geom.u_coords()[0]), geom.pixel_width
        Lz = jnp.asarray(_lerp_matrix(geom.v_coords(), v.z_coords()))
        angs = jnp.asarray(geom.angles_array())

    def one(acc, inp):
        ang_, w, qa = inp                                    # qa (nv, nu)
        c, s = jnp.cos(ang_), jnp.sin(ang_)
        ell = jnp.maximum(sod - (X * c + Y * s), _EPS)       # (nxy,)
        t = Y * c - X * s
        if curved:
            ustar = sdd * jnp.arctan2(t, ell)
            wdist = sod * sdd / (ell * ell + t * t)
        else:
            ustar = sdd * t / ell
            wdist = sod ** 2 / (ell * ell)
        ui = (ustar - u0) / du
        j = jnp.floor(ui).astype(jnp.int32)
        frac = ui - j
        ok0 = (j >= 0) & (j < nu)
        ok1 = (j + 1 >= 0) & (j + 1 < nu)
        g0 = jnp.take(qa, jnp.clip(j, 0, nu - 1), axis=1)    # (nv, nxy)
        g1 = jnp.take(qa, jnp.clip(j + 1, 0, nu - 1), axis=1)
        S = g0 * jnp.where(ok0, 1 - frac, 0.0) + g1 * jnp.where(ok1, frac, 0.0)
        S = S * wdist[None, :]
        return acc + w * jnp.einsum("vq,vz->qz", S, Lz).reshape(nx, ny, nz), 0

    acc0 = jnp.zeros(v.shape, sino.dtype)
    acc, _ = jax.lax.scan(one, acc0, (angs, wts, q))
    return acc


def fbp_cone(sino, geom: CTGeometry, filter_name: str = "ramp"):
    """FDK reconstruction (flat detector)."""
    v = geom.vol
    nx, ny, nz = v.shape
    nu, nv = geom.n_cols, geom.n_rows
    sod, sdd = geom.sod, geom.sdd
    us = jnp.asarray(geom.u_coords())
    vs = jnp.asarray(geom.v_coords())
    # cosine pre-weight
    cw = sdd / jnp.sqrt(sdd ** 2 + us[None, :] ** 2 + vs[:, None] ** 2)
    q = filter_sinogram(sino * cw[None], geom.pixel_width, filter_name)
    # The ramp filter acts at detector scale; frequencies at the isocenter are
    # higher by the magnification sdd/sod, so rescale the filtered data.
    q = q * (sdd / sod)
    with obs.span("fbp.tables"):
        X = jnp.asarray(np.repeat(v.x_coords(), ny))
        Y = jnp.asarray(np.tile(v.y_coords(), nx))
        Z = jnp.asarray(v.z_coords())
        u0, du = float(geom.u_coords()[0]), geom.pixel_width
        v0, dv = float(geom.v_coords()[0]), geom.pixel_height
        wts = jnp.asarray(_angle_weights(geom.angles_array(),
                                         2 * np.pi)) / 2.0
        angs = jnp.asarray(geom.angles_array())

    def one(acc, inp):
        ang, w, qa = inp
        c, s = jnp.cos(ang), jnp.sin(ang)
        ell = sod - (X * c + Y * s)                              # (nxy,)
        ell = jnp.maximum(ell, _EPS)
        ustar = sdd * (Y * c - X * s) / ell
        ui = (ustar - u0) / du
        j = jnp.floor(ui).astype(jnp.int32)
        t = ui - j
        ok0 = (j >= 0) & (j < nu)
        ok1 = (j + 1 >= 0) & (j + 1 < nu)
        g0 = jnp.take(qa, jnp.clip(j, 0, nu - 1), axis=1)
        g1 = jnp.take(qa, jnp.clip(j + 1, 0, nu - 1), axis=1)
        S = g0 * jnp.where(ok0, 1 - t, 0.0) + g1 * jnp.where(ok1, t, 0.0)
        S = S.T                                                  # (nxy, nv)
        vi = (sdd * Z[None, :] / ell[:, None] - v0) / dv         # (nxy, nz)
        jv = jnp.floor(vi).astype(jnp.int32)
        tv = vi - jv
        okv0 = (jv >= 0) & (jv < nv)
        okv1 = (jv + 1 >= 0) & (jv + 1 < nv)
        h0 = jnp.take_along_axis(S, jnp.clip(jv, 0, nv - 1), axis=1)
        h1 = jnp.take_along_axis(S, jnp.clip(jv + 1, 0, nv - 1), axis=1)
        val = h0 * jnp.where(okv0, 1 - tv, 0.0) + h1 * jnp.where(okv1, tv, 0.0)
        val = val * (sod ** 2 / ell[:, None] ** 2)
        return acc + w * val.reshape(nx, ny, nz), 0

    acc0 = jnp.zeros(v.shape, sino.dtype)
    acc, _ = jax.lax.scan(one, acc0, (angs, wts, q))
    return acc


def fbp(sino, geom: CTGeometry, model: str = "sf", backend: str = "auto",
        filter_name: str = "ramp", config=None,
        short_scan: Optional[bool] = None):
    """Analytic reconstruction.

    ``config`` (a :class:`repro.kernels.tune.KernelConfig`) is accepted for
    API uniformity with the projector ops and reserved for a kernelized
    backprojector; the current interpolation backprojectors are pure jnp
    and take no tile sizes.  ``short_scan`` applies only to fan beams
    (Parker weighting; ``None`` auto-detects from the angular span).
    """
    if config is not None:
        from repro.kernels.tune import KernelConfig
        if not isinstance(config, KernelConfig):
            raise TypeError(f"config must be a KernelConfig, got {config!r}")
    if geom.geom_type == "parallel":
        return fbp_parallel(sino, geom, filter_name)
    if geom.geom_type == "fan":
        return fbp_fan(sino, geom, filter_name, short_scan=short_scan)
    if geom.geom_type == "cone":
        if geom.detector_type != "flat":
            raise NotImplementedError("FDK implemented for flat detectors")
        return fbp_cone(sino, geom, filter_name)
    raise NotImplementedError("FBP needs parallel, fan, or cone geometry; "
                              "use iterative recon (repro.recon) for modular")
