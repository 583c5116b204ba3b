"""Pallas TPU kernels: modular-beam Separable-Footprint forward/back
projection — LEAP's distinguishing geometry class, on-kernel.

A modular geometry carries an arbitrary *per-view frame*: source position
``s_a``, detector center ``c_a``, and detector axes ``(e_u, e_v)``.  The
Pallas pair supports the **axial-frame** subclass — detector rows parallel
to the rotation axis (``e_v = ±ẑ``, ``e_u`` transaxial) with a free source
position *including z* — which covers the trajectories that fixed-geometry
kernels structurally cannot express: helical scans, per-view detector
shifts, non-uniform angular sampling, non-circular orbits.  Fully tilted
frames fall back to the Joseph ray-marching reference
(``ref.fp_modular_joseph``); ``modular_frames_axial`` is the dispatch gate.

The kernels are the exact cone pair (``fp_cone.py``) generalized to
per-view frames, and reduce to it exactly on axial circular trajectories
(``tests/test_modular.py`` pins this through ``cone_as_modular``):

* **Transaxial**: a per-view rescale + shear at trace time maps the modular
  corner projection onto the cone form with one *static* reference distance.
  With ``n̂`` the in-plane unit normal toward the detector,
  ``q = (p − s)·e_u``, ``ℓ = (p − s)·n̂``, ``sdd_a = (c − s)·n̂`` and the
  in-plane detector offset ``cu = (s − c)·e_u``, the detector coordinate of
  a corner is::

      u = sdd_a·(q + dq)/(ℓ + dl) + cu
        = SDD_REF·(q̂ + dq̂)/(ℓ + dl),   q̂ = (sdd_a/SDD_REF)·q + (cu/SDD_REF)·ℓ

  so the shared ``fp_cone._corner_trapezoid`` (and the window-start
  inversion) applies verbatim — only the per-view affine coefficients
  change.  The scalar-prefetched parameter row grows from 20 to 24 floats
  to carry the per-view axial frame (signed magnification numerator
  ``e_vz·sdd_a``, source height ``s_z``, row offset ``cv``).
* **Axial**: the per-element resample maps the volume z-line onto detector
  rows at ``v = (z − s_z)·(e_vz·sdd_a)/ℓ + cv`` — the cone kernel's
  per-element rect-overlap matvec with a per-view shift/offset (and a sign,
  handled by sorting the projected voxel edges).  This per-lane dependence
  is exactly why the modular pair uses the cone kernels' grid-folded
  batching, not fan-style lane packing (docs/KERNELS.md).
* **Batching**: a leading batch dim folds into the *view* grid axis (FP) /
  the *gathered-output* grid axis (BP), sharing one SMEM parameter table
  across samples — identical to the exact cone pair.

``bp_modular_sf_pallas`` is the exact transpose of the forward kernel
(same 24-float parameter rows, same corner-projected breakpoints,
transposed contraction + adjoint-direction axial matvec), so the
registered pair is *matched* and helical training/recon steps stay
on-kernel end to end.  ``fp_modular_sf_ref``/``bp_modular_sf_ref`` are the
jnp oracles (same frame math, no Pallas), and ``bp_modular_joseph_ref``
adjoins the Joseph reference for tilted frames.

Tile sizes come from :mod:`repro.kernels.tune` (``"modular"`` shape class).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.geometry import CTGeometry
from repro.kernels import precision, ref, tune
from repro.kernels.footprint import trapezoid_pixel_weight
from repro.kernels.fp_cone import (_corner_trapezoid, _line_major_batch,
                                   _volume_from_line_major)
from repro.kernels.fp_par import _pad_cols
from repro.runtime import obs

_EPS = 1e-9
_AXIAL_TOL = 1e-4


# --------------------------------------------------------------------------- #
# Per-view frames
# --------------------------------------------------------------------------- #
def _frames(geom: CTGeometry):
    """Decompose the per-view modular frames into the kernel's quantities.

    Returns a dict of (na,)-shaped float64 arrays: source ``s``/``sz``,
    in-plane detector axis ``eu``, in-plane unit normal ``n`` oriented
    source -> detector, detector distance ``sdd`` along ``n``, in-plane /
    axial detector offsets ``cu``/``cv``, and the e_v z-sign ``evz``."""
    if geom.geom_type != "modular":
        raise ValueError(f"_frames needs a modular geometry, got "
                         f"geom_type={geom.geom_type!r}")
    s = np.asarray(geom.source_pos, np.float64)
    c = np.asarray(geom.det_center, np.float64)
    eu = np.asarray(geom.det_u, np.float64)
    ev = np.asarray(geom.det_v, np.float64)
    n = np.stack([eu[:, 1] * ev[:, 2], -eu[:, 0] * ev[:, 2],
                  np.zeros(len(eu))], -1)              # eu x ev (axial frames)
    d = c - s
    sdd = np.einsum("ai,ai->a", d, n)
    flip = np.sign(sdd)
    flip[flip == 0] = 1.0
    n = n * flip[:, None]
    sdd = sdd * flip
    return {
        "s": s, "sz": s[:, 2], "eu": eu, "ev": ev, "n": n, "sdd": sdd,
        "cu": -np.einsum("ai,ai->a", d, eu),
        "cv": -np.einsum("ai,ai->a", d, ev),
        "evz": ev[:, 2],
    }


def modular_frames_axial(geom: CTGeometry, fr=None) -> bool:
    """True when the per-view frames are in the axial subclass the SF pair
    supports: unit detector axes, ``e_u`` transaxial, ``e_v = ±ẑ``, a
    non-degenerate detector distance, and the source transaxially outside
    the volume for every view (the SF validity condition, the modular
    analogue of cone's ``sod > radius``).  ``fr`` accepts a precomputed
    ``_frames(geom)`` so entry points decompose the frames only once."""
    if geom.geom_type != "modular":
        return False
    eu = np.asarray(geom.det_u, np.float64)
    ev = np.asarray(geom.det_v, np.float64)
    if not (np.allclose(np.linalg.norm(eu, axis=1), 1.0, atol=_AXIAL_TOL)
            and np.allclose(np.linalg.norm(ev, axis=1), 1.0, atol=_AXIAL_TOL)
            and np.all(np.abs(eu[:, 2]) < _AXIAL_TOL)
            and np.all(np.abs(ev[:, 0]) < _AXIAL_TOL)
            and np.all(np.abs(ev[:, 1]) < _AXIAL_TOL)):
        return False
    fr = _frames(geom) if fr is None else fr
    if np.any(fr["sdd"] <= _AXIAL_TOL):
        return False
    lc, _ = _ell_center(geom, fr)
    return bool(np.all(lc - geom.vol.radius > 1e-3))


def _require_axial(geom: CTGeometry, fr=None):
    if not modular_frames_axial(geom, fr):
        raise NotImplementedError(
            "the modular SF pair supports axial frames (detector rows "
            "parallel to the rotation axis, source outside the volume); "
            "use model='joseph' (ray marching) for tilted frames")


def _ell_center(geom: CTGeometry, fr) -> Tuple[np.ndarray, float]:
    """Per-view in-plane distance from the source to the volume center along
    the detector normal, plus the volume's transaxial radius."""
    v = geom.vol
    p0 = np.asarray([v.offset_x, v.offset_y])
    lc = np.einsum("ai,ai->a", p0[None, :] - fr["s"][:, :2], fr["n"][:, :2])
    return lc, v.radius


def _mag_bounds_modular(geom: CTGeometry, fr) -> Tuple[float, float]:
    """(mag_min, mag_max) of the unsigned magnification sdd_a/ℓ over all
    views and the volume disk (the modular analogue of cone _mag_bounds)."""
    lc, r = _ell_center(geom, fr)
    mag_min = float(np.min(fr["sdd"] / (lc + r)))
    mag_max = float(np.max(fr["sdd"] / np.maximum(lc - r, 1e-3)))
    return mag_min, mag_max


# --------------------------------------------------------------------------- #
# Per-view affine parameters (24 floats)
# --------------------------------------------------------------------------- #
def _view_params_modular(geom: CTGeometry, fr=None
                         ) -> Tuple[np.ndarray, np.ndarray,
                                    np.ndarray, float]:
    """Per-view affine coefficients of q̂(gi, li) and ℓ(gi, li), the rx/ry
    affines, the four corner offsets (dq̂_k, dl_k), and the per-view axial
    frame, split into x-gathered (|n_y| >= |n_x|) and y-gathered groups.

    Layout per view (24 floats; [0:20] is the cone layout evaluated on the
    rescaled/sheared q̂ so ``_corner_trapezoid`` applies with the static
    ``sdd_ref`` returned alongside):

      [Aq, Bq, Cq, Al, Bl, Cl, Arx, Brx, Crx, Ary, Bry, Cry,
       dq0, dl0, dq1, dl1, dq2, dl2, dq3, dl3,
       mags (= e_vz * sdd_a), sz, cv, 0]
    """
    v = geom.vol
    fr = _frames(geom) if fr is None else fr
    x0, y0 = float(v.x_coords()[0]), float(v.y_coords()[0])
    hx, hy = v.dx / 2.0, v.dy / 2.0
    sdd_ref = float(np.median(fr["sdd"]))
    scale = fr["sdd"] / sdd_ref
    shear = fr["cu"] / sdd_ref
    eux, euy = fr["eu"][:, 0], fr["eu"][:, 1]
    nx, ny = fr["n"][:, 0], fr["n"][:, 1]
    sx, sy = fr["s"][:, 0], fr["s"][:, 1]
    # q̂ / ℓ direction cosines along world x/y (per view)
    qx = scale * eux + shear * nx
    qy = scale * euy + shear * ny
    C_off = (x0 - sx, y0 - sy)                        # volume corner - source
    Cq = qx * C_off[0] + qy * C_off[1]
    Cl = nx * C_off[0] + ny * C_off[1]

    def grp(gathered_x: bool):
        if gathered_x:                                # gi -> x, li -> y
            Aq, Bq = qx * v.dx, qy * v.dy
            Al, Bl = nx * v.dx, ny * v.dy
            Arx, Brx = v.dx * np.ones_like(nx), np.zeros_like(nx)
            Ary, Bry = np.zeros_like(nx), v.dy * np.ones_like(nx)
        else:                                         # gi -> y, li -> x
            Aq, Bq = qy * v.dy, qx * v.dx
            Al, Bl = ny * v.dy, nx * v.dx
            Arx, Brx = np.zeros_like(nx), v.dx * np.ones_like(nx)
            Ary, Bry = v.dy * np.ones_like(nx), np.zeros_like(nx)
        cols = [Aq, Bq, Cq, Al, Bl, Cl, Arx, Brx, C_off[0],
                Ary, Bry, C_off[1]]
        for ox in (-hx, hx):
            for oy in (-hy, hy):
                cols.append(qx * ox + qy * oy)        # dq̂
                cols.append(nx * ox + ny * oy)        # dl
        cols += [fr["evz"] * fr["sdd"], fr["sz"], fr["cv"],
                 np.zeros_like(nx)]
        return np.stack(cols, -1).astype(np.float32)

    gx = np.abs(ny) >= np.abs(nx)
    px, py = grp(True), grp(False)
    idx_x = np.nonzero(gx)[0]
    idx_y = np.nonzero(~gx)[0]
    return px[idx_x], py[idx_y], np.concatenate([idx_x, idx_y]), sdd_ref


# --------------------------------------------------------------------------- #
# Forward kernel
# --------------------------------------------------------------------------- #
def _fp_modular_kernel(params_ref,     # SMEM (n_views, 24)
                       f_ref,          # VMEM (NG, NZ) volume line plane
                       out_ref,        # VMEM (1, BU, BV) sino tile
                       *, W: int, u0: float, du: float,
                       v0: float, dv: float, z0c: float, dz: float,
                       sdd_ref: float, dxv: float, ng: int, nz: int,
                       bu: int, bv: int, nav: int, align: int):
    """One program: one view x one (bu, bv) sino tile x one volume line —
    the exact cone FP kernel with the per-view frame read from the prefetch
    row: static ``sdd`` becomes ``sdd_ref`` (transaxial, via the q̂
    rescale) and the axial resample picks up the per-view signed
    magnification, source height, and row offset."""
    a = pl.program_id(0)
    ub = pl.program_id(1)
    vb = pl.program_id(2)
    li = pl.program_id(3)

    @pl.when(li == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    av = jax.lax.rem(a, nav)                 # batch folded into the view axis
    P = [params_ref[av, i] for i in range(24)]
    Aq, Bq, Cq, Al, Bl, Cl = P[:6]
    mags, sz, cv = P[20], P[21], P[22]
    lif = li.astype(jnp.float32)
    u_first = u0 + (ub * bu) * du
    u_last = u_first + (bu - 1) * du

    # window start: invert u = sdd_ref*(Aq*gi + q0)/(Al*gi + l0)
    q0 = Bq * lif + Cq
    l0 = Bl * lif + Cl

    def gi_of(u):
        den = sdd_ref * Aq - u * Al
        den = jnp.where(jnp.abs(den) > 1e-6,
                        den, jnp.where(den >= 0, 1e-6, -1e-6))
        return (u * l0 - sdd_ref * q0) / den

    g1, g2 = gi_of(u_first), gi_of(u_last)
    start = tune.window_start(g1, g2, W, ng, align)

    win = f_ref[pl.ds(start, W), :]                          # (W, nz)
    gi = start + tune.iota_f32((1, W), 1)                    # (1, W)
    t0, t1, t2, t3, h, rt2 = _corner_trapezoid(P, gi, q0, l0, lif,
                                               sdd_ref, dxv)

    uk = u_first + du * tune.iota_f32((bu, 1), 0)
    el = uk - du / 2.0
    wu = trapezoid_pixel_weight(el, el + du, t0, t1, t2, t3, h)  # (bu, W)

    ell = jnp.maximum(Al * gi + l0, _EPS)
    mag = mags / ell                         # signed per-element magnification
    elv = v0 + (vb * bv) * dv - dv / 2.0 + dv * tune.iota_f32((bv, 1), 0)
    zs = z0c + dz * tune.iota_f32((1, nz), 1) - sz           # (1, nz)

    acc = jnp.zeros((bu, bv), jnp.float32)
    for w in range(W):
        # The axial overlap spans the whole z line (lanes), as in the
        # exact cone FP kernel.
        mag_w = mag[:, w:w + 1]                              # (1, 1)
        va = (zs - dz / 2.0) * mag_w + cv                    # (1, nz)
        vb_ = (zs + dz / 2.0) * mag_w + cv
        vlo = jnp.minimum(va, vb_)           # sorted: mag may be negative
        vhi = jnp.maximum(va, vb_)
        ov = jnp.maximum(jnp.minimum(vhi, elv + dv)
                         - jnp.maximum(vlo, elv), 0.0) / dv  # (bv, nz)
        obl = jnp.sqrt(1.0 + (zs * zs) / jnp.maximum(rt2[:, w:w + 1], 1e-9))
        fw = win[w:w + 1, :]                                 # (1, nz)
        rv = precision.mxu_dot(fw, precision.cast_like(ov * obl, fw),
                               ((1,), (1,)))                 # (1, bv)
        acc = acc + wu[:, w:w + 1] * rv
    precision.store_tile(out_ref, 0, acc)


def _fp_window_size(geom: CTGeometry, bu: int, mag_min: float,
                    mag_max: float) -> int:
    """Gathered-axis window covering one u-tile, before alignment."""
    vol = geom.vol
    du = geom.pixel_width
    span = bu * du * math.sqrt(2.0) / (vol.dx * mag_min)
    margin = 2.0 * (math.sqrt(2.0) * vol.dx * mag_max + du) \
        / (vol.dx * mag_min) + 4.0
    return int(math.ceil(span + 2 * margin)) + 2


def _run_fp_group(fb, params: np.ndarray, geom: CTGeometry, gathered_x: bool,
                  bu: int, bv: int, sdd_ref: float,
                  mag_min: float, mag_max: float):
    """fb: (B, nx, ny, nz) batch of volumes; the batch is folded into the
    view grid axis exactly like the exact cone FP.  Returns
    (B, na_group, NUp, NVp)."""
    if params.shape[0] == 0:
        return None
    vol = geom.vol
    B = fb.shape[0]
    fs, ng = _line_major_batch(fb, gathered_x)
    nl, _, nz = fs.shape
    na = params.shape[0]
    nup = tune.round_up(geom.n_cols, bu)
    nvp = tune.round_up(geom.n_rows, bv)
    align = tune.sublane_tile(fs.dtype)
    W = tune.aligned_window(_fp_window_size(geom, bu, mag_min, mag_max),
                            ng, align)
    kernel = functools.partial(
        _fp_modular_kernel, W=W,
        u0=float(geom.u_coords()[0]), du=geom.pixel_width,
        v0=float(geom.v_coords()[0]), dv=geom.pixel_height,
        z0c=float(vol.z_coords()[0]), dz=vol.dz,
        sdd_ref=sdd_ref, dxv=vol.dx, ng=ng, nz=nz, bu=bu, bv=bv, nav=na,
        align=align)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * na, nup // bu, nvp // bv, nl),
            in_specs=[pl.BlockSpec((None, ng, nz),
                                   lambda a, ub, vb, l, *_: (l, a // na, 0))],
            out_specs=pl.BlockSpec((1, bu, bv),
                                   lambda a, ub, vb, l, *_: (a, ub, vb)),
        ),
        # output buffer is the cross-step accumulator: always f32
        out_shape=jax.ShapeDtypeStruct((B * na, nup, nvp), jnp.float32),
        interpret=tune._interpret(),
    )(jnp.asarray(params), fs)
    return out.reshape(B, na, nup, nvp)


@obs.spanned("kernels.fp_modular")
def fp_modular_sf_pallas(f, geom: CTGeometry, bu: Optional[int] = None,
                         bv: Optional[int] = None,
                         config: Optional[tune.KernelConfig] = None,
                         compute_dtype=None):
    """f: (nx, ny, nz) -> sino (n_angles, n_rows, n_cols), or batched
    f: (batch, nx, ny, nz) -> (batch, ...).  Axial modular frames."""
    if geom.geom_type != "modular":
        raise ValueError(f"fp_modular_sf_pallas needs a modular geometry, "
                         f"got geom_type={geom.geom_type!r}; dispatch "
                         f"through get_ops/forward_project for auto kernel "
                         f"selection")
    fr = _frames(geom)
    _require_axial(geom, fr)
    if f.ndim not in (3, 4):
        raise ValueError(f"expected 3D or batched 4D volume, got {f.shape}")
    batched = f.ndim == 4
    out_dtype = f.dtype
    cdt = precision.resolve(compute_dtype, f.dtype)
    fb = precision.cast_in(f if batched else f[None], cdt)
    cfg = tune.resolve_config(geom, fb.shape[0], config, dtype=cdt,
                              bu=bu, bv=bv)
    px, py, order, sdd_ref = _view_params_modular(geom, fr)
    mag_min, mag_max = _mag_bounds_modular(geom, fr)
    outs = []
    o1 = _run_fp_group(fb, px, geom, True, cfg.bu, cfg.bv, sdd_ref,
                       mag_min, mag_max)
    if o1 is not None:
        outs.append(o1)
    o2 = _run_fp_group(fb, py, geom, False, cfg.bu, cfg.bv, sdd_ref,
                       mag_min, mag_max)
    if o2 is not None:
        outs.append(o2)
    out = jnp.concatenate(outs, axis=1)                # (B, na, NUp, NVp)
    out = out[:, :, :geom.n_cols, :geom.n_rows]
    inv = np.argsort(order)
    out = jnp.swapaxes(out[:, inv], 2, 3).astype(out_dtype)  # (B, na, nv, nu)
    return out if batched else out[0]


# --------------------------------------------------------------------------- #
# Backprojection kernel (exact transpose)
# --------------------------------------------------------------------------- #
def _bp_modular_kernel(params_ref,     # SMEM (n_views, 24)
                       q_ref,          # VMEM (bab, NU, bv) u-major sino stripes
                       out_ref,        # VMEM (bg, nz) volume tile (z lanes)
                       *, Wu: int, u0: float, du: float, v0: float, dv: float,
                       z0c: float, dz: float, sdd_ref: float, dxv: float,
                       nu: int, nz: int, bg: int, bv: int, bab: int,
                       ngb: int, align: int):
    """Exact transpose of ``_fp_modular_kernel`` — the cone BP kernel with
    the per-view frame read from the 24-float prefetch row: the same
    corner-projected breakpoints contracted in the transposed direction,
    and each gathered element's (bv, nz) rect-overlap matrix (signed
    per-view magnification, source height, row offset) mapping its
    u-contracted detector rows back onto the volume's z lanes."""
    gall = pl.program_id(0)
    li = pl.program_id(1)
    vb = pl.program_id(2)
    ab = pl.program_id(3)

    @pl.when((vb == 0) & (ab == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lif = li.astype(jnp.float32)
    # batch folded into the gathered axis
    gi0 = (jax.lax.rem(gall, ngb) * bg).astype(jnp.float32)
    gi_abs = gi0 + tune.iota_f32((bg, 1), 0)                 # (bg, 1)
    elv = v0 + (vb * bv) * dv - dv / 2.0 + dv * tune.iota_f32((bv, 1), 0)
    zt = z0c + dz * tune.iota_f32((1, nz), 1)                # (1, nz)
    row = jax.lax.broadcasted_iota(jnp.int32, (bg, 1), 0)

    acc = jnp.zeros((bg, nz), jnp.float32)
    for j in range(bab):
        a = ab * bab + j
        P = [params_ref[a, i] for i in range(24)]
        Aq, Bq, Cq, Al, Bl, Cl = P[:6]
        mags, sz, cv = P[20], P[21], P[22]
        q0 = Bq * lif + Cq
        l0 = Bl * lif + Cl

        # window start: center projection u(gi) over the gathered tile
        def uc_of(gi):
            qg = Aq * gi + q0
            lg = jnp.maximum(Al * gi + l0, _EPS)
            return sdd_ref * qg / lg

        uc_a = uc_of(gi0)
        uc_b = uc_of(gi0 + (bg - 1))
        ustart = tune.window_start((uc_a - u0) / du, (uc_b - u0) / du,
                                   Wu, nu, align)

        qwin = q_ref[j, pl.ds(ustart, Wu), :]                # (Wu, bv)
        t0, t1, t2, t3, h, rt2 = _corner_trapezoid(
            P, gi_abs, q0, l0, lif, sdd_ref, dxv)            # (bg, 1)
        uk = u0 + (ustart + tune.iota_f32((1, Wu), 1)) * du
        el = uk - du / 2.0                                   # (1, Wu)
        wgt = trapezoid_pixel_weight(el, el + du, t0, t1, t2, t3, h)
        rows = precision.mxu_dot(precision.cast_like(wgt, qwin),
                                 qwin)                       # (bg, bv)
        # Row g of the tile is contracted alone by masking the others (see
        # the exact cone BP kernel).
        mag = mags / jnp.maximum(Al * gi_abs + l0, _EPS)     # (bg, 1)
        zs = zt - sz                                         # (1, nz)
        obl = jnp.sqrt(1.0 + (zs * zs) / jnp.maximum(rt2, _EPS))  # (bg, nz)
        for g in range(bg):
            mag_g = mag[g:g + 1, :]                          # (1, 1)
            va = (zs - dz / 2.0) * mag_g + cv                # (1, nz)
            vb_ = (zs + dz / 2.0) * mag_g + cv
            vlo = jnp.minimum(va, vb_)
            vhi = jnp.maximum(va, vb_)
            ov = jnp.maximum(jnp.minimum(vhi, elv + dv)
                             - jnp.maximum(vlo, elv), 0.0) / dv   # (bv, nz)
            acc = acc + precision.mxu_dot(
                jnp.where(row == g, rows, 0.0),
                ov * obl[g:g + 1, :])                        # (bg, nz)
    precision.store_tile(out_ref, slice(None), acc)


def _u_window_size_modular(geom: CTGeometry, bg: int, mag_max: float) -> int:
    """Detector-column window covering one bg voxel tile, before
    alignment."""
    du, dx = geom.pixel_width, geom.vol.dx
    span = bg * dx * math.sqrt(2.0) * mag_max / du
    margin = 2.0 * math.sqrt(2.0) * dx * mag_max / du + 4.0
    return max(int(math.ceil(span + 2 * margin)) + 2, 8)


def _run_bp_group(q, params: np.ndarray, geom: CTGeometry, gathered_x: bool,
                  bg: int, bv: int, bab: int, sdd_ref: float, mag_max: float):
    """q: (B, na_group, n_cols, n_rows) u-major sino slice.  Batch folded
    into the gathered-output grid axis (the transpose of the FP's view-axis
    folding).  Returns this group's volume accumulator (B, nx, ny, nz)."""
    vol = geom.vol
    ng, nl = (vol.nx, vol.ny) if gathered_x else (vol.ny, vol.nx)
    nz = vol.nz
    align = tune.sublane_tile(q.dtype)
    q = _pad_cols(q, align)
    B, na, nu_, nv_ = q.shape
    bab = max(1, min(bab, na))
    nap = tune.round_up(na, bab)
    if nap != na:
        params = np.concatenate([params, np.repeat(params[-1:],
                                                   nap - na, 0)], 0)
        q = jnp.pad(q, ((0, 0), (0, nap - na), (0, 0), (0, 0)))
    nvp = tune.round_up(nv_, bv)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, nvp - nv_)))
    qs = q.reshape(B * nap, nu_, nvp)
    ngp = tune.round_up(ng, bg)
    ngb, nab = ngp // bg, nap // bab
    Wu = tune.aligned_window(_u_window_size_modular(geom, bg, mag_max),
                             nu_, align)
    kernel = functools.partial(
        _bp_modular_kernel, Wu=Wu,
        u0=float(geom.u_coords()[0]), du=geom.pixel_width,
        v0=float(geom.v_coords()[0]), dv=geom.pixel_height,
        z0c=float(vol.z_coords()[0]), dz=vol.dz, sdd_ref=sdd_ref,
        dxv=vol.dx, nu=nu_, nz=nz, bg=bg, bv=bv, bab=bab, ngb=ngb,
        align=align)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * ngb, nl, nvp // bv, nab),
            in_specs=[pl.BlockSpec((bab, nu_, bv),
                                   lambda gall, l, vb, ab, *_:
                                   (gall // ngb * nab + ab, 0, vb))],
            out_specs=pl.BlockSpec((None, bg, nz),
                                   lambda gall, l, vb, ab, *_: (l, gall, 0)),
        ),
        # output buffer is the cross-step accumulator: always f32
        out_shape=jax.ShapeDtypeStruct((nl, B * ngp, nz), jnp.float32),
        interpret=tune._interpret(),
    )(jnp.asarray(params), qs)
    return _volume_from_line_major(out, B, ng, gathered_x)


@obs.spanned("kernels.bp_modular")
def bp_modular_sf_pallas(sino, geom: CTGeometry, bg: Optional[int] = None,
                         bv: Optional[int] = None, bab: Optional[int] = None,
                         config: Optional[tune.KernelConfig] = None,
                         compute_dtype=None):
    """sino: (n_angles, n_rows, n_cols) -> volume (nx, ny, nz), or batched
    sino: (batch, ...) -> (batch, nx, ny, nz).  Exact transpose of
    ``fp_modular_sf_pallas`` (incl. the batched path)."""
    if geom.geom_type != "modular":
        raise ValueError(f"bp_modular_sf_pallas needs a modular geometry, "
                         f"got geom_type={geom.geom_type!r}; dispatch "
                         f"through get_ops/back_project for auto kernel "
                         f"selection")
    fr = _frames(geom)
    _require_axial(geom, fr)
    if sino.ndim not in (3, 4):
        raise ValueError(f"expected 3D or batched 4D sinogram, got {sino.shape}")
    batched = sino.ndim == 4
    out_dtype = sino.dtype
    cdt = precision.resolve(compute_dtype, sino.dtype)
    qb = sino if batched else sino[None]
    cfg = tune.resolve_config(geom, qb.shape[0], config, dtype=cdt,
                              bg=bg, bv=bv, bab=bab)
    px, py, order, sdd_ref = _view_params_modular(geom, fr)
    _, mag_max = _mag_bounds_modular(geom, fr)
    q = jnp.swapaxes(qb, 2, 3)                         # (B, na, nu, nv)
    q = precision.cast_in(q[:, order], cdt)            # group-major views
    nax = px.shape[0]
    acc = jnp.zeros((qb.shape[0],) + geom.vol.shape, jnp.float32)
    if nax:
        acc = acc + _run_bp_group(q[:, :nax], px, geom, True,
                                  cfg.bg, cfg.bv, cfg.bab, sdd_ref, mag_max)
    if py.shape[0]:
        acc = acc + _run_bp_group(q[:, nax:], py, geom, False,
                                  cfg.bg, cfg.bv, cfg.bab, sdd_ref, mag_max)
    acc = acc.astype(out_dtype)
    return acc if batched else acc[0]


# --------------------------------------------------------------------------- #
# jnp oracles
# --------------------------------------------------------------------------- #
def fp_modular_sf_ref(f, geom: CTGeometry):
    """Separable-footprint modular forward projection in pure jnp — the
    oracle for the Pallas pair (same frame math, no windowing), and the
    ``model="sf"`` modular entry of the ``ref`` backend.  Tilted
    (non-axial) frames delegate to the Joseph ray-marching reference, the
    same fallback the seed applied to all modular geometries.

    Like the other oracles this scans over views (per-view frame scalars
    ride the scan carry), so trace/compile cost is independent of the view
    count — helical recon on the ref backend stays usable."""
    fr = _frames(geom)
    if not modular_frames_axial(geom, fr):
        return ref.fp_modular_joseph(f, geom)
    v = geom.vol
    nx, ny, nz = v.shape
    nu, nv = geom.n_cols, geom.n_rows
    du, dv = geom.pixel_width, geom.pixel_height
    _, mag_max = _mag_bounds_modular(geom, fr)
    Ku = int(math.ceil(math.sqrt(2.0) * v.dx * mag_max / du)) + 2
    Kv = int(math.ceil(v.dz * mag_max / dv)) + 2
    uedge0 = float(geom.u_coords()[0]) - du / 2.0
    vedge0 = float(geom.v_coords()[0]) - dv / 2.0
    X = jnp.asarray(np.repeat(v.x_coords(), ny))             # (nxy,)
    Y = jnp.asarray(np.tile(v.y_coords(), nx))
    Z = jnp.asarray(v.z_coords())                            # (nz,)
    hx, hy = v.dx / 2.0, v.dy / 2.0
    fflat = f.reshape(nx * ny, nz)
    views = jnp.asarray(np.stack(
        [fr["s"][:, 0], fr["s"][:, 1], fr["sz"],
         fr["eu"][:, 0], fr["eu"][:, 1], fr["n"][:, 0], fr["n"][:, 1],
         fr["sdd"], fr["cu"], fr["cv"], fr["evz"] * fr["sdd"]],
        -1).astype(np.float32))                              # (na, 11)

    def one_view(_, vd):
        sx, sy, sz, eux, euy, nxh, nyh, sdd_a, cu, cv, mags = (
            vd[i] for i in range(11))
        rx, ry = X - sx, Y - sy
        q = rx * eux + ry * euy
        ell = rx * nxh + ry * nyh
        taus = []
        for ox in (-hx, hx):
            for oy in (-hy, hy):
                dq = ox * eux + oy * euy
                dl = ox * nxh + oy * nyh
                taus.append(sdd_a * (q + dq)
                            / jnp.maximum(ell + dl, _EPS) + cu)
        taus = jnp.sort(jnp.stack(taus, -1), -1)
        t0, t1, t2, t3 = (taus[..., 0], taus[..., 1], taus[..., 2],
                          taus[..., 3])
        rt2 = rx * rx + ry * ry
        h = v.dx * jnp.sqrt(rt2) / jnp.maximum(
            jnp.maximum(jnp.abs(rx), jnp.abs(ry)), _EPS)
        obl = jnp.sqrt(1.0 + ((Z[None, :] - sz) ** 2)
                       / jnp.maximum(rt2[:, None], _EPS))
        mag = mags / jnp.maximum(ell, _EPS)                  # signed, (nxy,)
        va = (Z[None, :] - v.dz / 2 - sz) * mag[:, None] + cv
        vb = (Z[None, :] + v.dz / 2 - sz) * mag[:, None] + cv
        vlo = jnp.minimum(va, vb)                            # (nxy, nz)
        vhi = jnp.maximum(va, vb)
        # Same 1e-4 floor nudge as the cone/fan oracles (bin-boundary ulp).
        ku0 = jnp.floor((t0 - uedge0) / du + 1e-4).astype(jnp.int32)
        kv0 = jnp.floor((vlo - vedge0) / dv + 1e-4).astype(jnp.int32)
        vals = fflat * obl                                   # (nxy, nz)
        acc = jnp.zeros((nv * nu,), f.dtype)
        for ku in range(Ku):
            iu = ku0 + ku
            el = uedge0 + iu.astype(f.dtype) * du
            wu = trapezoid_pixel_weight(el, el + du, t0, t1, t2, t3, h)
            oku = (iu >= 0) & (iu < nu)
            wu = jnp.where(oku, wu, 0.0)
            iuc = jnp.clip(iu, 0, nu - 1)                    # (nxy,)
            for kv in range(Kv):
                iv = kv0 + kv                                # (nxy, nz)
                elv = vedge0 + iv.astype(f.dtype) * dv
                wv = jnp.maximum(jnp.minimum(vhi, elv + dv)
                                 - jnp.maximum(vlo, elv), 0.0) / dv
                okv = (iv >= 0) & (iv < nv)
                wv = jnp.where(okv, wv, 0.0)
                ivc = jnp.clip(iv, 0, nv - 1)
                idx = ivc * nu + iuc[:, None]                # (nxy, nz)
                acc = acc + jax.ops.segment_sum(
                    (vals * wu[:, None] * wv).reshape(-1),
                    idx.reshape(-1), num_segments=nv * nu)
        return 0, acc.reshape(nv, nu)

    _, sino = jax.lax.scan(one_view, 0, views)
    return sino


def bp_modular_sf_ref(sino, geom: CTGeometry):
    """Exact linear transpose of the SF oracle (via jax.vjp) — the
    cross-check for ``bp_modular_sf_pallas``."""
    f0 = jnp.zeros(geom.vol.shape, sino.dtype)
    _, vjp = jax.vjp(lambda x: fp_modular_sf_ref(x, geom), f0)
    return vjp(sino)[0]


def bp_modular_joseph_ref(sino, geom: CTGeometry):
    """Adjoint of the Joseph ray-marching modular reference (via jax.vjp) —
    the oracle pair for tilted frames the SF kernels don't cover."""
    return ref.adjoint(sino, geom, "joseph")


def register():
    from repro.kernels import ops
    ops.register_kernel("modular", "sf",
                        fp_modular_sf_pallas, bp_modular_sf_pallas,
                        fp_batched=fp_modular_sf_pallas,
                        bp_batched=bp_modular_sf_pallas,
                        supports=modular_frames_axial)
    # The SF oracle doubles as the ref-backend modular "sf" model (the seed
    # silently downgraded every modular request to joseph).
    ref.register_reference("modular", "sf", fp_modular_sf_ref)
