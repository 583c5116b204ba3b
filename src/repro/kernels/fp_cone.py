"""Pallas TPU kernel: cone-beam (flat detector) Separable-Footprint forward
projection and its exact transpose.

Both kernels walk the volume in the BP's order: per program one
``(bg gathered elements, nz)`` tile of a volume line ``li`` and one view's
``bv`` detector rows.  Two cone-beam specifics:

* the transaxial footprint is the *exact corner projection* trapezoid —
  ``u = sdd * q / ell`` with q, ell affine in the voxel index, evaluated for
  the four voxel corners and sorted with min/max ops (``_corner_trapezoid``,
  shared with the fan kernels), over a ``Wu``-wide detector-column window;
* the axial footprint magnifies per gathered element: each element's
  ``(bv, nz)`` rect-overlap matrix (iota-built, times the obliquity) maps
  its z line onto the ``bv`` detector rows on the MXU — the per-element
  axial resample that makes cone beams non-separable on TPU (DESIGN.md §2).

``_tile_footprint`` and ``_axial_overlap`` derive both for the forward and
the backprojection kernel, so the registered pair is matched on-kernel
term by term:

* FP (``fp_cone_sf_pallas``): the axial matrices contract each element's z
  line into detector rows, then the (bg, Wu) weights scatter the rows into
  the view's resident (NU, bv) detector stripe;
* BP (``bp_cone_sf_pallas``): the weights gather a (Wu, bv) sinogram
  window into each element's rows, then the axial matrices map the rows
  back onto the z lanes.

Each axial matrix is built once per (view, line, gathered element, v tile)
in either direction.

``bp_cone_sf_ref`` (the jnp-oracle adjoint) is kept as the cross-check
oracle for ``tests/test_kernels.py``.

Batching: the *exact* kernels' per-lane axial resample depends on the actual
detector-row coordinate of each lane, so batch cannot be packed into the
128-wide axis the way the parallel kernel does.  Instead a leading batch
dimension is folded into the *view* grid axis (FP) / the *gathered-output*
grid axis (BP) — the per-view parameter table stays shared across samples,
so one ``pallas_call`` covers the whole batch (no vmap over the kernel).

For small cone angles the **packed pair** (``fp_cone_packed`` /
``bp_cone_packed``) removes the obstacle: detector rows are pre-resampled
onto volume z-planes at the central magnification *outside* the kernel
(``_z_overlap_cone_packed``), the transaxial remainder is exactly the fan
kernel, and ``batch x n_rows`` lane packing applies directly.  The
approximation carries a derived per-geometry error bound
(``cone_packed_error_bound``) that gates ``mode="auto"`` dispatch in
``repro.kernels.ops`` (see docs/KERNELS.md "Packed cone pair").

Tile sizes come from :mod:`repro.kernels.tune` (``KernelConfig``; the exact
pair shares ``bg`` and ``bv``).

Device names, as in ``fp_par.py``: the exact pair's stages run under
``sf_cone_{fp,bp}_pre`` and ``_post`` scopes and its kernels are named
``sf_cone_{fp,bp}_g0`` (x-gathered views) and ``_g1``; the packed pair's
axial pre-resample runs under ``sf_cone_packed_{fp,bp}_axial``.
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
from repro.kernels import fp_par, precision, ref, tune
from repro.kernels.footprint import trapezoid_pixel_weight
from repro.runtime import obs


_EPS = 1e-9


def _mag_bounds(geom: CTGeometry) -> Tuple[float, float]:
    """(mag_min, mag_max) transaxial magnification over the volume disk."""
    r = geom.vol.radius
    mag_max = geom.sdd / max(geom.sod - r, 1e-3)
    mag_min = geom.sdd / (geom.sod + r)
    return mag_min, mag_max


def _u_window_size_div(geom: CTGeometry, bg: int) -> int:
    """Static bound on the detector-column window covering one bg voxel tile
    for a *divergent* (fan / cone transaxial) beam (the BP kernels and the
    exact cone FP), before alignment (``tune.aligned_window``).
    |duc/dgi| <= sqrt(2) * dx * mag_max and one voxel footprint spans
    <= sqrt(2) * dx * mag_max; curved (fan) footprints are never wider."""
    du, dx = geom.pixel_width, geom.vol.dx
    _, mag_max = _mag_bounds(geom)
    span = bg * dx * math.sqrt(2.0) * mag_max / du
    margin = 2.0 * math.sqrt(2.0) * dx * mag_max / du + 4.0
    return max(int(math.ceil(span + 2 * margin)) + 2, 8)


def _line_major_batch(fb, gathered_x: bool):
    """(B, nx, ny, nz) -> (NL, B*NG, nz): loop axis first and the batch
    stacked along the gathered axis, each sample's gathered axis
    zero-padded to the sublane tile, so one kernel block is a whole
    (NG, nz) line plane of one sample."""
    if gathered_x:
        fb = jnp.swapaxes(fb, 1, 2)
    B, nl, ng, nz = fb.shape
    ngp = tune.round_up(ng, tune.sublane_tile(fb.dtype))
    fb = jnp.pad(fb, ((0, 0), (0, 0), (0, ngp - ng), (0, 0)))
    return jnp.swapaxes(fb, 0, 1).reshape(nl, B * ngp, nz), ngp


def _volume_from_line_major(out, B: int, ng: int, gathered_x: bool):
    """Inverse of :func:`_line_major_batch` for a BP accumulator
    (NL, B*NGp, nz) -> (B, nx, ny, nz)."""
    nl, bng, nz = out.shape
    out = jnp.swapaxes(out.reshape(nl, B, bng // B, nz)[:, :, :ng], 0, 1)
    return jnp.swapaxes(out, 1, 2) if gathered_x else out


def _corner_trapezoid(P, gi, q0, l0, lif, sdd, dxv, curved: bool = False):
    """Corner-projection trapezoid breakpoints + amplitude + squared
    transaxial ray length for gathered indices ``gi`` (broadcast shape).

    ``P`` is the 20-float per-view parameter row of ``_view_params_cone``.
    Shared by the cone FP/BP kernels and the fan kernels (``fp_fan.py``) so
    every evaluation of the same (view, gi, li) triple produces identical
    weights — the exact-transpose requirement of the matched pair."""
    Aq, Al = P[0], P[3]
    q = Aq * gi + q0
    ell = Al * gi + l0
    taus = []
    for k in range(4):
        dq, dl = P[12 + 2 * k], P[13 + 2 * k]
        lc = jnp.maximum(ell + dl, _EPS)
        if curved:
            taus.append(sdd * jnp.arctan2(q + dq, lc))
        else:
            taus.append(sdd * (q + dq) / lc)
    m1 = jnp.minimum(taus[0], taus[1])
    M1 = jnp.maximum(taus[0], taus[1])
    m2 = jnp.minimum(taus[2], taus[3])
    M2 = jnp.maximum(taus[2], taus[3])
    t0 = jnp.minimum(m1, m2)
    t3 = jnp.maximum(M1, M2)
    ta, tb = jnp.maximum(m1, m2), jnp.minimum(M1, M2)
    t1 = jnp.minimum(ta, tb)
    t2 = jnp.maximum(ta, tb)
    Arx, Brx, Crx, Ary, Bry, Cry = P[6:12]
    rx = Arx * gi + Brx * lif + Crx
    ry = Ary * gi + Bry * lif + Cry
    rt2 = rx * rx + ry * ry
    h = dxv * jnp.sqrt(rt2) / jnp.maximum(
        jnp.maximum(jnp.abs(rx), jnp.abs(ry)), _EPS)
    return t0, t1, t2, t3, h, rt2


def _view_params_cone(geom: CTGeometry) -> Tuple[np.ndarray, np.ndarray,
                                                 np.ndarray]:
    """Per-view affine coefficients of q(gi, li) and ell(gi, li) plus the
    four corner offsets (dq_k, dl_k) and the rx/ry affines, split into the
    x-gathered (|sin|>=|cos|) and y-gathered groups.

    Layout per view (20 floats):
      [Aq, Bq, Cq, Al, Bl, Cl, Arx, Brx, Crx, Ary, Bry, Cry,
       dq0, dl0, dq1, dl1, dq2, dl2, dq3, dl3]
    """
    v = geom.vol
    ang = geom.angles_array()
    c, s = np.cos(ang), np.sin(ang)
    x0, y0 = float(v.x_coords()[0]), float(v.y_coords()[0])
    sod = geom.sod
    hx, hy = v.dx / 2.0, v.dy / 2.0

    def grp(gathered_x: bool):
        if gathered_x:
            # gi -> x, li -> y
            Aq, Bq = -s * v.dx, c * v.dy
            Al, Bl = -c * v.dx, -s * v.dy
            Arx, Brx = v.dx * np.ones_like(c), np.zeros_like(c)
            Ary, Bry = np.zeros_like(c), v.dy * np.ones_like(c)
        else:
            Aq, Bq = c * v.dy, -s * v.dx
            Al, Bl = -s * v.dy, -c * v.dx
            Arx, Brx = np.zeros_like(c), v.dx * np.ones_like(c)
            Ary, Bry = v.dy * np.ones_like(c), np.zeros_like(c)
        Cq = c * y0 - s * x0
        Cl = sod - (c * x0 + s * y0)
        Crx = x0 - sod * c
        Cry = y0 - sod * s
        cols = [Aq, Bq, Cq, Al, Bl, Cl, Arx, Brx, Crx, Ary, Bry, Cry]
        for sx in (-hx, hx):
            for sy in (-hy, hy):
                cols.append(c * sy - s * sx)            # dq
                cols.append(-(c * sx + s * sy))         # dl
        return np.stack(cols, -1).astype(np.float32)

    gx = np.abs(s) >= np.abs(c)
    px, py = grp(True), grp(False)
    idx_x = np.nonzero(gx)[0]
    idx_y = np.nonzero(~gx)[0]
    return px[idx_x], py[idx_y], np.concatenate([idx_x, idx_y])


def _tile_footprint(P, gi0, lif, *, bg: int, Wu: int, u0: float, du: float,
                    sdd: float, dxv: float, zt, nu: int, align: int):
    """Everything the exact cone FP and BP derive for one (view, line li,
    gathered tile starting at ``gi0``), from one set of expressions so the
    pair stays an exact transpose:

    * ``ustart``: start of the ``Wu``-wide detector-column window that holds
      the tile's footprints (centre projections of its first and last
      voxel, aligned by ``tune.window_start``);
    * ``wgt`` (bg, Wu): the corner-projected trapezoid weights of each
      gathered element over the window's columns;
    * ``mag`` (bg, 1) and ``obl`` (bg, nz): each element's axial
      magnification and the obliquity of each of its z voxels."""
    Aq, Bq, Cq, Al, Bl, Cl = P[:6]
    q0 = Bq * lif + Cq
    l0 = Bl * lif + Cl

    def uc_of(gi):
        qg = Aq * gi + q0
        lg = jnp.maximum(Al * gi + l0, _EPS)
        return sdd * qg / lg

    ustart = tune.window_start((uc_of(gi0) - u0) / du,
                               (uc_of(gi0 + (bg - 1)) - u0) / du,
                               Wu, nu, align)
    gi_abs = gi0 + tune.iota_f32((bg, 1), 0)                 # (bg, 1)
    t0, t1, t2, t3, h, rt2 = _corner_trapezoid(
        P, gi_abs, q0, l0, lif, sdd, dxv)                    # (bg, 1)
    uk = u0 + (ustart + tune.iota_f32((1, Wu), 1)) * du
    el = uk - du / 2.0                                       # (1, Wu)
    wgt = trapezoid_pixel_weight(el, el + du, t0, t1, t2, t3, h)
    mag = sdd / jnp.maximum(Al * gi_abs + l0, _EPS)          # (bg, 1)
    obl = jnp.sqrt(1.0 + (zt * zt) / jnp.maximum(rt2, _EPS))  # (bg, nz)
    return ustart, wgt, mag, obl


def _axial_overlap(mag_g, obl_g, zt, elv, dz: float, dv: float):
    """(bv, nz) axial resample of one gathered element: the rect overlap of
    each z voxel, magnified by ``mag_g`` (1, 1), with each of the tile's
    ``bv`` detector rows (lower edges ``elv`` (bv, 1)), times the voxel's
    obliquity ``obl_g`` (1, nz).  The overlap spans the whole z line
    (lanes): Mosaic slices lanes only at static or 128-aligned offsets."""
    vlo = (zt - dz / 2.0) * mag_g                            # (1, nz)
    vhi = (zt + dz / 2.0) * mag_g
    ov = jnp.maximum(jnp.minimum(vhi, elv + dv)
                     - jnp.maximum(vlo, elv), 0.0) / dv      # (bv, nz)
    return ov * obl_g


def _fp_cone_kernel(params_ref,        # SMEM (n_views, 20)
                    f_ref,             # VMEM (bg, nz) volume tile (z lanes)
                    out_ref,           # VMEM (1, NU, bv) u-major sino stripe
                    *, Wu: int, u0: float, du: float, v0: float, dv: float,
                    z0c: float, dz: float, sdd: float, dxv: float,
                    nu: int, nz: int, bg: int, bv: int, nav: int,
                    align: int):
    """One program: scatter one (bg gathered elements, nz) volume tile of
    line ``li`` into one view's resident (NU, bv) detector stripe — the
    exact transpose of ``_bp_cone_kernel``:

    * axial: each gathered element's (bv, nz) overlap matrix (built once
      per view, v tile and element, as in the BP) maps its z line onto the
      tile's ``bv`` detector rows on the MXU;
    * transaxial: the (bg, Wu) corner-projected weights, contracted over
      the gathered elements, add the rows into the ``Wu``-column window.
    """
    a = pl.program_id(0)
    vb = pl.program_id(1)
    li = pl.program_id(2)
    gt = pl.program_id(3)

    @pl.when((li == 0) & (gt == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Batched runs fold the batch into the view grid axis; the params table
    # stays (n_views, 20) in SMEM and the view index wraps per sample.
    P = [params_ref[jax.lax.rem(a, nav), i] for i in range(20)]
    lif = li.astype(jnp.float32)
    gi0 = (gt * bg).astype(jnp.float32)
    elv = v0 + (vb * bv) * dv - dv / 2.0 + dv * tune.iota_f32((bv, 1), 0)
    zt = z0c + dz * tune.iota_f32((1, nz), 1)                # (1, nz)
    ustart, wgt, mag, obl = _tile_footprint(
        P, gi0, lif, bg=bg, Wu=Wu, u0=u0, du=du, sdd=sdd, dxv=dxv, zt=zt,
        nu=nu, align=align)

    # Per-element axial resample; row g of the tile is contracted alone by
    # masking the others.
    fv = f_ref[...]                                          # (bg, nz)
    row = jax.lax.broadcasted_iota(jnp.int32, (bg, 1), 0)
    rows = jnp.zeros((bg, bv), jnp.float32)
    for g in range(bg):
        ovo = _axial_overlap(mag[g:g + 1, :], obl[g:g + 1, :], zt, elv,
                             dz, dv)                         # (bv, nz)
        rows = rows + precision.mxu_dot(
            jnp.where(row == g, fv, 0.0), precision.cast_like(ovo, fv),
            ((1,), (1,)))                                    # (bg, bv)
    precision.store_tile(out_ref, (0, pl.ds(ustart, Wu), slice(None)),
                         precision.mxu_dot(wgt, rows, ((0,), (0,))))


def _run_group(fb, params: np.ndarray, geom: CTGeometry, gathered_x: bool,
               bg: int, bv: int):
    """fb: (B, nx, ny, nz) batch of volumes.  The batch is folded into the
    view grid axis: grid step ``a`` covers view ``a % na`` of sample
    ``a // na`` (volumes stacked along the gathered axis; the SMEM params
    table is *not* duplicated per sample).  Returns (B, na_group, NUp, NVp)."""
    if params.shape[0] == 0:
        return None
    vol = geom.vol
    B = fb.shape[0]
    align = tune.sublane_tile(fb.dtype)
    with jax.named_scope("sf_cone_fp_pre"):
        # Pad the gathered axis to whole bg tiles (and the sublane tile, so
        # the line-major layout adds no padding of its own).
        ax = 1 if gathered_x else 2
        pad = [(0, 0)] * 4
        pad[ax] = (0, tune.round_up(fb.shape[ax], math.lcm(bg, align))
                   - fb.shape[ax])
        fs, ngp = _line_major_batch(jnp.pad(fb, pad), gathered_x)
    nl, _, nz = fs.shape
    na = params.shape[0]
    ngb = ngp // bg
    nu_ = tune.round_up(geom.n_cols, align)
    nvp = tune.round_up(geom.n_rows, bv)
    Wu = tune.aligned_window(_u_window_size_div(geom, bg), nu_, align)
    kernel = functools.partial(
        _fp_cone_kernel, Wu=Wu,
        u0=float(geom.u_coords()[0]), du=geom.pixel_width,
        v0=float(geom.v_coords()[0]), dv=geom.pixel_height,
        z0c=float(vol.z_coords()[0]), dz=vol.dz, sdd=geom.sdd, dxv=vol.dx,
        nu=nu_, nz=nz, bg=bg, bv=bv, nav=na, align=align)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * na, nvp // bv, nl, ngb),
            in_specs=[pl.BlockSpec((None, bg, nz),
                                   lambda a, vb, l, gt, *_:
                                   (l, a // na * ngb + gt, 0))],
            out_specs=pl.BlockSpec((1, nu_, bv),
                                   lambda a, vb, l, gt, *_: (a, 0, vb)),
        ),
        # output buffer is the cross-step accumulator: always f32
        out_shape=jax.ShapeDtypeStruct((B * na, nu_, nvp), jnp.float32),
        interpret=tune._interpret(),
        name=f"sf_cone_fp_g{0 if gathered_x else 1}",
    )(jnp.asarray(params), fs)
    with jax.named_scope("sf_cone_fp_post"):
        return out.reshape(B, na, nu_, nvp)


@obs.spanned("kernels.fp_cone")
def fp_cone_sf_pallas(f, geom: CTGeometry, bg: Optional[int] = None,
                      bv: Optional[int] = None,
                      config: Optional[tune.KernelConfig] = None,
                      compute_dtype=None):
    """f: (nx, ny, nz) -> sino (n_angles, n_rows, n_cols), or batched
    f: (batch, nx, ny, nz) -> (batch, ...).  Flat detector.  ``bg`` tiles
    the gathered volume axis, as in the BP this kernel is the transpose of."""
    if geom.geom_type != "cone" or geom.detector_type != "flat":
        raise ValueError(
            f"fp_cone_sf_pallas needs a flat-detector cone geometry, got "
            f"geom_type={geom.geom_type!r} detector_type="
            f"{getattr(geom, 'detector_type', None)!r}; curved-detector "
            f"cone runs through the ref backend")
    if f.ndim not in (3, 4):
        raise ValueError(f"expected 3D or batched 4D volume, got {f.shape}")
    batched = f.ndim == 4
    out_dtype = f.dtype
    cdt = precision.resolve(compute_dtype, f.dtype)
    with jax.named_scope("sf_cone_fp_pre"):
        fb = precision.cast_in(f if batched else f[None], cdt)
    cfg = tune.resolve_config(geom, fb.shape[0], config, dtype=cdt,
                              bg=bg, bv=bv)
    px, py, order = _view_params_cone(geom)
    outs = []
    o1 = _run_group(fb, px, geom, True, cfg.bg, cfg.bv)
    if o1 is not None:
        outs.append(o1)
    o2 = _run_group(fb, py, geom, False, cfg.bg, cfg.bv)
    if o2 is not None:
        outs.append(o2)
    with jax.named_scope("sf_cone_fp_post"):
        out = jnp.concatenate(outs, axis=1)                # (B, na, NUp, NVp)
        out = out[:, :, :geom.n_cols, :geom.n_rows]
        inv = np.argsort(order)
        out = jnp.swapaxes(out[:, inv], 2, 3).astype(out_dtype)
        return out if batched else out[0]                  # (B, na, nv, nu)


# --------------------------------------------------------------------------- #
# Backprojection kernel (exact transpose)
# --------------------------------------------------------------------------- #
def _bp_cone_kernel(params_ref,        # SMEM (n_views, 20)
                    q_ref,             # VMEM (bab, NU, bv) u-major sino stripes
                    out_ref,           # VMEM (bg, nz) volume tile (z lanes)
                    *, Wu: int, u0: float, du: float, v0: float, dv: float,
                    z0c: float, dz: float, sdd: float, dxv: float,
                    nu: int, nz: int, bg: int, bv: int, bab: int, ngb: int,
                    align: int):
    """One program: accumulate ``bab`` views x ``bv`` detector rows into one
    (bg gathered elements, nz) volume tile — the exact transpose of
    ``_fp_cone_kernel``:

    * transaxial: the same (bg, Wu) corner-projected weights gather the
      (Wu, bv) sinogram window into each element's detector rows;
    * axial: each gathered element's (bv, nz) overlap matrix maps its rows
      back onto the volume line's z lanes via one MXU matvec per element.
    """
    gall = pl.program_id(0)
    li = pl.program_id(1)
    vb = pl.program_id(2)
    ab = pl.program_id(3)

    @pl.when((vb == 0) & (ab == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lif = li.astype(jnp.float32)
    # Batched runs fold the batch into the gathered-output grid axis; the
    # params table stays (n_views, 20) in SMEM shared across samples.
    gi0 = (jax.lax.rem(gall, ngb) * bg).astype(jnp.float32)
    elv = v0 + (vb * bv) * dv - dv / 2.0 + dv * tune.iota_f32((bv, 1), 0)
    zt = z0c + dz * tune.iota_f32((1, nz), 1)                # (1, nz)
    row = jax.lax.broadcasted_iota(jnp.int32, (bg, 1), 0)

    acc = jnp.zeros((bg, nz), jnp.float32)
    for j in range(bab):
        P = [params_ref[ab * bab + j, i] for i in range(20)]
        ustart, wgt, mag, obl = _tile_footprint(
            P, gi0, lif, bg=bg, Wu=Wu, u0=u0, du=du, sdd=sdd, dxv=dxv, zt=zt,
            nu=nu, align=align)
        qwin = q_ref[j, pl.ds(ustart, Wu), :]                # (Wu, bv)
        rows = precision.mxu_dot(precision.cast_like(wgt, qwin),
                                 qwin)                       # (bg, bv)
        # Transposed per-element axial resample: every gathered element has
        # its own magnification, so its bv u-contracted detector rows map
        # through an element-specific (bv, nz) overlap matrix onto z lanes.
        # Row g of the tile is contracted alone by masking the others.
        for g in range(bg):
            acc = acc + precision.mxu_dot(
                jnp.where(row == g, rows, 0.0),
                _axial_overlap(mag[g:g + 1, :], obl[g:g + 1, :], zt, elv,
                               dz, dv))                      # (bg, nz)
    precision.store_tile(out_ref, slice(None), acc)


def _run_bp_group(q, params: np.ndarray, geom: CTGeometry, gathered_x: bool,
                  bg: int, bv: int, bab: int):
    """q: (B, na_group, n_cols, n_rows) u-major sino slice for this view
    group.  The batch is folded into the gathered-output grid axis (the
    transpose of the FP's view-axis folding).  Returns this group's volume
    accumulator (B, nx, ny, nz)."""
    vol = geom.vol
    ng, nl = (vol.nx, vol.ny) if gathered_x else (vol.ny, vol.nx)
    nz = vol.nz
    align = tune.sublane_tile(q.dtype)
    with jax.named_scope("sf_cone_bp_pre"):
        q = fp_par._pad_cols(q, align)
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
    Wu = tune.aligned_window(_u_window_size_div(geom, bg), nu_, align)
    kernel = functools.partial(
        _bp_cone_kernel, Wu=Wu,
        u0=float(geom.u_coords()[0]), du=geom.pixel_width,
        v0=float(geom.v_coords()[0]), dv=geom.pixel_height,
        z0c=float(vol.z_coords()[0]), dz=vol.dz, sdd=geom.sdd, dxv=vol.dx,
        nu=nu_, nz=nz, bg=bg, bv=bv, bab=bab, ngb=ngb, align=align)
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
        name=f"sf_cone_bp_g{0 if gathered_x else 1}",
    )(jnp.asarray(params), qs)
    with jax.named_scope("sf_cone_bp_post"):
        return _volume_from_line_major(out, B, ng, gathered_x)


@obs.spanned("kernels.bp_cone")
def bp_cone_sf_pallas(sino, geom: CTGeometry, bg: Optional[int] = None,
                      bv: Optional[int] = None, bab: Optional[int] = None,
                      config: Optional[tune.KernelConfig] = None,
                      compute_dtype=None):
    """sino: (n_angles, n_rows, n_cols) -> volume (nx, ny, nz), or batched
    sino: (batch, ...) -> (batch, nx, ny, nz).  Flat detector.

    Exact transpose of ``fp_cone_sf_pallas`` (incl. the batched path): the
    same window, trapezoid weights and per-element axial overlap matrices,
    contracted in the adjoint direction (detector -> volume)."""
    if geom.geom_type != "cone" or geom.detector_type != "flat":
        raise ValueError(
            f"bp_cone_sf_pallas needs a flat-detector cone geometry, got "
            f"geom_type={geom.geom_type!r} detector_type="
            f"{getattr(geom, 'detector_type', None)!r}; curved-detector "
            f"cone runs through the ref backend")
    if sino.ndim not in (3, 4):
        raise ValueError(f"expected 3D or batched 4D sinogram, got {sino.shape}")
    batched = sino.ndim == 4
    out_dtype = sino.dtype
    cdt = precision.resolve(compute_dtype, sino.dtype)
    qb = sino if batched else sino[None]
    cfg = tune.resolve_config(geom, qb.shape[0], config, dtype=cdt,
                              bg=bg, bv=bv, bab=bab)
    px, py, order = _view_params_cone(geom)
    nax = px.shape[0]
    with jax.named_scope("sf_cone_bp_pre"):
        q = jnp.swapaxes(qb, 2, 3)                         # (B, na, nu, nv)
        q = precision.cast_in(q[:, order], cdt)            # group-major views
        acc = jnp.zeros((qb.shape[0],) + geom.vol.shape, jnp.float32)
    if nax:
        o = _run_bp_group(q[:, :nax], px, geom, True, cfg.bg, cfg.bv, cfg.bab)
        with jax.named_scope("sf_cone_bp_post"):
            acc = acc + o
    if py.shape[0]:
        o = _run_bp_group(q[:, nax:], py, geom, False, cfg.bg, cfg.bv,
                          cfg.bab)
        with jax.named_scope("sf_cone_bp_post"):
            acc = acc + o
    with jax.named_scope("sf_cone_bp_post"):
        acc = acc.astype(out_dtype)
        return acc if batched else acc[0]


# --------------------------------------------------------------------------- #
# Packed (lane-packed) cone pair: small-cone-angle axial pre-resample
# --------------------------------------------------------------------------- #
def _z_overlap_cone_packed(geom: CTGeometry) -> np.ndarray:
    """(nz, nv) axial pre-resample matrix at the *central* magnification.

    The exact cone kernel resamples each volume z-line onto detector rows at
    the per-voxel magnification ``sdd/ell`` — that per-lane dependence is
    what blocks lane packing.  The packed approximation freezes the
    magnification at its rotation-axis value ``mag0 = sdd/sod`` (and the
    axial obliquity at the central ray's ``sqrt(1 + z^2/sod^2)``), making
    the z -> detector-row map voxel-independent: it becomes one (nz, nv)
    rect-overlap matrix applied *outside* the kernel, exactly like the
    parallel/fan axial separation.  The remaining transaxial contraction is
    the fan kernel verbatim, so batch x n_rows lane packing applies.

    Error: a z-plane at height ``z`` lands ``z * (sdd/ell - mag0)`` mm from
    its exact row; see :func:`cone_packed_row_shift` for the worst case.
    """
    v = geom.vol
    mag0 = geom.sdd / geom.sod
    dv = geom.pixel_height
    zc = v.z_coords().astype(np.float64)[:, None]            # (nz, 1)
    ve = geom.v_coords().astype(np.float64)[None, :]         # (1, nv)
    vlo = (zc - v.dz / 2.0) * mag0
    vhi = (zc + v.dz / 2.0) * mag0
    ov = np.maximum(np.minimum(vhi, ve + dv / 2.0)
                    - np.maximum(vlo, ve - dv / 2.0), 0.0) / dv
    obl = np.sqrt(1.0 + (zc / geom.sod) ** 2)                # central ray
    return (ov * obl).astype(np.float32)


def _z_edge_extent(geom: CTGeometry) -> float:
    """|z| of the outermost voxel *edge* (mm) — the worst-case height."""
    v = geom.vol
    return v.nz * v.dz / 2.0 + abs(v.offset_z)


def half_cone_tangent(geom: CTGeometry) -> float:
    """tan of the half-cone angle subtended by the volume's z extent at the
    source (``z_max / sod`` — the small parameter of the approximation)."""
    return _z_edge_extent(geom) / geom.sod


def cone_packed_row_shift(geom: CTGeometry) -> float:
    """Worst-case axial footprint displacement of the packed approximation,
    in *detector-row units*.

    A voxel at transaxial source distance ``ell`` projects its z-extent at
    magnification ``sdd/ell``; the packed matrix uses ``mag0 = sdd/sod``.
    Over the volume disk ``ell`` ranges in [sod - R, sod + R], so a footprint
    edge at height ``z`` is displaced by at most::

        |z| * max(sdd/(sod-R) - mag0, mag0 - sdd/(sod+R))
          =  z_max * mag0 * R / (sod - R)        [mm on the detector]

    Equivalently ``tan(theta_half) * sdd * R / (sod - R)`` with theta_half
    the half-cone angle — the shift is *first order* in the cone angle and
    vanishes in the fan limit.
    """
    r = geom.vol.radius
    mag0 = geom.sdd / geom.sod
    dmag = max(geom.sdd / max(geom.sod - r, 1e-3) - mag0,
               mag0 - geom.sdd / (geom.sod + r))
    return _z_edge_extent(geom) * dmag / geom.pixel_height


def cone_packed_error_bound(geom: CTGeometry) -> float:
    """Documented bound on the relative L2 sinogram error of the packed
    pair vs the exact cone pair (docs/KERNELS.md derives it).

    Two mismatch sources, both functions of the half-cone angle:

    * footprint displacement: every (voxel, z-plane) row-overlap window
      shifts by at most ``s = cone_packed_row_shift(geom)`` rows, and the
      normalized rect-overlap weights are 2-Lipschitz in the shift (a box
      edge moves through at most ``s`` rows on each side), giving a
      relative weight perturbation <= 2 s;
    * obliquity: ``sqrt(1 + z^2/ell_t^2)`` is evaluated at ``ell_t = sod``
      instead of the true transaxial distance, a relative error of at most
      ``0.5 * tan(theta_half)^2 * ((sod/(sod-R))^2 - 1)`` (second order).
    """
    r = geom.vol.radius
    s = cone_packed_row_shift(geom)
    t = half_cone_tangent(geom)
    obl = 0.5 * (t ** 2) * ((geom.sod / max(geom.sod - r, 1e-3)) ** 2 - 1.0)
    return 2.0 * s + obl


@obs.spanned("kernels.fp_cone_packed")
def fp_cone_packed(f, geom: CTGeometry, bu: Optional[int] = None,
                   bv: Optional[int] = None, ba: Optional[int] = None,
                   config: Optional[tune.KernelConfig] = None,
                   compute_dtype=None):
    """Lane-packed cone forward projection (axial pre-resample).

    f: (nx, ny, nz) -> sino (n_angles, n_rows, n_cols), or batched
    f: (batch, nx, ny, nz) -> (batch, ...) with ``batch * n_rows`` detector
    rows folded onto the 128-lane axis (the fan kernel's packing, applied to
    the cone transaxial footprint).  Valid for small cone angles — callers
    go through ``ops``/``Projector`` ``mode=`` dispatch, which gates on
    :func:`cone_packed_error_bound`."""
    if geom.geom_type != "cone" or geom.detector_type != "flat":
        raise NotImplementedError(
            "packed cone pair supports flat-detector cone geometries only, "
            f"got {geom.geom_type}/{geom.detector_type}")
    if f.ndim not in (3, 4):
        raise ValueError(f"expected 3D or batched 4D volume, got {f.shape}")
    from repro.kernels import fp_fan                 # late: fan imports us
    batch = f.shape[0] if f.ndim == 4 else 1
    out_dtype = f.dtype
    cdt = precision.resolve(compute_dtype, f.dtype)
    cfg = tune.resolve_config(geom, batch, config, dtype=cdt,
                              bu=bu, bv=bv, ba=ba, packed=True)
    Fz = jnp.asarray(_z_overlap_cone_packed(geom))             # (nz, nv)
    if f.ndim == 3:
        with jax.named_scope("sf_cone_packed_fp_axial"):
            g = jnp.einsum("xyz,zv->xyv", f, Fz)               # pre-resample
        out = fp_fan._fp_core(precision.cast_in(g, cdt), geom, cfg)
        return jnp.swapaxes(out, 1, 2).astype(out_dtype)       # (na, nv, nu)
    with jax.named_scope("sf_cone_packed_fp_axial"):
        g = jnp.einsum("bxyz,zv->xybv", f, Fz)                 # (nx, ny, B, nv)
    g = g.reshape(geom.vol.nx, geom.vol.ny, batch * geom.n_rows)
    out = fp_fan._fp_core(precision.cast_in(g, cdt), geom, cfg)
    out = out.reshape(geom.n_angles, geom.n_cols, batch, geom.n_rows)
    return jnp.transpose(out, (2, 0, 3, 1)).astype(out_dtype)  # (B, na, nv, nu)


@obs.spanned("kernels.bp_cone_packed")
def bp_cone_packed(sino, geom: CTGeometry, bg: Optional[int] = None,
                   bv: Optional[int] = None, bab: Optional[int] = None,
                   bs: Optional[int] = None,
                   config: Optional[tune.KernelConfig] = None,
                   compute_dtype=None):
    """Exact transpose of ``fp_cone_packed`` (incl. the batched path): the
    fan BP kernel's transposed transaxial contraction followed by the
    transposed axial pre-resample einsum."""
    if geom.geom_type != "cone" or geom.detector_type != "flat":
        raise NotImplementedError(
            "packed cone pair supports flat-detector cone geometries only, "
            f"got {geom.geom_type}/{geom.detector_type}")
    if sino.ndim not in (3, 4):
        raise ValueError(f"expected 3D or batched 4D sinogram, got {sino.shape}")
    from repro.kernels import fp_fan                 # late: fan imports us
    batch = sino.shape[0] if sino.ndim == 4 else 1
    out_dtype = sino.dtype
    cdt = precision.resolve(compute_dtype, sino.dtype)
    cfg = tune.resolve_config(geom, batch, config, dtype=cdt,
                              bg=bg, bv=bv, bab=bab, bs=bs, packed=True)
    Fz = jnp.asarray(_z_overlap_cone_packed(geom))             # (nz, nv)
    if sino.ndim == 3:
        q = precision.cast_in(jnp.swapaxes(sino, 1, 2), cdt)   # (na, nu, nv)
        acc = fp_fan._bp_core(q, geom, cfg)                    # (nx, ny, nv)
        with jax.named_scope("sf_cone_packed_bp_axial"):
            return jnp.einsum("xyv,zv->xyz", acc, Fz).astype(out_dtype)
    q = jnp.transpose(sino, (1, 3, 0, 2))                      # (na, nu, B, nv)
    q = q.reshape(geom.n_angles, geom.n_cols, batch * geom.n_rows)
    acc = fp_fan._bp_core(precision.cast_in(q, cdt), geom, cfg)
    acc = acc.reshape(geom.vol.nx, geom.vol.ny, batch, geom.n_rows)
    with jax.named_scope("sf_cone_packed_bp_axial"):
        return jnp.einsum("xybv,zv->bxyz", acc, Fz).astype(out_dtype)


def fp_cone_packed_ref(f, geom: CTGeometry):
    """jnp oracle for the packed pair: the fan transaxial oracle with the
    central-magnification axial pre-resample — differentiable, runs
    everywhere, and the cross-check for ``fp_cone_packed``."""
    return ref.fp_fan_sf(f, geom, z_overlap=_z_overlap_cone_packed(geom))


def bp_cone_packed_ref(sino, geom: CTGeometry):
    """Exact linear transpose of the packed oracle (via jax.vjp)."""
    f0 = jnp.zeros(geom.vol.shape, sino.dtype)
    _, vjp = jax.vjp(lambda x: fp_cone_packed_ref(x, geom), f0)
    return vjp(sino)[0]


def bp_cone_sf_ref(sino, geom: CTGeometry,
                   config: Optional[tune.KernelConfig] = None):
    """Adjoint via the jnp oracle (exact transpose of the oracle forward).
    Kept as the cross-check oracle for the Pallas BP kernel; the registered
    pair uses ``bp_cone_sf_pallas``."""
    return ref.adjoint(sino, geom, "sf")


def bp_cone_sf_ref_batched(sino, geom: CTGeometry,
                           config: Optional[tune.KernelConfig] = None):
    """Batched oracle adjoint (vmap over the jnp oracle)."""
    return jax.vmap(lambda q: ref.adjoint(q, geom, "sf"))(sino)


def register():
    from repro.kernels import ops
    ops.register_kernel("cone", "sf", fp_cone_sf_pallas, bp_cone_sf_pallas,
                        fp_batched=fp_cone_sf_pallas,
                        bp_batched=bp_cone_sf_pallas,
                        fp_packed=fp_cone_packed,
                        bp_packed=bp_cone_packed,
                        packed_ok=tune.packed_cone_ok)
