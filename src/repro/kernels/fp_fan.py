"""Pallas TPU kernels: fan-beam Separable-Footprint forward/back projection.

The fan beam is the cone beam with the axial part collapsed: each detector
row is an independent in-plane fan of the matching z-slab, so the axial
(z -> detector row) footprint is the *parallel-beam* angle-independent
rectangle overlap and is hoisted out of the kernel as one einsum — exactly
like ``fp_par.py``.  What remains inside the kernel is the cone kernel's
transaxial *corner-projection* trapezoid (``fp_cone.py``), evaluated per
window element with no per-lane axial resample.

Because the lane axis is purely data-parallel again, **lane packing applies
directly**: batched inputs fold ``batch x n_rows`` detector rows onto the
128-wide axis instead of vmapping the ``pallas_call`` — the fan beam is the
"pre-collapsed axial" limit of the cone beam, and the packed cone pair
(``fp_cone.fp_cone_packed``) reuses ``_fp_core``/``_bp_core`` below with a
central-magnification axial pre-resample to lane-pack small-cone-angle
batches the same way.

Detector models (``geom.detector_type``):

* ``flat``   — equispaced columns, corner projection ``u = sdd * q / ell``;
* ``curved`` — equiangular arc, ``u`` is arc length and the corner
  projection is ``u = sdd * atan2(q, ell)``.  The window-start inversion
  uses ``tan(u / sdd)`` (the geometry validator guarantees |u|/sdd < pi/2).

Both kernels share the weight math; the backprojector is the exact
transpose of the forward (same corner-projected breakpoints, transposed
contraction), so the registered pair is *matched* in the paper's sense and
fan training steps stay on-kernel end to end (as do cone steps, whose BP in
``fp_cone.py`` transposes the per-element axial resample as well).

Tile/block sizes come from :mod:`repro.kernels.tune` (``KernelConfig``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.geometry import CTGeometry
from repro.kernels import precision, tune
from repro.kernels.footprint import trapezoid_pixel_weight
from repro.kernels.fp_cone import (_corner_trapezoid, _mag_bounds,
                                   _u_window_size_div, _view_params_cone)
from repro.kernels.fp_par import _line_major, _pad_cols, _pad_views
from repro.kernels.ref import _z_overlap_matrix
from repro.runtime import obs

_EPS = 1e-9


def _curved_stretch(geom: CTGeometry) -> float:
    """For the curved detector du/dgi shrinks by cos^2(gamma) at the fan
    edge, widening the gathered-index window per u-tile by 1/cos^2."""
    if geom.detector_type != "curved":
        return 1.0
    umax = (geom.n_cols - 1) / 2.0 * geom.pixel_width + abs(geom.center_col)
    gmax = min(umax / geom.sdd, math.pi / 2 - 1e-3)
    return 1.0 / (math.cos(gmax) ** 2)


def _window_size_fan(geom: CTGeometry, bu: int) -> int:
    """Static bound on the gathered-axis window covering one u-tile (same
    construction as the cone kernel, plus the curved-detector stretch),
    before alignment (``tune.aligned_window``)."""
    du, dx = geom.pixel_width, geom.vol.dx
    mag_min, mag_max = _mag_bounds(geom)
    stretch = _curved_stretch(geom)
    span = bu * du * math.sqrt(2.0) * stretch / (dx * mag_min)
    margin = 2.0 * (math.sqrt(2.0) * dx * mag_max + du) / (dx * mag_min) + 4.0
    w = int(math.ceil(span + 2 * margin)) + 2
    return max(w, 8)


def _fan_trapezoid(P, gi, q0, l0, lif, sdd, dxv, curved):
    """Shared weight math (used by FP and BP identically, so the pair is an
    exact transpose): corner-projected trapezoid breakpoints + amplitude for
    gathered indices ``gi`` (broadcast shape).  Thin wrapper over the cone
    kernels' ``_corner_trapezoid`` (``P`` is the 20-float per-view parameter
    row of ``fp_cone._view_params_cone``); fan drops the squared ray length
    used by the cone axial obliquity."""
    t0, t1, t2, t3, h, _rt2 = _corner_trapezoid(P, gi, q0, l0, lif, sdd,
                                                dxv, curved)
    return t0, t1, t2, t3, h


# --------------------------------------------------------------------------- #
# Forward kernel
# --------------------------------------------------------------------------- #
def _fp_fan_kernel(params_ref,          # SMEM (n_views, 20)
                   g_ref,               # VMEM (NG, bv) volume line
                   out_ref,             # VMEM (ba, bu, bv) sino tile
                   *, W: int, u0: float, du: float, sdd: float, dxv: float,
                   ng: int, bu: int, bv: int, ba: int, curved: bool,
                   align: int):
    """One program: for ``ba`` consecutive views, contract a (bu, W)
    corner-projection footprint tile against the same (W, bv) volume window
    on the MXU.  Identical structure to ``fp_par._fp_kernel`` — the lane
    axis carries packed ``batch x n_rows`` rows — with the parallel affine
    ``uc`` replaced by the divergent corner projection."""
    ab = pl.program_id(0)
    ub = pl.program_id(1)
    li = pl.program_id(3)

    @pl.when(li == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lif = li.astype(jnp.float32)
    u_first = u0 + (ub * bu) * du
    u_last = u_first + (bu - 1) * du

    for j in range(ba):
        a = ab * ba + j
        P = [params_ref[a, i] for i in range(20)]
        Aq, Bq, Cq, Al, Bl, Cl = P[:6]
        q0 = Bq * lif + Cq
        l0 = Bl * lif + Cl

        # window start: invert the center projection u(gi)
        def gi_of(u):
            if curved:
                t = jnp.tan(u / sdd)
                den = Aq - t * Al
                den = jnp.where(jnp.abs(den) > 1e-6,
                                den, jnp.where(den >= 0, 1e-6, -1e-6))
                return (t * l0 - q0) / den
            den = sdd * Aq - u * Al
            den = jnp.where(jnp.abs(den) > 1e-6,
                            den, jnp.where(den >= 0, 1e-6, -1e-6))
            return (u * l0 - sdd * q0) / den

        g1, g2 = gi_of(u_first), gi_of(u_last)
        start = tune.window_start(g1, g2, W, ng, align)

        win = g_ref[pl.ds(start, W), :]                        # (W, bv)
        gi = start + tune.iota_f32((1, W), 1)                  # (1, W)
        t0, t1, t2, t3, h = _fan_trapezoid(P, gi, q0, l0, lif, sdd, dxv,
                                           curved)

        uk = u_first + du * tune.iota_f32((bu, 1), 0)
        el = uk - du / 2.0                                     # (bu, 1)
        wgt = trapezoid_pixel_weight(el, el + du, t0, t1, t2, t3, h)
        precision.store_tile(out_ref, j, precision.mxu_dot(
            precision.cast_like(wgt, win), win))


def _run_fp_group(g, params: np.ndarray, geom: CTGeometry, gathered_x: bool,
                  bu: int, bv: int, ba: int = 1):
    """g: (nx, ny, NVp) volume with the lane axis already padded to a bv
    multiple (NVp lanes = packed batch * n_rows)."""
    if params.shape[0] == 0:
        raise ValueError(
            "empty view group reached the fan Pallas kernel; callers "
            "(_fp_core/_bp_core) must skip groups with no views")
    g = _line_major(g, gathered_x)
    nl, ng, nvp = g.shape
    na = params.shape[0]
    params, _, ba = _pad_views(params, ba)     # padded views dropped after
    nap = params.shape[0]
    nup = tune.round_up(geom.n_cols, bu)
    align = tune.sublane_tile(g.dtype)
    W = tune.aligned_window(_window_size_fan(geom, bu), ng, align)
    grid = (nap // ba, nup // bu, nvp // bv, nl)
    kernel = functools.partial(
        _fp_fan_kernel, W=W, u0=float(geom.u_coords()[0]),
        du=geom.pixel_width, sdd=geom.sdd, dxv=geom.vol.dx, ng=ng,
        bu=bu, bv=bv, ba=ba, curved=geom.detector_type == "curved",
        align=align)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((None, ng, bv),
                                   lambda ab, ub, vb, l, *_: (l, 0, vb))],
            out_specs=pl.BlockSpec((ba, bu, bv),
                                   lambda ab, ub, vb, l, *_: (ab, ub, vb)),
        ),
        # f32 cross-step accumulator regardless of the tile dtype.
        out_shape=jax.ShapeDtypeStruct((nap, nup, nvp), jnp.float32),
        interpret=tune._interpret(),
    )(jnp.asarray(params), g)
    return out[:na]


def _fp_core(g, geom: CTGeometry, cfg: tune.KernelConfig):
    """g: (nx, ny, NV) lane-packed axial-footprint volume.  Returns the
    u-major sinogram (n_angles, n_cols, NV)."""
    nv_lanes = g.shape[2]
    nvp = tune.round_up(nv_lanes, cfg.bv)
    g = jnp.pad(g, ((0, 0), (0, 0), (0, nvp - nv_lanes)))
    px, py, order = _view_params_cone(geom)
    outs = []
    if px.shape[0]:
        outs.append(_run_fp_group(g, px, geom, True, cfg.bu, cfg.bv, cfg.ba))
    if py.shape[0]:
        outs.append(_run_fp_group(g, py, geom, False, cfg.bu, cfg.bv, cfg.ba))
    out = jnp.concatenate(outs, axis=0)                        # (na, NUp, NVp)
    out = out[:, :geom.n_cols, :nv_lanes]
    inv = np.argsort(order)
    return out[inv]


@obs.spanned("kernels.fp_fan")
def fp_fan_sf_pallas(f, geom: CTGeometry, bu: Optional[int] = None,
                     bv: Optional[int] = None, ba: Optional[int] = None,
                     config: Optional[tune.KernelConfig] = None,
                     compute_dtype=None):
    """f: (nx, ny, nz) -> sino (n_angles, n_rows, n_cols), or lane-packed
    batched f: (batch, nx, ny, nz) -> (batch, n_angles, n_rows, n_cols).
    ``compute_dtype`` selects the tile dtype at the VMEM boundary (None =
    follow ``f.dtype``); accumulation stays f32, output is ``f.dtype``."""
    if geom.geom_type != "fan":
        raise ValueError(f"fp_fan_sf_pallas needs a fan geometry, got "
                         f"geom_type={geom.geom_type!r}; dispatch through "
                         f"get_ops/forward_project for auto kernel selection")
    if f.ndim not in (3, 4):
        raise ValueError(f"expected 3D or batched 4D volume, got {f.shape}")
    batch = f.shape[0] if f.ndim == 4 else 1
    out_dtype = f.dtype
    cdt = precision.resolve(compute_dtype, f.dtype)
    cfg = tune.resolve_config(geom, batch, config, dtype=cdt,
                              bu=bu, bv=bv, ba=ba)
    Fz = jnp.asarray(_z_overlap_matrix(geom))                  # (nz, nv)
    if f.ndim == 3:
        g = jnp.einsum("xyz,zv->xyv", f, Fz)                   # axial footprint
        g = precision.cast_in(g, cdt)
        out = _fp_core(g, geom, cfg)                           # (na, nu, nv)
        return jnp.swapaxes(out, 1, 2).astype(out_dtype)       # (na, nv, nu)
    g = jnp.einsum("bxyz,zv->xybv", f, Fz)                     # (nx, ny, B, nv)
    g = g.reshape(geom.vol.nx, geom.vol.ny, batch * geom.n_rows)
    g = precision.cast_in(g, cdt)
    out = _fp_core(g, geom, cfg)                               # (na, nu, B*nv)
    out = out.reshape(geom.n_angles, geom.n_cols, batch, geom.n_rows)
    return jnp.transpose(out, (2, 0, 3, 1)).astype(out_dtype)


# --------------------------------------------------------------------------- #
# Backprojection kernel (exact transpose)
# --------------------------------------------------------------------------- #
def _bp_fan_kernel(params_ref,          # SMEM (n_views, 20)
                   q_ref,               # VMEM (bab, NU, bv) sino stripes
                   out_ref,             # VMEM (bs*bg, bv) volume tile
                   *, Wu: int, u0: float, du: float, sdd: float, dxv: float,
                   nu: int, bg: int, bv: int, bab: int, bs: int,
                   curved: bool, align: int):
    """One program: accumulate ``bab`` views into ``bs`` consecutive
    (bg, bv) volume sub-tiles — the exact transpose of ``_fp_fan_kernel``
    (same corner-projected breakpoints, transposed contraction).  Stripe
    reuse (bs > 1) serves ``bs`` gathered sub-tiles per stripe residency;
    see ``fp_par._bp_kernel``."""
    gb = pl.program_id(0)
    li = pl.program_id(1)
    ab = pl.program_id(3)

    @pl.when(ab == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lif = li.astype(jnp.float32)
    subs = [jnp.zeros((bg, bv), jnp.float32) for _ in range(bs)]
    for j in range(bab):
        a = ab * bab + j
        P = [params_ref[a, i] for i in range(20)]
        Aq, Bq, Cq, Al, Bl, Cl = P[:6]
        q0 = Bq * lif + Cq
        l0 = Bl * lif + Cl

        def uc_of(gi):
            qg = Aq * gi + q0
            lg = jnp.maximum(Al * gi + l0, _EPS)
            if curved:
                return sdd * jnp.arctan2(qg, lg)
            return sdd * qg / lg

        for sj in range(bs):
            gi0 = ((gb * bs + sj) * bg).astype(jnp.float32)
            gi_abs = gi0 + tune.iota_f32((bg, 1), 0)
            uc_a = uc_of(gi0)
            uc_b = uc_of(gi0 + (bg - 1))
            ustart = tune.window_start((uc_a - u0) / du, (uc_b - u0) / du,
                                       Wu, nu, align)

            qwin = q_ref[j, pl.ds(ustart, Wu), :]              # (Wu, bv)
            t0, t1, t2, t3, h = _fan_trapezoid(P, gi_abs, q0, l0, lif, sdd,
                                               dxv, curved)    # (bg, 1)
            uk = u0 + (ustart + tune.iota_f32((1, Wu), 1)) * du
            el = uk - du / 2.0                                 # (1, Wu)
            wgt = trapezoid_pixel_weight(el, el + du, t0, t1, t2, t3, h)
            subs[sj] = subs[sj] + precision.mxu_dot(
                precision.cast_like(wgt, qwin), qwin)
    acc = subs[0] if bs == 1 else jnp.concatenate(subs, axis=0)
    precision.store_tile(out_ref, slice(None), acc)


def _run_bp_group(q, params: np.ndarray, geom: CTGeometry, gathered_x: bool,
                  bg: int, bv: int, bab: int = 1, bs: int = 1):
    """q: (na_group, NU, NVp) u-major sino slice for this view group.
    Returns the loop-axis-major volume accumulator (NL, NG, NVp)."""
    ng, nl = ((geom.vol.nx, geom.vol.ny) if gathered_x
              else (geom.vol.ny, geom.vol.nx))
    align = tune.sublane_tile(q.dtype)
    q = _pad_cols(q, align)
    na, nup, nvp = q.shape
    params, q, bab = _pad_views(params, bab, q)
    nap = params.shape[0]
    bs = max(1, min(bs, max(1, ng // bg)))    # don't block past the axis
    bstr = bg * bs                            # gathered voxels per program
    ngp = tune.round_up(ng, bstr)
    Wu = tune.aligned_window(_u_window_size_div(geom, bg), nup, align)
    grid = (ngp // bstr, nl, nvp // bv, nap // bab)
    kernel = functools.partial(
        _bp_fan_kernel, Wu=Wu, u0=float(geom.u_coords()[0]),
        du=geom.pixel_width, sdd=geom.sdd, dxv=geom.vol.dx, nu=nup,
        bg=bg, bv=bv, bab=bab, bs=bs, curved=geom.detector_type == "curved",
        align=align)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((bab, nup, bv),
                                   lambda gb, l, vb, ab, *_: (ab, 0, vb))],
            out_specs=pl.BlockSpec((None, bstr, bv),
                                   lambda gb, l, vb, ab, *_: (l, gb, vb)),
        ),
        # f32 cross-step accumulator regardless of the stripe dtype.
        out_shape=jax.ShapeDtypeStruct((nl, ngp, nvp), jnp.float32),
        interpret=tune._interpret(),
    )(jnp.asarray(params), q)
    return out[:, :ng]


def _bp_core(q, geom: CTGeometry, cfg: tune.KernelConfig):
    """q: (n_angles, n_cols, NV) u-major lane-packed sinogram.  Returns the
    transaxial volume accumulator (nx, ny, NV)."""
    nv_lanes = q.shape[2]
    nvp = tune.round_up(nv_lanes, cfg.bv)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, nvp - nv_lanes)))
    px, py, order = _view_params_cone(geom)
    q = q[order]                                               # group-major
    nax = px.shape[0]
    acc = jnp.zeros((geom.vol.nx, geom.vol.ny, nvp), jnp.float32)
    if nax:                                     # (ny, nx, NVp): transpose
        acc = acc + jnp.swapaxes(_run_bp_group(
            q[:nax], px, geom, True, cfg.bg, cfg.bv, cfg.bab, cfg.bs), 0, 1)
    if py.shape[0]:                             # (nx, ny, NVp) already
        acc = acc + _run_bp_group(q[nax:], py, geom, False,
                                  cfg.bg, cfg.bv, cfg.bab, cfg.bs)
    return acc[:, :, :nv_lanes]


@obs.spanned("kernels.bp_fan")
def bp_fan_sf_pallas(sino, geom: CTGeometry, bg: Optional[int] = None,
                     bv: Optional[int] = None, bab: Optional[int] = None,
                     bs: Optional[int] = None,
                     config: Optional[tune.KernelConfig] = None,
                     compute_dtype=None):
    """sino: (n_angles, n_rows, n_cols) -> volume (nx, ny, nz), or
    lane-packed batched sino: (batch, ...) -> (batch, nx, ny, nz).
    Exact transpose of ``fp_fan_sf_pallas`` (incl. the batched path).
    ``compute_dtype`` selects the stripe dtype at the VMEM boundary; ``bs``
    overrides the stripe-reuse blocking factor."""
    if geom.geom_type != "fan":
        raise ValueError(f"bp_fan_sf_pallas needs a fan geometry, got "
                         f"geom_type={geom.geom_type!r}; dispatch through "
                         f"get_ops/back_project for auto kernel selection")
    if sino.ndim not in (3, 4):
        raise ValueError(f"expected 3D or batched 4D sinogram, got {sino.shape}")
    batch = sino.shape[0] if sino.ndim == 4 else 1
    out_dtype = sino.dtype
    cdt = precision.resolve(compute_dtype, sino.dtype)
    cfg = tune.resolve_config(geom, batch, config, dtype=cdt,
                              bg=bg, bv=bv, bab=bab, bs=bs)
    Fz = jnp.asarray(_z_overlap_matrix(geom))                  # (nz, nv)
    if sino.ndim == 3:
        q = jnp.swapaxes(sino, 1, 2)                           # (na, nu, nv)
        q = precision.cast_in(q, cdt)
        acc = _bp_core(q, geom, cfg)                           # (nx, ny, nv)
        return jnp.einsum("xyv,zv->xyz", acc, Fz).astype(out_dtype)
    q = jnp.transpose(sino, (1, 3, 0, 2))                      # (na, nu, B, nv)
    q = q.reshape(geom.n_angles, geom.n_cols, batch * geom.n_rows)
    q = precision.cast_in(q, cdt)
    acc = _bp_core(q, geom, cfg)                               # (nx, ny, B*nv)
    acc = acc.reshape(geom.vol.nx, geom.vol.ny, batch, geom.n_rows)
    return jnp.einsum("xybv,zv->bxyz", acc, Fz).astype(out_dtype)


def register():
    from repro.kernels import ops
    ops.register_kernel("fan", "sf", fp_fan_sf_pallas, bp_fan_sf_pallas,
                        fp_batched=fp_fan_sf_pallas,
                        bp_batched=bp_fan_sf_pallas)
