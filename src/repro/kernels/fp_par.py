"""Pallas TPU kernels: parallel-beam Separable-Footprint forward/back projection.

TPU-native design (see DESIGN.md §2).  LEAP's CUDA kernels are
thread-per-output with 3D texture gathers; here each Pallas program computes a
``(bu detector columns) x (bv lanes)`` output tile for a block of ``ba`` views
by looping over the volume's *loop axis* and, per step, contracting a
``(bu, W)`` footprint-weight tile against a ``(W, bv)`` volume window on the
MXU.  The footprint weights are exact SF trapezoid-pixel integrals; the
``W``-wide window along the *gathered axis* is addressed with a scalar
``pl.dynamic_slice`` start computed from per-view affine coefficients held in
SMEM (scalar prefetch) — no gather hardware required.

Views are partitioned at trace time (geometry is static) into an
``x-gathered`` group (|sin| >= |cos|) and a ``y-gathered`` group, which run as
two ``pallas_call``s over the volume and its transpose; this replaces the
per-ray driving-axis branch of GPU implementations.

The axial (z -> detector row) part of the separable footprint is an
angle-independent banded matrix for parallel beams and is applied as a single
einsum outside the kernel (it maps to the MXU directly).

**Lane packing.**  Because the axial part is hoisted out, the kernel's lane
axis is purely data-parallel: every lane sees the same footprint weights and
the same gathered-axis window.  Batched inputs therefore fold the batch
dimension *into the lanes* — ``batch x n_rows`` detector rows are packed onto
the 128-wide axis — instead of vmapping the ``pallas_call`` per sample.  For
the paper's flagship 2D limited-angle training shape (nz=1, n_rows=1) this
turns ~1/128 lane occupancy into full tiles: up to 128x more useful MXU work
per contraction.  Both public entry points accept a leading batch dim.

Tile/block sizes come from :mod:`repro.kernels.tune` (``KernelConfig``);
the old hard-coded ``BU``/``BV`` module constants are gone.

Both kernels share the weight math; the backprojector is the exact transpose
of the forward (same coefficients, transposed contraction), so the pair is
*matched* in the paper's sense.

Device names.  Each stage of a wrapper runs under a ``jax.named_scope``
(``sf_par_{fp,bp}_axial``, ``_pre`` for the transposes and padding before a
kernel, ``_post`` for the slicing, reordering and sums after it), and each
kernel is named ``sf_par_{fp,bp}_g0`` (x-gathered views) or ``_g1``
(y-gathered), so a profile's ops read by program stage.
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
from repro.kernels import precision, tune
from repro.kernels.footprint import trapezoid_pixel_weight
from repro.kernels.ref import _z_overlap_matrix
from repro.runtime import obs


# --------------------------------------------------------------------------- #
# Per-view affine coefficients (static, numpy)
# --------------------------------------------------------------------------- #
def _view_params(geom: CTGeometry) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Split views into x-gathered / y-gathered groups and compute, per view,
    the coefficients of  uc(gi, li) = P*gi + Q*li + R  (detector coordinate of
    the voxel center at gathered-index gi, loop-index li) plus the SF
    trapezoid parameters (hs, hd, h)."""
    v = geom.vol
    ang = geom.angles_array()
    c, s = np.cos(ang), np.sin(ang)
    x0, y0 = float(v.x_coords()[0]), float(v.y_coords()[0])
    a = v.dx * np.abs(c)
    b = v.dx * np.abs(s)
    hs = 0.5 * (a + b)
    hd = 0.5 * np.abs(a - b)
    h = v.dx / np.maximum(np.abs(c), np.abs(s))
    gx = np.abs(s) >= np.abs(c)          # x-gathered group
    # x-gathered: gi = ix, li = iy:  uc = -s*dx*gi + c*dy*li + (c*y0 - s*x0)
    px = np.stack([-s * v.dx, c * v.dy, c * y0 - s * x0, hs, hd, h], -1)
    # y-gathered: gi = iy, li = ix:  uc =  c*dy*gi - s*dx*li + (c*y0 - s*x0)
    py = np.stack([c * v.dy, -s * v.dx, c * y0 - s * x0, hs, hd, h], -1)
    idx_x = np.nonzero(gx)[0]
    idx_y = np.nonzero(~gx)[0]
    return (px[idx_x].astype(np.float32), py[idx_y].astype(np.float32),
            np.concatenate([idx_x, idx_y]))


def _window_size(geom: CTGeometry, bu: int) -> int:
    """Static bound on the gathered-axis window covering one u-tile.
    |duc/dgi| >= dx/sqrt(2) in-group, so the tile spans <= bu*du*sqrt(2)/dx
    voxels, plus the footprint half-width margin on each side."""
    du, dx = geom.pixel_width, geom.vol.dx
    span = bu * du * math.sqrt(2.0) / dx
    margin = 2.0 * (math.sqrt(2.0) / 2.0 * dx + du) / dx + 2.0
    return max(int(math.ceil(span + 2 * margin)) + 2, 8)


def _pad_views(params: np.ndarray, block: int, q=None):
    """Pad a view group to a multiple of ``block`` views.  Params rows are
    duplicated (keeps the weight math finite); the optional sinogram data
    ``q`` is zero-padded so padded views contribute nothing.  Returns
    (params, q, clipped_block)."""
    na = params.shape[0]
    block = max(1, min(block, na))
    nap = ((na + block - 1) // block) * block
    if nap != na:
        params = np.concatenate([params, np.repeat(params[-1:],
                                                   nap - na, 0)], 0)
        if q is not None:
            q = jnp.pad(q, ((0, nap - na), (0, 0), (0, 0)))
    return params, q, block


# --------------------------------------------------------------------------- #
# Forward kernel
# --------------------------------------------------------------------------- #
def _fp_kernel(params_ref,            # SMEM (n_views, 6)
               g_ref,                 # VMEM (NG, bv) volume line
               out_ref,               # VMEM (ba, bu, bv) sino tile
               *, W: int, u0: float, du: float, ng: int, bu: int, bv: int,
               ba: int, align: int):
    """One program: for ``ba`` consecutive views, contract a (bu, W) footprint
    tile against the same (W, bv) volume window on the MXU.

    Angle-blocking (ba > 1) is the §Perf-CT hillclimb: the volume line
    g[:, l, vblock] — the dominant HBM stream — is fetched ONCE per program
    and reused for all ba views, dividing volume traffic by ba."""
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
        P = params_ref[a, 0]
        Q = params_ref[a, 1]
        R = params_ref[a, 2]
        hs = params_ref[a, 3]
        hd = params_ref[a, 4]
        h = params_ref[a, 5]

        gi_a = (u_first - R - Q * lif) / P
        gi_b = (u_last - R - Q * lif) / P
        start = tune.window_start(gi_a, gi_b, W, ng, align)

        win = g_ref[pl.ds(start, W), :]                    # (W, bv)
        gi_abs = start + tune.iota_f32((1, W), 1)          # (1, W)
        uc = P * gi_abs + Q * lif + R                      # (1, W)
        uk = u_first + du * tune.iota_f32((bu, 1), 0)
        el = uk - du / 2.0                                 # (bu, 1)
        wgt = trapezoid_pixel_weight(el, el + du,
                                     uc - hs, uc - hd, uc + hd, uc + hs, h)
        precision.store_tile(out_ref, j, precision.mxu_dot(
            precision.cast_like(wgt, win), win))


def _run_fp_group(g, params: np.ndarray, geom: CTGeometry, gathered_x: bool,
                  bu: int, bv: int, ba: int = 1):
    """g: (nx, ny, NVp) volume with the lane axis already padded to a bv
    multiple (NVp lanes = packed batch * n_rows).  Callers guard against
    empty view groups."""
    if params.shape[0] == 0:
        raise ValueError(
            "empty view group reached the parallel Pallas kernel; callers "
            "(_fp_core/_bp_core) must skip groups with no views")
    with jax.named_scope("sf_par_fp_pre"):
        g = _line_major(g, gathered_x)
    nl, ng, nvp = g.shape
    na = params.shape[0]
    params, _, ba = _pad_views(params, ba)   # padded views dropped after
    nap = params.shape[0]
    nup = tune.round_up(geom.n_cols, bu)
    align = tune.sublane_tile(g.dtype)
    W = tune.aligned_window(_window_size(geom, bu), ng, align)
    u0 = float(geom.u_coords()[0])
    grid = (nap // ba, nup // bu, nvp // bv, nl)
    kernel = functools.partial(_fp_kernel, W=W, u0=u0, du=geom.pixel_width,
                               ng=ng, bu=bu, bv=bv, ba=ba, align=align)
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
        # The output buffer is the cross-step accumulator: always f32
        # regardless of the tile dtype (the caller restores its dtype once).
        out_shape=jax.ShapeDtypeStruct((nap, nup, nvp), jnp.float32),
        interpret=tune._interpret(),
        name=f"sf_par_fp_g{0 if gathered_x else 1}",
    )(jnp.asarray(params), g)
    with jax.named_scope("sf_par_fp_post"):
        return out[:na]


def _line_major(g, gathered_x: bool):
    """(nx, ny, NV) -> (NL, NG, NV): loop axis first, so a kernel block is
    one whole (NG, bv) line plane — Mosaic tiles the last two block dims
    and refuses a 1 in the second-minor place.  The gathered axis is padded
    with zeros to the sublane tile of its dtype."""
    if gathered_x:
        g = jnp.swapaxes(g, 0, 1)
    ng = g.shape[1]
    ngp = tune.round_up(ng, tune.sublane_tile(g.dtype))
    return jnp.pad(g, ((0, 0), (0, ngp - ng), (0, 0)))


def _fp_core(g, geom: CTGeometry, cfg: tune.KernelConfig):
    """g: (nx, ny, NV) lane-packed axial-footprint volume (NV lanes carry
    batch x n_rows).  Returns the u-major sinogram (n_angles, n_cols, NV)."""
    nv_lanes = g.shape[2]
    nvp = tune.round_up(nv_lanes, cfg.bv)
    with jax.named_scope("sf_par_fp_pre"):
        g = jnp.pad(g, ((0, 0), (0, 0), (0, nvp - nv_lanes)))
    px, py, order = _view_params(geom)
    outs = []
    if px.shape[0]:
        outs.append(_run_fp_group(g, px, geom, True, cfg.bu, cfg.bv, cfg.ba))
    if py.shape[0]:
        outs.append(_run_fp_group(g, py, geom, False, cfg.bu, cfg.bv, cfg.ba))
    with jax.named_scope("sf_par_fp_post"):
        out = jnp.concatenate(outs, axis=0)                # (na, NUp, NVp)
        out = out[:, :geom.n_cols, :nv_lanes]
        inv = np.argsort(order)
        return out[inv]


@obs.spanned("kernels.fp_par")
def fp_parallel_sf_pallas(f, geom: CTGeometry, bu: Optional[int] = None,
                          bv: Optional[int] = None, ba: Optional[int] = None,
                          config: Optional[tune.KernelConfig] = None,
                          compute_dtype=None):
    """f: (nx, ny, nz) -> sino (n_angles, n_rows, n_cols), or lane-packed
    batched f: (batch, nx, ny, nz) -> (batch, n_angles, n_rows, n_cols).
    ``compute_dtype`` selects the tile dtype at the VMEM boundary
    (None = follow ``f.dtype``); accumulation is always f32 and the result
    comes back in ``f.dtype``."""
    if f.ndim not in (3, 4):
        raise ValueError(f"expected 3D or batched 4D volume, got {f.shape}")
    batch = f.shape[0] if f.ndim == 4 else 1
    out_dtype = f.dtype
    cdt = precision.resolve(compute_dtype, f.dtype)
    cfg = tune.resolve_config(geom, batch, config, dtype=cdt,
                              bu=bu, bv=bv, ba=ba)
    Fz = jnp.asarray(_z_overlap_matrix(geom))              # (nz, nv)
    if f.ndim == 3:
        with jax.named_scope("sf_par_fp_axial"):
            g = jnp.einsum("xyz,zv->xyv", f, Fz)           # axial footprint
        with jax.named_scope("sf_par_fp_pre"):
            g = precision.cast_in(g, cdt)
        out = _fp_core(g, geom, cfg)                       # (na, nu, nv) f32
        with jax.named_scope("sf_par_fp_post"):
            return jnp.swapaxes(out, 1, 2).astype(out_dtype)  # (na, nv, nu)
    # Lane-packed batch: (B, nx, ny, nz) -> lanes = B * n_rows
    with jax.named_scope("sf_par_fp_axial"):
        g = jnp.einsum("bxyz,zv->xybv", f, Fz)             # (nx, ny, B, nv)
    with jax.named_scope("sf_par_fp_pre"):
        g = g.reshape(geom.vol.nx, geom.vol.ny, batch * geom.n_rows)
        g = precision.cast_in(g, cdt)
    out = _fp_core(g, geom, cfg)                           # (na, nu, B*nv)
    with jax.named_scope("sf_par_fp_post"):
        out = out.reshape(geom.n_angles, geom.n_cols, batch, geom.n_rows)
        return jnp.transpose(out, (2, 0, 3, 1)).astype(out_dtype)


# --------------------------------------------------------------------------- #
# Backprojection kernel (exact transpose)
# --------------------------------------------------------------------------- #
def _bp_kernel(params_ref,            # SMEM (n_views, 6)
               q_ref,                 # VMEM (bab, NU, bv) sino stripes (u-major)
               out_ref,               # VMEM (bs*bg, bv) volume tile
               *, Wu: int, u0: float, du: float, nu: int, bg: int, bv: int,
               bab: int, bs: int, align: int):
    """One program: accumulate ``bab`` views into ``bs`` consecutive
    (bg, bv) volume sub-tiles.

    View-blocking (bab > 1) mirrors the forward kernel's ``ba``: the ``bab``
    sinogram stripes arrive in a single wide DMA and each sub-tile is
    read-modify-written once per block instead of once per view.

    Stripe reuse (bs > 1) blocks the gathered axis: while a stripe is
    resident in VMEM (double-buffered by the Pallas pipeline) it serves
    ``bs`` gathered-axis sub-tiles before eviction, dividing sinogram
    traffic by ``bs``; on the TPU the default covers the whole gathered
    line.  The sub-tiles are walked by a loop, each keeping its own ``Wu``
    detector window (sized by ``bg``), so weight tiles do not widen.  Every
    voxel sums the same views in the same order at any ``bs``."""
    gb = pl.program_id(0)
    li = pl.program_id(1)
    ab = pl.program_id(3)

    @pl.when(ab == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lif = li.astype(jnp.float32)
    views = [tuple(params_ref[ab * bab + j, k] for k in range(6))
             for j in range(bab)]

    def sub_tile(sj, carry):
        gi0 = ((gb * bs + sj) * bg).astype(jnp.float32)
        gi_abs = gi0 + tune.iota_f32((bg, 1), 0)
        sub = jnp.zeros((bg, bv), jnp.float32)
        for j, (P, Q, R, hs, hd, h) in enumerate(views):
            uc_a = P * gi0 + Q * lif + R
            uc_b = P * (gi0 + bg - 1) + Q * lif + R
            ustart = tune.window_start((uc_a - u0) / du, (uc_b - u0) / du,
                                       Wu, nu, align)

            qwin = q_ref[j, pl.ds(ustart, Wu), :]          # (Wu, bv)
            uc = P * gi_abs + Q * lif + R                  # (bg, 1)
            uk = u0 + (ustart + tune.iota_f32((1, Wu), 1)) * du
            el = uk - du / 2.0                             # (1, Wu)
            wgt = trapezoid_pixel_weight(el, el + du,
                                         uc - hs, uc - hd, uc + hd, uc + hs,
                                         h)
            sub = sub + precision.mxu_dot(precision.cast_like(wgt, qwin),
                                          qwin)
        precision.store_tile(out_ref, (pl.ds(pl.multiple_of(sj * bg, bg), bg),
                                       slice(None)), sub)
        return carry

    jax.lax.fori_loop(0, bs, sub_tile, 0)


def _pad_cols(q, align: int):
    """Zero-pad the detector-column axis (axis -2) of a u-major sinogram
    to a multiple of ``align``, so aligned u-windows stay inside it."""
    nu = q.shape[-2]
    pad = [(0, 0)] * q.ndim
    pad[-2] = (0, tune.round_up(nu, align) - nu)
    return jnp.pad(q, pad)


def _run_bp_group(q, params: np.ndarray, geom: CTGeometry, gathered_x: bool,
                  bg: int, bv: int, bab: int = 1, bs: int = 1):
    """q: (na_group, NU, NVp) u-major sino slice for this view group.
    Returns the loop-axis-major volume accumulator (NL, NG, NVp)."""
    ng, nl = ((geom.vol.nx, geom.vol.ny) if gathered_x
              else (geom.vol.ny, geom.vol.nx))
    align = tune.sublane_tile(q.dtype)
    with jax.named_scope("sf_par_bp_pre"):
        q = _pad_cols(q, align)
        na, nup, nvp = q.shape
        params, q, bab = _pad_views(params, bab, q)
    nap = params.shape[0]
    bs = max(1, min(bs, -(-ng // bg)))        # at most the whole padded line
    bstr = bg * bs                            # gathered voxels per program
    ngp = tune.round_up(ng, bstr)
    # Sinogram bytes the stripe BlockSpec fetches: once per program.
    obs.count("kernels.bp_par_stripe_bytes",
              (ngp // bstr) * nl * (nvp // bv) * nap * nup * bv
              * q.dtype.itemsize)
    du, dx = geom.pixel_width, geom.vol.dx
    Wu = tune.aligned_window(int(math.ceil(bg * dx / du)) + 8, nup, align)
    u0 = float(geom.u_coords()[0])
    grid = (ngp // bstr, nl, nvp // bv, nap // bab)
    kernel = functools.partial(_bp_kernel, Wu=Wu, u0=u0, du=du, nu=nup,
                               bg=bg, bv=bv, bab=bab, bs=bs, align=align)
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
        name=f"sf_par_bp_g{0 if gathered_x else 1}",
    )(jnp.asarray(params), q)
    with jax.named_scope("sf_par_bp_post"):
        return out[:, :ng]


def _bp_core(q, geom: CTGeometry, cfg: tune.KernelConfig):
    """q: (n_angles, n_cols, NV) u-major lane-packed sinogram.  Returns the
    transaxial volume accumulator (nx, ny, NV) — axial transpose not yet
    applied."""
    nv_lanes = q.shape[2]
    nvp = tune.round_up(nv_lanes, cfg.bv)
    px, py, order = _view_params(geom)
    nax = px.shape[0]
    with jax.named_scope("sf_par_bp_pre"):
        q = jnp.pad(q, ((0, 0), (0, 0), (0, nvp - nv_lanes)))
        q = q[order]                                       # group-major order
        acc = jnp.zeros((geom.vol.nx, geom.vol.ny, nvp), jnp.float32)
    if nax:                                 # (ny, nx, NVp): transpose back
        o = _run_bp_group(q[:nax], px, geom, True, cfg.bg, cfg.bv, cfg.bab,
                          cfg.bs)
        with jax.named_scope("sf_par_bp_post"):
            acc = acc + jnp.swapaxes(o, 0, 1)
    if py.shape[0]:                         # (nx, ny, NVp) already
        o = _run_bp_group(q[nax:], py, geom, False, cfg.bg, cfg.bv, cfg.bab,
                          cfg.bs)
        with jax.named_scope("sf_par_bp_post"):
            acc = acc + o
    with jax.named_scope("sf_par_bp_post"):
        return acc[:, :, :nv_lanes]


@obs.spanned("kernels.bp_par")
def bp_parallel_sf_pallas(sino, geom: CTGeometry, bg: Optional[int] = None,
                          bv: Optional[int] = None, bab: Optional[int] = None,
                          bs: Optional[int] = None,
                          config: Optional[tune.KernelConfig] = None,
                          compute_dtype=None):
    """sino: (n_angles, n_rows, n_cols) -> volume (nx, ny, nz), or lane-packed
    batched sino: (batch, ...) -> (batch, nx, ny, nz).
    Exact transpose of ``fp_parallel_sf_pallas`` (incl. the batched path).
    ``compute_dtype`` selects the stripe dtype at the VMEM boundary; ``bs``
    overrides the stripe-reuse blocking factor."""
    if sino.ndim not in (3, 4):
        raise ValueError(f"expected 3D or batched 4D sinogram, got {sino.shape}")
    batch = sino.shape[0] if sino.ndim == 4 else 1
    out_dtype = sino.dtype
    cdt = precision.resolve(compute_dtype, sino.dtype)
    cfg = tune.resolve_config(geom, batch, config, dtype=cdt,
                              bg=bg, bv=bv, bab=bab, bs=bs)
    Fz = jnp.asarray(_z_overlap_matrix(geom))              # (nz, nv)
    if sino.ndim == 3:
        with jax.named_scope("sf_par_bp_pre"):
            q = jnp.swapaxes(sino, 1, 2)                   # (na, nu, nv)
            q = precision.cast_in(q, cdt)
        acc = _bp_core(q, geom, cfg)                       # (nx, ny, nv) f32
        with jax.named_scope("sf_par_bp_axial"):
            return jnp.einsum("xyv,zv->xyz", acc, Fz).astype(out_dtype)
    with jax.named_scope("sf_par_bp_pre"):
        q = jnp.transpose(sino, (1, 3, 0, 2))              # (na, nu, B, nv)
        q = q.reshape(geom.n_angles, geom.n_cols, batch * geom.n_rows)
        q = precision.cast_in(q, cdt)
    acc = _bp_core(q, geom, cfg)                           # (nx, ny, B*nv)
    with jax.named_scope("sf_par_bp_axial"):
        acc = acc.reshape(geom.vol.nx, geom.vol.ny, batch, geom.n_rows)
        return jnp.einsum("xybv,zv->bxyz", acc, Fz).astype(out_dtype)


def register():
    from repro.kernels import ops
    ops.register_kernel("parallel", "sf", fp_parallel_sf_pallas,
                        bp_parallel_sf_pallas,
                        fp_batched=fp_parallel_sf_pallas,
                        bp_batched=bp_parallel_sf_pallas)
