"""Plain references for the benchmark's correctness checks.

Written from the definitions, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, and importing nothing of the
system under test: the geometry comes from the configuration file's
``geometry`` mapping, never from the program's objects.

* ``sf_fp_bp`` - the Separable-Footprint (Long, Fessler & Balter 2010)
  forward projection of a volume and the exact transpose applied to the
  residual, for parallel and flat-detector cone beams.  Per view, the
  transaxial footprint is one dense (n_cols x voxel-columns) matrix of
  trapezoid-over-pixel integrals, contracted on the MXU; the axial part is
  the rectangle overlap (parallel: one angle-independent matrix; cone: a
  gather over the few z voxels or detector rows each pair touches).
  Voxel columns go in blocks, so the 512^3 cells fit next to the outputs.
* ``fbp_slices`` - filtered backprojection of a parallel-beam sinogram on
  a set of z slices: the band-limited ramp (Kak & Slaney eq. 61) applied
  as a direct convolution (a Toeplitz matrix), and the linear-interpolation
  backprojector with trapezoid-rule view weights.

Conventions (mm; volume ``f[ix, iy, iz]``, sinogram ``p[ia, iv, iu]``):
``x(ix) = (ix - (nx-1)/2) dx + offset_x`` and likewise y, z; detector
``u(iu) = (iu - (nu-1)/2) du + center_col``, ``v(iv)`` likewise; parallel
rays run along ``(cos a, sin a, 0)`` and hit ``u = y cos a - x sin a``; the
cone source sits at ``sod (cos a, sin a, 0)`` with the flat detector
``sdd`` from it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

_EPS = 1e-9
HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Geom:
    """The configuration's geometry, read independently of the program."""
    kind: str
    nx: int
    ny: int
    nz: int
    dx: float
    dz: float
    off: tuple
    n_rows: int
    n_cols: int
    du: float
    dv: float
    center_row: float
    center_col: float
    angles: np.ndarray          # float32 radians
    sod: float = 0.0
    sdd: float = 0.0

    @classmethod
    def from_config(cls, g: dict) -> "Geom":
        vol = g["volume"]
        if "angles" in g:
            ang = np.asarray(g["angles"], np.float64)
        else:
            n = g["n_angles"]
            ang = np.deg2rad(np.arange(n) * (g["angular_range"] / n))
        if g["geom_type"] == "cone" and g.get("detector_type", "flat") != "flat":
            raise ValueError("the reference covers flat cone detectors only")
        if g["geom_type"] not in ("parallel", "cone"):
            raise ValueError(f"no reference for {g['geom_type']!r}")
        dx, dy = vol.get("dx", 1.0), vol.get("dy", 1.0)
        if dx != dy:
            raise ValueError("the SF footprint needs square in-plane voxels")
        return cls(g["geom_type"], vol["nx"], vol["ny"], vol["nz"], dx,
                   vol.get("dz", 1.0),
                   (vol.get("offset_x", 0.0), vol.get("offset_y", 0.0),
                    vol.get("offset_z", 0.0)),
                   g["n_rows"], g["n_cols"], g.get("pixel_width", 1.0),
                   g.get("pixel_height", 1.0), g.get("center_row", 0.0),
                   g.get("center_col", 0.0), ang.astype(np.float32),
                   g.get("sod", 0.0), g.get("sdd", 0.0))

    def axis(self, n, d, off):
        return ((np.arange(n) - (n - 1) / 2.0) * d + off).astype(np.float32)

    @property
    def xs(self):
        return self.axis(self.nx, self.dx, self.off[0])

    @property
    def ys(self):
        return self.axis(self.ny, self.dx, self.off[1])

    @property
    def zs(self):
        return self.axis(self.nz, self.dz, self.off[2])

    @property
    def us(self):
        return self.axis(self.n_cols, self.du, self.center_col)

    @property
    def vs(self):
        return self.axis(self.n_rows, self.dv, self.center_row)

    @property
    def mag_range(self):
        """Least and largest magnification over the volume's circumcircle."""
        r = math.hypot(self.nx * self.dx / 2 + abs(self.off[0]),
                       self.ny * self.dx / 2 + abs(self.off[1]))
        return self.sdd / (self.sod + r), self.sdd / max(self.sod - r, 1e-3)


# --------------------------------------------------------------------------- #
# Footprint integrals
# --------------------------------------------------------------------------- #
def _trapezoid_cdf(t, t0, t1, t2, t3, h):
    d01 = jnp.maximum(t1 - t0, _EPS)
    d23 = jnp.maximum(t3 - t2, _EPS)
    a = jnp.clip(t, t0, t1)
    b = jnp.clip(t, t1, t2)
    c = jnp.clip(t, t2, t3)
    return h * ((a - t0) ** 2 / (2.0 * d01) + (b - t1)
                + ((t3 - t2) ** 2 - (t3 - c) ** 2) / (2.0 * d23))


def _pixel_mean(lo, hi, t0, t1, t2, t3, h):
    """Mean of the trapezoid (t0..t3, plateau h) over the pixel [lo, hi]."""
    return (_trapezoid_cdf(hi, t0, t1, t2, t3, h)
            - _trapezoid_cdf(lo, t0, t1, t2, t3, h)) / (hi - lo)


def _overlap(lo, hi, elo, ehi):
    """Share of the pixel [elo, ehi] covered by the interval [lo, hi]."""
    return jnp.maximum(jnp.minimum(hi, ehi) - jnp.maximum(lo, elo),
                       0.0) / (ehi - elo)


def _transaxial(g: Geom, X, Y, c, s):
    """Trapezoid breakpoints, plateau height and (cone) source distance
    of each voxel column (X, Y) at view (cos, sin) = (c, s)."""
    if g.kind == "parallel":
        uc = Y * c - X * s
        a, b = g.dx * jnp.abs(c), g.dx * jnp.abs(s)
        hs, hd = 0.5 * (a + b), 0.5 * jnp.abs(a - b)
        h = g.dx / jnp.maximum(jnp.abs(c), jnp.abs(s))
        return (uc - hs, uc - hd, uc + hd, uc + hs,
                jnp.broadcast_to(h, uc.shape), None)
    hx = 0.5 * g.dx
    corners = []
    for sx in (-hx, hx):
        for sy in (-hx, hx):
            xx, yy = X + sx, Y + sy
            ell = g.sod - (xx * c + yy * s)
            corners.append(g.sdd * (yy * c - xx * s) / jnp.maximum(ell, _EPS))
    t = jnp.sort(jnp.stack(corners, -1), -1)
    rx, ry = X - g.sod * c, Y - g.sod * s
    h = g.dx / jnp.maximum(jnp.abs(rx), jnp.abs(ry)) * jnp.sqrt(rx * rx
                                                                + ry * ry)
    ell = g.sod - (X * c + Y * s)
    return t[..., 0], t[..., 1], t[..., 2], t[..., 3], h, ell


def _u_matrix(g: Geom, X, Y, c, s):
    """(n_cols, n_columns) transaxial weights, and the cone source distance."""
    t0, t1, t2, t3, h, ell = _transaxial(g, X, Y, c, s)
    lo = jnp.asarray(g.us - g.du / 2.0)[:, None]
    w = _pixel_mean(lo, lo + g.du, t0[None], t1[None], t2[None], t3[None],
                    h[None])
    return w, ell


def _z_overlap(g: Geom) -> np.ndarray:
    """(nz, n_rows) parallel-beam axial weights: rectangle over pixel."""
    z = g.zs.astype(np.float64)[:, None]
    v = g.vs.astype(np.float64)[None, :]
    lo = np.maximum(z - g.dz / 2, v - g.dv / 2)
    hi = np.minimum(z + g.dz / 2, v + g.dv / 2)
    return (np.maximum(hi - lo, 0.0) / g.dv).astype(np.float32)


def _cone_axial(g: Geom, ell, rt2, fwd: bool, arr):
    """Cone axial part for a block of voxel columns with source distances
    ``ell`` along the central ray and ``rt2`` squared in-plane (nb,).
    Forward: ``arr`` is the volume block (nb, nz) and the
    result (nb, n_rows) holds, per detector row, the sum over the z voxels
    whose magnified extent overlaps it.  Adjoint: ``arr`` is (nb, n_rows)
    and the result (nb, nz).  Each term is voxel value x obliquity x the
    share of the row the voxel covers."""
    mag = (g.sdd / jnp.maximum(ell, _EPS))[:, None]                  # (nb, 1)
    z0 = float(g.zs[0]) - g.dz / 2.0
    v0 = float(g.vs[0]) - g.dv / 2.0
    mmin, mmax = g.mag_range
    nz, nv = g.nz, g.n_rows
    out = 0.0
    if fwd:
        elv = jnp.asarray(g.vs - g.dv / 2.0)[None, :]                 # (1, nv)
        k0 = jnp.floor((elv / mag - z0) / g.dz).astype(jnp.int32)    # (nb, nv)
        for k in range(_span(g.dv / (mmin * g.dz))):
            iz = k0 + k
            ok = (iz >= 0) & (iz < nz)
            izc = jnp.clip(iz, 0, nz - 1)
            z = z0 + g.dz / 2.0 + izc.astype(jnp.float32) * g.dz
            w = _overlap((z - g.dz / 2.0) * mag, (z + g.dz / 2.0) * mag,
                         elv, elv + g.dv)
            out = out + jnp.where(ok, w, 0.0) * _obl(z, rt2) * \
                jnp.take_along_axis(arr, izc, axis=1)
        return out
    z = jnp.asarray(g.zs)[None, :]                                   # (1, nz)
    lo, hi = (z - g.dz / 2.0) * mag, (z + g.dz / 2.0) * mag          # (nb, nz)
    k0 = jnp.floor((lo - v0) / g.dv).astype(jnp.int32)
    for k in range(_span(g.dz * mmax / g.dv)):
        iv = k0 + k
        ok = (iv >= 0) & (iv < nv)
        ivc = jnp.clip(iv, 0, nv - 1)
        elv = v0 + ivc.astype(jnp.float32) * g.dv
        w = _overlap(lo, hi, elv, elv + g.dv)
        out = out + jnp.where(ok, w, 0.0) * jnp.take_along_axis(arr, ivc,
                                                                axis=1)
    return out * _obl(z, rt2)


def _span(length: float) -> int:
    """Most cells of unit width that an interval of ``length`` cells
    overlaps: the first is floor(lo), the last floor(lo + length)."""
    return int(math.floor(length)) + 2


def _obl(z, rt2):
    """Cone obliquity sqrt(1 + z^2 / r^2), r the voxel column's in-plane
    distance from the source."""
    return jnp.sqrt(1.0 + z * z / jnp.maximum(rt2[:, None], _EPS))


# --------------------------------------------------------------------------- #
# SF forward projection and its transpose
# --------------------------------------------------------------------------- #
def _rt2(g: Geom, X, Y, c, s):
    return (X - g.sod * c) ** 2 + (Y - g.sod * s) ** 2


def _block(n: int, most: int) -> int:
    """The largest block size up to ``most`` that divides ``n``."""
    b = max(1, min(n, most))
    while n % b:
        b -= 1
    return b


def sf_fp_bp(cfg_geom: dict, x, b, bp_rows=None, rows_per_block: int = 64):
    """``r = A x - b`` and ``g = A^T r`` of the SF model, both float32.

    ``x``: (nx, ny, nz); ``b``: (n_angles, n_rows, n_cols).  ``r`` is whole;
    ``g`` holds the x rows ``bp_rows`` (all by default), so a check can
    compare a sample of the adjoint at a fraction of its cost.  One view at
    a time: the forward projection of every block of voxel columns, the
    residual of that view, then its transpose into the chosen rows."""
    g = Geom.from_config(cfg_geom)
    rows = np.arange(g.nx) if bp_rows is None else np.asarray(bp_rows)
    Fz = jnp.asarray(_z_overlap(g)) if g.kind == "parallel" else None
    xf = x.reshape(g.nx * g.ny, g.nz)

    def grid(xs, bx):
        """Column coordinates, and a slicer of blocks of ``bx`` x rows."""
        n = bx * g.ny
        X = jnp.asarray(np.repeat(xs, g.ny))
        Y = jnp.asarray(np.tile(g.ys, len(xs)))
        return lambda a, i: jax.lax.dynamic_slice_in_dim(a, i * n, n), X, Y

    bf = _block(g.nx, rows_per_block)
    bb = _block(len(rows), rows_per_block)
    fsl, FX, FY = grid(g.xs, bf)
    bsl, BX, BY = grid(g.xs[rows], bb)

    def view(gacc, inp):
        ang, bv = inp
        c, s = jnp.cos(ang), jnp.sin(ang)

        def fp_block(i, acc):
            Xb, Yb = fsl(FX, i), fsl(FY, i)
            W, ell = _u_matrix(g, Xb, Yb, c, s)                   # (nu, ncol)
            vol = fsl(xf, i)
            if g.kind == "parallel":
                h = jnp.dot(vol, Fz, precision=HIGHEST)           # (ncol, nv)
            else:
                h = _cone_axial(g, ell, _rt2(g, Xb, Yb, c, s), True, vol)
            return acc + jnp.dot(W, h, precision=HIGHEST)         # (nu, nv)

        sv = jax.lax.fori_loop(0, g.nx // bf, fp_block,
                               jnp.zeros((g.n_cols, g.n_rows), jnp.float32))
        rv = sv.T - bv                                            # (nv, nu)

        def bp_block(i, acc):
            Xb, Yb = bsl(BX, i), bsl(BY, i)
            W, ell = _u_matrix(g, Xb, Yb, c, s)
            cb = jnp.dot(W.T, rv.T, precision=HIGHEST)            # (ncol, nv)
            if g.kind == "parallel":
                gb = jnp.dot(cb, Fz.T, precision=HIGHEST)         # (ncol, nz)
            else:
                gb = _cone_axial(g, ell, _rt2(g, Xb, Yb, c, s), False, cb)
            n = bb * g.ny
            return jax.lax.dynamic_update_slice_in_dim(
                acc, bsl(acc, i) + gb, i * n, 0)

        gacc = jax.lax.fori_loop(0, len(rows) // bb, bp_block, gacc)
        return gacc, rv

    with jax.default_matmul_precision("highest"):
        gacc, r = jax.lax.scan(
            view, jnp.zeros((len(rows) * g.ny, g.nz), jnp.float32),
            (jnp.asarray(g.angles), b))
    return r, gacc.reshape(len(rows), g.ny, g.nz)


# --------------------------------------------------------------------------- #
# Filtered backprojection (parallel beam) on chosen z slices
# --------------------------------------------------------------------------- #
def ramp_taps(n_cols: int, du: float) -> np.ndarray:
    """Band-limited ramp h[n], n = -(n_cols-1) .. n_cols-1 (Kak & Slaney
    eq. 61): 1/(4 du^2) at 0, -1/(pi^2 n^2 du^2) at odd n, 0 at even n."""
    n = np.arange(-(n_cols - 1), n_cols)
    h = np.zeros(n.shape, np.float64)
    h[n == 0] = 1.0 / (4.0 * du * du)
    odd = n % 2 != 0
    h[odd] = -1.0 / (np.pi ** 2 * n[odd].astype(np.float64) ** 2 * du * du)
    return h


def view_weights(angles: np.ndarray, full_range: float) -> np.ndarray:
    """Trapezoid-rule weight of each view over ``full_range`` radians: half
    the angular distance to each neighbour, the ends sharing what the scan
    leaves uncovered."""
    a = np.asarray(angles, np.float64)
    n = len(a)
    if n == 1:
        return np.asarray([full_range], np.float32)
    order = np.argsort(a)
    srt = a[order]
    gaps = np.diff(srt)
    rest = (full_range - (srt[-1] - srt[0])) / 2
    w = np.empty(n)
    w[0] = gaps[0] / 2 + rest
    w[-1] = gaps[-1] / 2 + rest
    w[1:-1] = (gaps[:-1] + gaps[1:]) / 2
    out = np.empty(n)
    out[order] = w
    return out.astype(np.float32)


def row_lerp(g: Geom, zi: np.ndarray):
    """Detector rows and weights that linearly interpolate slice ``zi``'s
    height from the row centres (zero outside the detector)."""
    vs = g.vs.astype(np.float64)
    pos = (g.zs.astype(np.float64)[zi] - vs[0]) / (vs[1] - vs[0]
                                                   if len(vs) > 1 else 1.0)
    j = np.floor(pos).astype(np.int64)
    t = pos - j
    w0 = np.where((j >= 0) & (j < len(vs)), 1 - t, 0.0)
    w1 = np.where((j + 1 >= 0) & (j + 1 < len(vs)), t, 0.0)
    return (np.clip(j, 0, len(vs) - 1), np.clip(j + 1, 0, len(vs) - 1),
            w0.astype(np.float32), w1.astype(np.float32))


def fbp_slices(cfg_geom: dict, sino, zi: np.ndarray, dtype=jnp.float32):
    """Ramp-filtered parallel-beam backprojection of ``sino`` on the z
    slices ``zi``: (nx, ny, len(zi)) float32.  ``dtype`` is the precision
    of the data, the filter and every sum (bfloat16 makes the control);
    coordinates stay float32, as a bfloat16 mantissa cannot place a ray."""
    g = Geom.from_config(cfg_geom)
    if g.kind != "parallel":
        raise ValueError("fbp_slices covers parallel beams")
    nu = g.n_cols
    taps = ramp_taps(nu, g.du)
    iu = np.arange(nu)
    T = jnp.asarray((taps[(iu[None, :] - iu[:, None]) + nu - 1]
                     * g.du), dtype)                      # q = p @ T
    j0, j1, w0, w1 = row_lerp(g, np.asarray(zi))
    w0, w1 = jnp.asarray(w0, dtype), jnp.asarray(w1, dtype)
    X = jnp.asarray(np.repeat(g.xs, g.ny))
    Y = jnp.asarray(np.tile(g.ys, g.nx))
    u0 = float(g.us[0])
    wts = jnp.asarray(view_weights(g.angles, np.pi), dtype)

    def view(acc, inp):
        ang, w, p = inp                                   # p (n_rows, nu)
        rows = jnp.dot(p[j0], T, precision=HIGHEST) * w0[:, None] + \
            jnp.dot(p[j1], T, precision=HIGHEST) * w1[:, None]     # (nzs, nu)
        pos = (Y * jnp.cos(ang) - X * jnp.sin(ang) - u0) / g.du
        k = jnp.floor(pos).astype(jnp.int32)
        t = pos - k
        a0 = jnp.where((k >= 0) & (k < nu), 1 - t, 0.0).astype(dtype)
        a1 = jnp.where((k + 1 >= 0) & (k + 1 < nu), t, 0.0).astype(dtype)
        s = (jnp.take(rows, jnp.clip(k, 0, nu - 1), axis=1) * a0
             + jnp.take(rows, jnp.clip(k + 1, 0, nu - 1), axis=1) * a1)
        return acc + w * s, None

    acc, _ = jax.lax.scan(
        view, jnp.zeros((len(zi), g.nx * g.ny), dtype),
        (jnp.asarray(g.angles), wts, sino.astype(dtype)))
    return acc.T.reshape(g.nx, g.ny, len(zi)).astype(jnp.float32)
