"""Fan-beam geometry end to end: Pallas kernels vs the jnp oracle, matched
adjoints, fan FBP weighting (cosine / equiangular ramp correction / Parker
short-scan), and reconstruction quality vs the parallel-beam baseline."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Projector, ProjectorSpec, VolumeGeometry, fan_beam,
                        parallel_beam)
from repro.core.fbp import parker_weights
from repro.kernels import ops, ref
from repro.kernels.fp_fan import bp_fan_sf_pallas, fp_fan_sf_pallas
from repro.kernels.tune import KernelConfig
from repro.data.phantoms import shepp_logan_2d

RTOL = ATOL = 2e-4


def _assert_close(a, b, tol=RTOL):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=tol, atol=tol)


def _psnr(rec, f):
    mse = float(jnp.mean((rec - f) ** 2))
    return 10 * np.log10(float(jnp.max(f)) ** 2 / mse)


# --------------------------------------------------------------------------- #
# Kernels vs oracle
# --------------------------------------------------------------------------- #
FAN_SHAPES = [
    # nx, ny, nz, na, nv, nu, sod, sdd, detector_type
    (16, 16, 4, 6, 4, 24, 80.0, 160.0, "flat"),
    (24, 24, 2, 5, 2, 36, 120.0, 200.0, "curved"),   # non-tile-multiple dims
]


@pytest.mark.parametrize("shape", FAN_SHAPES)
def test_fan_fp_bp_match_oracle(shape):
    nx, ny, nz, na, nv, nu, sod, sdd, det = shape
    g = fan_beam(na, nv, nu, VolumeGeometry(nx, ny, nz), sod=sod, sdd=sdd,
                 pixel_width=2.0, detector_type=det)
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    _assert_close(fp_fan_sf_pallas(f, g), ref.forward(f, g, "sf"))
    _assert_close(bp_fan_sf_pallas(y, g), ref.adjoint(y, g, "sf"))


def test_fan_view_blocking_matches_oracle():
    """ba/bab > 1 (view-blocked fan FP/BP) is exactly the unblocked math."""
    g = fan_beam(7, 3, 28, VolumeGeometry(16, 16, 3), sod=60.0, sdd=120.0,
                 pixel_width=2.0, detector_type="curved")
    cfg = KernelConfig(bu=8, ba=3, bg=8, bab=2)
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    _assert_close(fp_fan_sf_pallas(f, g, config=cfg), ref.forward(f, g, "sf"))
    _assert_close(bp_fan_sf_pallas(y, g, config=cfg), ref.adjoint(y, g, "sf"))


@pytest.mark.parametrize("det", ["flat", "curved"])
def test_fan_windowed_gather_matches_oracle(det):
    """Geometry sized so the static window bounds do NOT clamp to the full
    axis (W < ng in FP, Wu < nup in BP): exercises the window-start
    inversion — incl. the curved-detector tan inversion — that full-axis
    shapes skip.  Guarded by assertions on the actual window sizes."""
    from repro.kernels import fp_fan, tune
    g = fan_beam(4, 1, 128, VolumeGeometry(64, 64, 1), sod=200.0, sdd=220.0,
                 pixel_width=1.0, detector_type=det)
    cfg = KernelConfig(bu=8, bg=8)
    assert tune.aligned_window(fp_fan._window_size_fan(g, cfg.bu),
                               g.vol.nx, 8) < g.vol.nx
    assert tune.aligned_window(fp_fan._u_window_size_div(g, cfg.bg),
                               g.n_cols, 8) < g.n_cols
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    _assert_close(fp_fan_sf_pallas(f, g, config=cfg), ref.forward(f, g, "sf"))
    _assert_close(bp_fan_sf_pallas(y, g, config=cfg), ref.adjoint(y, g, "sf"))


def test_fan_registered_dispatch():
    assert ("fan", "sf") in ops._KERNEL_TABLE
    g = fan_beam(6, 2, 24, VolumeGeometry(16, 16, 2), sod=60.0, sdd=120.0,
                 pixel_width=2.0)
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    out = ops.forward_project(f, ProjectorSpec(g, "sf", backend="pallas"))
    _assert_close(out, ref.forward(f, g, "sf"))


def test_fan_parallel_limit():
    """sod -> inf with the pixel width scaled by the magnification reduces
    the fan transform to the parallel one."""
    v = VolumeGeometry(24, 24, 2)
    gp = parallel_beam(8, 2, 36, v, angular_range=360.0)
    gf = fan_beam(8, 2, 36, v, sod=1e5, sdd=2e5, pixel_width=2.0,
                  angular_range=360.0)
    f = jax.random.uniform(jax.random.PRNGKey(0), v.shape)
    pf, pp = ref.forward(f, gf, "sf"), ref.forward(f, gp, "sf")
    err = float(jnp.abs(pf - pp).max() / jnp.abs(pp).max())
    assert err < 1e-3, err


# --------------------------------------------------------------------------- #
# FBP weighting
# --------------------------------------------------------------------------- #
def test_fan_fbp_quantitative_disc():
    """Uniform disc reconstructs to its density in 1/mm (both detectors)."""
    vol = VolumeGeometry(64, 64, 2)
    xs = vol.x_coords()
    X, Y = np.meshgrid(xs, vol.y_coords(), indexing="ij")
    fd = (0.02 * ((X ** 2 + Y ** 2) <= 12.0 ** 2)).astype(np.float32)
    fd = jnp.asarray(np.repeat(fd[:, :, None], 2, axis=2))
    for det in ("flat", "curved"):
        g = fan_beam(180, 2, 112, vol, sod=180.0, sdd=360.0, pixel_width=2.0,
                     angular_range=360.0, detector_type=det)
        proj = Projector(ProjectorSpec(g, "sf"))
        rec = proj.fbp(proj(fd))
        center = np.asarray(rec[28:36, 28:36, 1]).mean()
        assert abs(center / 0.02 - 1.0) < 0.05, (det, center)


def test_fan_fbp_psnr_matches_parallel_baseline():
    """Shepp-Logan via fan FBP lands within 1 dB of the parallel-beam FBP
    baseline on an equivalent full-scan geometry (acceptance criterion)."""
    vol = VolumeGeometry(64, 64, 1)
    f = jnp.asarray(shepp_logan_2d(vol)[:, :, None]) * 0.02
    gp = parallel_beam(90, 1, 96, vol)
    pp = Projector(ProjectorSpec(gp, "sf"))
    base = _psnr(pp.fbp(pp(f)), f)
    for det in ("flat", "curved"):
        gf = fan_beam(360, 1, 96, vol, sod=200.0, sdd=400.0, pixel_width=2.0,
                      angular_range=360.0, detector_type=det)
        pf = Projector(ProjectorSpec(gf, "sf"))
        got = _psnr(pf.fbp(pf(f)), f)
        assert got > base - 1.0, (det, got, base)


def test_fan_parker_short_scan():
    """Parker weighting makes a pi + 2*delta short scan usable: a large PSNR
    gain over naive (double-counted) weighting on the same data."""
    vol = VolumeGeometry(64, 64, 1)
    f = jnp.asarray(shepp_logan_2d(vol)[:, :, None]) * 0.02
    gamma_max = math.atan((95 / 2 * 2.0) / 400.0)
    rng_deg = math.degrees(math.pi + 2 * gamma_max)
    g = fan_beam(144, 1, 96, vol, sod=200.0, sdd=400.0, pixel_width=2.0,
                 angular_range=rng_deg)
    proj = Projector(ProjectorSpec(g, "sf"))
    sino = proj(f)
    parker = _psnr(proj.fbp(sino), f)               # auto-detects short scan
    naive = _psnr(proj.fbp(sino, short_scan=False), f)
    assert parker > 20.0, parker
    assert parker > naive + 4.0, (parker, naive)


def test_parker_weights_conjugate_sum():
    """Parker weights of a conjugate ray pair — (beta, gamma) and
    (beta + pi - 2*gamma, -gamma) — sum to ~1: the redundancy split that
    replaces the full-scan 1/2."""
    g = fan_beam(200, 1, 64, VolumeGeometry(32, 32, 1), sod=100.0, sdd=200.0,
                 pixel_width=1.0,
                 angular_range=math.degrees(math.pi + 2 * math.atan(31.5 / 200)))
    w = parker_weights(g)
    assert w.shape == (200, 64)
    assert w.min() >= 0.0 and w.max() <= 1.0
    gamma = np.arctan2(g.u_coords(), g.sdd)
    ang = np.asarray(g.angles_array())
    iu = 20                                # -gamma lives at the mirror column
    iu_m = g.n_cols - 1 - iu
    conj = ang + np.pi - 2 * gamma[iu]
    inside = np.nonzero((conj >= ang.min()) & (conj <= ang.max()))[0]
    ic = np.clip(np.searchsorted(ang, conj[inside]), 0, len(ang) - 1)
    s = w[inside, iu] + w[ic, iu_m]
    assert np.all(np.abs(s - 1.0) < 0.08), (s.min(), s.max())
