"""Quantitative accuracy (paper: 'all numerical values scale appropriately').

* analytic ellipse line integrals vs SF/Joseph projections
* exact mass conservation of the SF footprint
* mm-scaling invariance: scaling voxel+pixel sizes by s scales projections by s
* quantitative FBP/FDK: uniform disc reconstructs to its density in 1/mm
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Projector, ProjectorSpec, VolumeGeometry, cone_beam,
                        parallel_beam)
from repro.data.phantoms import (Ellipse, analytic_parallel_projection,
                                 rasterize, shepp_logan_2d)


def _phantom_geom(n=64, na=24, supersample=4):
    vol = VolumeGeometry(n, n, 1)
    g = parallel_beam(na, 1, int(1.5 * n), vol)
    ells = [Ellipse(5.0, -3.0, 18.0, 11.0, 0.4, 0.8),
            Ellipse(-8.0, 6.0, 7.0, 12.0, -0.2, 0.5)]
    img = rasterize(ells, vol, supersample)
    return g, ells, jnp.asarray(img[:, :, None])


@pytest.mark.parametrize("model", ["sf", "joseph"])
def test_analytic_ellipse_projection(model):
    g, ells, f = _phantom_geom()
    sino = Projector(ProjectorSpec(g, model))(f)[:, 0, :]
    ana = analytic_parallel_projection(ells, np.asarray(g.angles),
                                       g.u_coords())
    err = np.abs(np.asarray(sino) - ana)
    # discretized phantom vs analytic: few-percent sup-norm, sub-percent L1
    assert err.max() / ana.max() < 0.12
    assert err.mean() / ana.mean() < 0.02


def test_sf_mass_conservation():
    """Sum over detector of SF projection x du == integral of the slice —
    exact (to fp32) by construction of the trapezoid footprint."""
    vol = VolumeGeometry(32, 32, 4)
    g = parallel_beam(16, 4, 64, vol)
    f = jax.random.uniform(jax.random.PRNGKey(0), vol.shape)
    sino = Projector(ProjectorSpec(g, "sf"))(f)
    mass_p = np.asarray(sino[:, 1, :].sum(axis=1)) * g.pixel_width
    mass_f = float(f[:, :, 1].sum()) * vol.dx * vol.dy
    np.testing.assert_allclose(mass_p, mass_f, rtol=1e-5)


@pytest.mark.parametrize("model", ["sf", "joseph"])
def test_mm_scaling(model):
    """Scaling all geometry lengths by s scales line integrals by s."""
    s = 2.5
    vol1 = VolumeGeometry(24, 24, 4)
    g1 = parallel_beam(8, 4, 36, vol1)
    vol2 = vol1.scale(s)
    g2 = dataclasses.replace(g1, vol=vol2, pixel_width=g1.pixel_width * s,
                             pixel_height=g1.pixel_height * s)
    f = jax.random.uniform(jax.random.PRNGKey(1), vol1.shape)
    p1 = Projector(ProjectorSpec(g1, model))(f)
    p2 = Projector(ProjectorSpec(g2, model))(f)
    np.testing.assert_allclose(np.asarray(p2), np.asarray(p1) * s,
                               rtol=1e-4, atol=1e-5)


def test_fbp_quantitative_parallel():
    vol = VolumeGeometry(96, 96, 2)
    g = parallel_beam(120, 2, 144, vol)
    xs = vol.x_coords()
    X, Y = np.meshgrid(xs, vol.y_coords(), indexing="ij")
    f = (0.02 * ((X ** 2 + Y ** 2) <= 15.0 ** 2)).astype(np.float32)
    f = jnp.asarray(np.repeat(f[:, :, None], 2, axis=2))
    proj = Projector(ProjectorSpec(g, "sf"))
    rec = proj.fbp(proj(f))
    center = np.asarray(rec[42:54, 42:54, 1]).mean()
    assert abs(center / 0.02 - 1.0) < 0.02


def test_fdk_quantitative_cone():
    vol = VolumeGeometry(96, 96, 4)
    g = cone_beam(240, 16, 160, vol, sod=250.0, sdd=500.0,
                  pixel_width=2.0, pixel_height=2.0)
    xs = vol.x_coords()
    X, Y = np.meshgrid(xs, vol.y_coords(), indexing="ij")
    f = (0.02 * ((X ** 2 + Y ** 2) <= 15.0 ** 2)).astype(np.float32)
    f = jnp.asarray(np.repeat(f[:, :, None], 4, axis=2))
    proj = Projector(ProjectorSpec(g, "sf"))
    rec = proj.fbp(proj(f))
    center = np.asarray(rec[42:54, 42:54, 2]).mean()
    assert abs(center / 0.02 - 1.0) < 0.05


def test_shepp_logan_roundtrip_psnr():
    vol = VolumeGeometry(64, 64, 1)
    g = parallel_beam(90, 1, 96, vol)
    f = jnp.asarray(shepp_logan_2d(vol)[:, :, None]) * 0.02
    proj = Projector(ProjectorSpec(g, "sf"))
    rec = proj.fbp(proj(f))
    mse = float(jnp.mean((rec - f) ** 2))
    psnr = 10 * np.log10(float(jnp.max(f)) ** 2 / mse)
    assert psnr > 24.0, psnr
