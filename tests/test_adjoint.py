"""The paper's central correctness property: matched projector pairs.

<A x, y> == <x, A^T y> must hold to float tolerance for every geometry x
model x backend combination — otherwise CG/least-squares gradients are wrong
and 1000+-iteration recon diverges (paper §2.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    # Only the property tests need hypothesis; the fixed-geometry dot-tests
    # (incl. the modular Pallas pair's ~1e-6 acceptance tests) must run in
    # minimal environments too, so the module no longer importorskips.
    def given(*a, **k):
        def deco(f):
            return pytest.mark.skip(reason="property test needs hypothesis")(f)
        return deco

    def settings(*a, **k):
        return lambda f: f

    class _NullStrategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _NullStrategies()

from repro.core import (Projector, ProjectorSpec, VolumeGeometry, cone_beam,
                        fan_beam, helical_beam, parallel_beam)
from repro.core.geometry import cone_as_modular


def _dot_test(proj, key=0, tol=1e-4):
    # fp32 accumulation noise over ~1e5-term reductions is ~4e-5 relative;
    # an *unmatched* pair fails this at the 1e-2..1e-1 level.
    kx, ky = jax.random.split(jax.random.PRNGKey(key))
    x = jax.random.normal(kx, proj.vol_shape())
    y = jax.random.normal(ky, proj.sino_shape())
    lhs = jnp.vdot(proj(x), y)
    rhs = jnp.vdot(x, proj.T(y))
    assert abs(lhs - rhs) / max(abs(lhs), 1e-6) < tol, (lhs, rhs)


@pytest.mark.parametrize("model", ["joseph", "sf"])
def test_parallel_matched(model):
    v = VolumeGeometry(24, 24, 6)
    g = parallel_beam(10, 6, 36, v)
    _dot_test(Projector(ProjectorSpec(g, model)))


@pytest.mark.parametrize("model", ["joseph", "sf"])
def test_cone_matched(model):
    v = VolumeGeometry(24, 24, 8)
    g = cone_beam(8, 12, 36, v, sod=120.0, sdd=240.0,
                  pixel_width=2.0, pixel_height=2.0)
    _dot_test(Projector(ProjectorSpec(g, model)))


def test_cone_curved_matched():
    v = VolumeGeometry(24, 24, 8)
    g = cone_beam(8, 12, 36, v, sod=120.0, sdd=240.0, pixel_width=2.0,
                  pixel_height=2.0, detector_type="curved")
    _dot_test(Projector(ProjectorSpec(g, "joseph")))


# Flat-detector cone Pallas matched pair (FP and BP both on-kernel) across
# cone angles.  The last case has nz far larger than the kernels' axial
# window NZW, so the z-window genuinely slides (is not clamped to the full
# volume) — the regime where a mismatched FP/BP windowing would show up.
CONE_PALLAS_GEOMS = [
    # nz, n_rows, pixel_height, sod, sdd
    (8, 12, 2.0, 120.0, 240.0),      # ~11 deg half cone angle
    (8, 16, 3.0, 80.0, 160.0),       # wide cone (~17 deg)
    (24, 8, 1.0, 100.0, 150.0),      # tall stack: un-clamped sliding z-window
]


@pytest.mark.parametrize("nz,nv,dv,sod,sdd", CONE_PALLAS_GEOMS)
def test_cone_pallas_pair_matched_angles(nz, nv, dv, sod, sdd):
    v = VolumeGeometry(16, 16, nz)
    g = cone_beam(6, nv, 24, v, sod=sod, sdd=sdd,
                  pixel_width=2.0, pixel_height=dv)
    _dot_test(Projector(ProjectorSpec(g, "sf", backend="pallas")))


def test_cone_pallas_bp_gradient_is_forward():
    """grad_y <A^T y, x> == A x on the registered cone Pallas pair — the
    custom_vjp wiring routes through the new Pallas BP's transpose."""
    v = VolumeGeometry(16, 16, 8)
    g = cone_beam(5, 8, 24, v, sod=80.0, sdd=160.0,
                  pixel_width=2.0, pixel_height=2.0)
    proj = Projector(ProjectorSpec(g, "sf", backend="pallas"))
    y = jax.random.normal(jax.random.PRNGKey(0), g.sino_shape)
    x = jax.random.normal(jax.random.PRNGKey(1), v.shape)
    grad_y = jax.grad(lambda q: jnp.vdot(proj.T(q), x))(y)
    np.testing.assert_allclose(np.asarray(grad_y), np.asarray(proj(x)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("det", ["flat", "curved"])
def test_fan_matched(det):
    v = VolumeGeometry(24, 24, 4)
    g = fan_beam(8, 4, 36, v, sod=120.0, sdd=240.0, pixel_width=2.0,
                 detector_type=det)
    _dot_test(Projector(ProjectorSpec(g, "sf")))


@pytest.mark.parametrize("det", ["flat", "curved"])
def test_fan_pallas_pair_matched(det):
    v = VolumeGeometry(24, 24, 4)
    g = fan_beam(8, 4, 36, v, sod=120.0, sdd=240.0, pixel_width=2.0,
                 detector_type=det)
    _dot_test(Projector(ProjectorSpec(g, "sf", backend="pallas")))


def test_modular_matched():
    v = VolumeGeometry(20, 20, 6)
    g = cone_as_modular(cone_beam(6, 10, 30, v, sod=100.0, sdd=200.0,
                                  pixel_width=2.0, pixel_height=2.0))
    _dot_test(Projector(ProjectorSpec(g)))


# Modular Pallas matched pair (FP and BP both on-kernel) across frame
# regimes: an axial circular trajectory re-expressed as modular frames, and
# genuinely helical scans (source translating in z) incl. a tall volume
# where the kernel's axial window slides.
def test_modular_pallas_pair_matched_cone_frames():
    v = VolumeGeometry(20, 20, 6)
    g = cone_as_modular(cone_beam(6, 10, 30, v, sod=100.0, sdd=200.0,
                                  pixel_width=2.0, pixel_height=2.0))
    _dot_test(Projector(ProjectorSpec(g, "sf", backend="pallas")))


@pytest.mark.parametrize("nz,pitch,nv", [(8, 8.0, 10), (24, 16.0, 6)])
def test_modular_pallas_pair_matched_helical(nz, pitch, nv):
    v = VolumeGeometry(16, 16, nz)
    g = helical_beam(1.0, pitch, 6, nv, 24, v, sod=80.0, sdd=160.0,
                     pixel_width=2.0, pixel_height=2.0)
    _dot_test(Projector(ProjectorSpec(g, "sf", backend="pallas")))


def test_modular_pallas_pair_matched_batched():
    """<A x, y> == <x, A^T y> through the grid-folded batched modular pair."""
    from repro.kernels import fp_modular
    v = VolumeGeometry(16, 16, 8)
    g = helical_beam(1.0, 8.0, 6, 8, 24, v, sod=80.0, sdd=160.0,
                     pixel_width=2.0, pixel_height=2.0)
    kx, ky = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (3,) + v.shape)
    y = jax.random.normal(ky, (3,) + g.sino_shape)
    lhs = jnp.vdot(fp_modular.fp_modular_sf_pallas(x, g), y)
    rhs = jnp.vdot(x, fp_modular.bp_modular_sf_pallas(y, g))
    assert abs(lhs - rhs) / max(abs(lhs), 1e-6) < 1e-4, (lhs, rhs)


def test_modular_pallas_bp_gradient_is_forward():
    """grad_y <A^T y, x> == A x on the registered modular Pallas pair."""
    v = VolumeGeometry(16, 16, 8)
    g = helical_beam(1.0, 8.0, 5, 8, 24, v, sod=80.0, sdd=160.0,
                     pixel_width=2.0, pixel_height=2.0)
    proj = Projector(ProjectorSpec(g, "sf", backend="pallas"))
    y = jax.random.normal(jax.random.PRNGKey(0), g.sino_shape)
    x = jax.random.normal(jax.random.PRNGKey(1), v.shape)
    grad_y = jax.grad(lambda q: jnp.vdot(proj.T(q), x))(y)
    np.testing.assert_allclose(np.asarray(grad_y), np.asarray(proj(x)),
                               rtol=1e-4, atol=1e-4)


def test_pallas_pair_matched():
    v = VolumeGeometry(24, 24, 6)
    g = parallel_beam(10, 6, 36, v)
    _dot_test(Projector(ProjectorSpec(g, "sf", backend="pallas")))


@settings(max_examples=8, deadline=None)
@given(na=st.integers(3, 12), nu=st.integers(16, 40),
       off=st.floats(-3.0, 3.0), du=st.floats(0.6, 2.0), seed=st.integers(0, 100))
def test_parallel_matched_property(na, nu, off, du, seed):
    """Property over randomized geometries (non-equispaced angles, shifts,
    anisotropic pixel sizes)."""
    rng = np.random.default_rng(seed)
    ang = np.sort(rng.uniform(0, np.pi, na))
    v = VolumeGeometry(16, 16, 4, offset_x=off)
    g = parallel_beam(na, 4, nu, v, angles=ang, pixel_width=du,
                      center_col=off / 2)
    _dot_test(Projector(ProjectorSpec(g, "sf")), key=seed)


@settings(max_examples=6, deadline=None)
@given(sod=st.floats(60.0, 200.0), mag=st.floats(1.2, 3.0),
       seed=st.integers(0, 100))
def test_cone_matched_property(sod, mag, seed):
    v = VolumeGeometry(16, 16, 6)
    g = cone_beam(6, 10, 30, v, sod=sod, sdd=sod * mag,
                  pixel_width=2.0, pixel_height=2.0)
    _dot_test(Projector(ProjectorSpec(g, "sf")), key=seed)


@settings(max_examples=6, deadline=None)
@given(sod=st.floats(60.0, 200.0), mag=st.floats(1.2, 3.0),
       curved=st.booleans(), seed=st.integers(0, 100))
def test_fan_matched_property(sod, mag, curved, seed):
    """Property over randomized fan geometries: flat + curved detectors,
    varying magnification."""
    v = VolumeGeometry(16, 16, 4)
    g = fan_beam(6, 4, 30, v, sod=sod, sdd=sod * mag, pixel_width=2.0,
                 detector_type="curved" if curved else "flat")
    _dot_test(Projector(ProjectorSpec(g, "sf")), key=seed)


def test_gradient_is_backprojection():
    """d/dx 0.5||Ax - y||^2 == A^T(Ax - y) exactly (custom_vjp wiring)."""
    v = VolumeGeometry(20, 20, 4)
    g = parallel_beam(8, 4, 30, v)
    proj = Projector(ProjectorSpec(g, "sf"))
    x = jax.random.normal(jax.random.PRNGKey(0), v.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    grad = jax.grad(lambda x: 0.5 * jnp.sum((proj(x) - y) ** 2))(x)
    expected = proj.T(proj(x) - y)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(expected),
                               rtol=1e-4, atol=1e-5)


def test_double_differentiation():
    """grad of back_project (A^T)^T == A: the pair is self-consistent."""
    v = VolumeGeometry(16, 16, 2)
    g = parallel_beam(6, 2, 24, v)
    proj = Projector(ProjectorSpec(g, "sf"))
    y = jax.random.normal(jax.random.PRNGKey(0), g.sino_shape)
    x = jax.random.normal(jax.random.PRNGKey(1), v.shape)
    grad_y = jax.grad(lambda y: jnp.vdot(proj.T(y), x))(y)
    np.testing.assert_allclose(np.asarray(grad_y), np.asarray(proj(x)),
                               rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------- #
# bf16 tile precision (kernels/precision.py)
# --------------------------------------------------------------------------- #
# At compute_dtype="bfloat16" the FP quantizes the volume-side stream while
# the BP quantizes the sinogram-side stream, so <A x, y> and <x, A^T y> are
# inner products of *differently quantized* operators: they agree to
# O(BF16_EPS) relative (f32 accumulation keeps the error from compounding),
# which is the documented BF16_DOT_TOL.  An unmatched pair still fails this
# at the 1e-1 level, so the dot-test stays discriminating at bf16.
from repro.kernels import precision  # noqa: E402

BF16_GEOMS = {
    "parallel": lambda: parallel_beam(10, 6, 36, VolumeGeometry(24, 24, 6)),
    "fan": lambda: fan_beam(8, 4, 36, VolumeGeometry(24, 24, 4), sod=120.0,
                            sdd=240.0, pixel_width=2.0),
    "cone": lambda: cone_beam(8, 12, 36, VolumeGeometry(24, 24, 8),
                              sod=120.0, sdd=240.0, pixel_width=2.0,
                              pixel_height=2.0),
    "modular": lambda: helical_beam(1.0, 8.0, 6, 8, 24,
                                    VolumeGeometry(16, 16, 8), sod=80.0,
                                    sdd=160.0, pixel_width=2.0,
                                    pixel_height=2.0),
}


@pytest.mark.parametrize("name", sorted(BF16_GEOMS))
def test_bf16_pallas_pair_dot(name):
    g = BF16_GEOMS[name]()
    proj = Projector(ProjectorSpec(g, "sf", backend="pallas", mode="exact",
                                   compute_dtype="bfloat16"))
    _dot_test(proj, tol=float(precision.BF16_DOT_TOL))


def test_bf16_packed_cone_pair_dot():
    g = BF16_GEOMS["cone"]()
    proj = Projector(ProjectorSpec(g, "sf", backend="pallas", mode="packed",
                                   compute_dtype="bfloat16"))
    _dot_test(proj, tol=float(precision.BF16_DOT_TOL))


def test_bf16_gradient_is_backprojection():
    """At bf16 the custom_vjp wiring still routes the gradient through the
    *same* bf16 BP op, so grad == A^T(Ax - y) holds tightly (same closure,
    not merely the same math)."""
    g = BF16_GEOMS["parallel"]()
    proj = Projector(ProjectorSpec(g, "sf", backend="pallas",
                                   compute_dtype="bfloat16"))
    x = jax.random.normal(jax.random.PRNGKey(0), proj.vol_shape())
    y = jax.random.normal(jax.random.PRNGKey(1), proj.sino_shape())
    grad = jax.grad(lambda x: 0.5 * jnp.sum((proj(x) - y) ** 2))(x)
    expected = proj.T(proj(x) - y)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(expected),
                               rtol=1e-4, atol=1e-5)


def test_bf16_stripe_reuse_pair_dot():
    """The BP stripe-reuse blocking (bs > 1) preserves the matched pair."""
    from repro.kernels import fp_par
    g = BF16_GEOMS["parallel"]()
    kx, ky = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, g.vol.shape)
    y = jax.random.normal(ky, g.sino_shape)
    lhs = jnp.vdot(fp_par.fp_parallel_sf_pallas(x, g,
                                                compute_dtype="bfloat16"), y)
    rhs = jnp.vdot(x, fp_par.bp_parallel_sf_pallas(y, g, bs=4,
                                                   compute_dtype="bfloat16"))
    assert abs(lhs - rhs) / max(abs(lhs), 1e-6) < precision.BF16_DOT_TOL
