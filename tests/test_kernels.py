"""Pallas kernel vs pure-jnp oracle: allclose across shape/dtype sweeps
(interpret mode on CPU; identical code path compiles for TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis; the non-property "
    "kernel-vs-oracle coverage lives in tests/test_batched_pallas.py")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.geometry import VolumeGeometry, parallel_beam
from repro.kernels import ref
from repro.kernels.fp_par import bp_parallel_sf_pallas, fp_parallel_sf_pallas

SHAPES = [
    (16, 16, 4, 6, 4, 24),     # nx, ny, nz, na, nv, nu
    (32, 32, 8, 12, 8, 48),
    (24, 24, 2, 5, 2, 40),     # non-multiple-of-tile sizes
    (32, 32, 8, 9, 8, 33),
]


@pytest.mark.parametrize("shape", SHAPES)
def test_fp_matches_oracle(shape):
    nx, ny, nz, na, nv, nu = shape
    vol = VolumeGeometry(nx, ny, nz)
    g = parallel_beam(na, nv, nu, vol)
    f = jax.random.normal(jax.random.PRNGKey(0), vol.shape)
    p_ref = ref.forward(f, g, "sf")
    p_pal = fp_parallel_sf_pallas(f, g)
    np.testing.assert_allclose(np.asarray(p_pal), np.asarray(p_ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", SHAPES[:2])
def test_bp_matches_oracle(shape):
    nx, ny, nz, na, nv, nu = shape
    vol = VolumeGeometry(nx, ny, nz)
    g = parallel_beam(na, nv, nu, vol)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    b_ref = ref.adjoint(y, g, "sf")
    b_pal = bp_parallel_sf_pallas(y, g)
    np.testing.assert_allclose(np.asarray(b_pal), np.asarray(b_ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 0.05)])
def test_fp_dtypes(dtype, tol):
    vol = VolumeGeometry(16, 16, 4)
    g = parallel_beam(6, 4, 24, vol)
    f = jax.random.normal(jax.random.PRNGKey(0), vol.shape).astype(dtype)
    p_ref = ref.forward(f.astype(jnp.float32), g, "sf")
    p_pal = fp_parallel_sf_pallas(f, g).astype(jnp.float32)
    err = float(jnp.abs(p_pal - p_ref).max())
    assert err <= tol * float(jnp.abs(p_ref).max()), err


def test_fp_anisotropic_pixels():
    vol = VolumeGeometry(20, 20, 4, dx=1.5, dy=1.5, dz=2.0)
    g = parallel_beam(8, 6, 30, vol, pixel_width=1.1, pixel_height=1.3)
    f = jax.random.normal(jax.random.PRNGKey(0), vol.shape)
    np.testing.assert_allclose(np.asarray(fp_parallel_sf_pallas(f, g)),
                               np.asarray(ref.forward(f, g, "sf")),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=6, deadline=None)
@given(na=st.integers(2, 10), seed=st.integers(0, 1000),
       du=st.floats(0.7, 1.6))
def test_fp_property_random_geoms(na, seed, du):
    rng = np.random.default_rng(seed)
    vol = VolumeGeometry(16, 16, 2)
    ang = np.sort(rng.uniform(0, np.pi, na))
    g = parallel_beam(na, 2, 28, vol, angles=ang, pixel_width=du)
    f = jnp.asarray(rng.normal(size=vol.shape).astype(np.float32))
    np.testing.assert_allclose(np.asarray(fp_parallel_sf_pallas(f, g)),
                               np.asarray(ref.forward(f, g, "sf")),
                               rtol=3e-4, atol=3e-4)


def test_kernel_registered_dispatch():
    from repro.kernels import ops
    assert ("parallel", "sf") in ops._KERNEL_TABLE
    vol = VolumeGeometry(16, 16, 4)
    g = parallel_beam(6, 4, 24, vol)
    f = jax.random.normal(jax.random.PRNGKey(0), vol.shape)
    # explicit pallas backend routes through the kernel
    out = ops.forward_project(f, g, "sf", backend="pallas")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.forward(f, g, "sf")),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------- #
# Cone-beam SF kernel
# --------------------------------------------------------------------------- #
CONE_SHAPES = [
    # nx, ny, nz, na, nv, nu, sod, sdd
    (16, 16, 8, 6, 8, 24, 80.0, 160.0),
    (24, 24, 4, 5, 8, 36, 120.0, 200.0),    # non-tile-multiple views/rows
    (16, 16, 16, 4, 16, 24, 60.0, 150.0),   # taller stack, higher mag
]


@pytest.mark.parametrize("shape", CONE_SHAPES)
def test_fp_cone_matches_oracle(shape):
    from repro.core.geometry import cone_beam
    from repro.kernels.fp_cone import fp_cone_sf_pallas
    nx, ny, nz, na, nv, nu, sod, sdd = shape
    vol = VolumeGeometry(nx, ny, nz)
    g = cone_beam(na, nv, nu, vol, sod=sod, sdd=sdd,
                  pixel_width=2.0, pixel_height=2.0)
    f = jax.random.normal(jax.random.PRNGKey(0), vol.shape)
    p_ref = ref.forward(f, g, "sf")
    p_pal = fp_cone_sf_pallas(f, g, bg=8, bv=8)
    np.testing.assert_allclose(np.asarray(p_pal), np.asarray(p_ref),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("shape", CONE_SHAPES)
def test_bp_cone_matches_oracle(shape):
    """The Pallas cone BP (exact transpose of the forward kernel) against
    the jnp-oracle adjoint."""
    from repro.core.geometry import cone_beam
    from repro.kernels.fp_cone import bp_cone_sf_pallas
    nx, ny, nz, na, nv, nu, sod, sdd = shape
    vol = VolumeGeometry(nx, ny, nz)
    g = cone_beam(na, nv, nu, vol, sod=sod, sdd=sdd,
                  pixel_width=2.0, pixel_height=2.0)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    b_ref = ref.adjoint(y, g, "sf")
    b_pal = bp_cone_sf_pallas(y, g, bg=8, bv=8)
    np.testing.assert_allclose(np.asarray(b_pal), np.asarray(b_ref),
                               rtol=3e-4, atol=3e-4)


def test_bp_cone_view_blocked_matches_oracle():
    """bab > 1 / non-multiple bg (padded views and gathered tiles) is
    exactly the unblocked math."""
    from repro.core.geometry import cone_beam
    from repro.kernels.fp_cone import bp_cone_sf_pallas
    from repro.kernels.tune import KernelConfig
    vol = VolumeGeometry(16, 16, 8)
    g = cone_beam(5, 8, 24, vol, sod=80.0, sdd=160.0,
                  pixel_width=2.0, pixel_height=2.0)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    b_ref = ref.adjoint(y, g, "sf")
    b_pal = bp_cone_sf_pallas(y, g, config=KernelConfig(bg=12, bv=8, bab=2))
    np.testing.assert_allclose(np.asarray(b_pal), np.asarray(b_ref),
                               rtol=3e-4, atol=3e-4)


def test_fp_cone_padded_gathered_tile_matches_oracle():
    """A gathered tile that divides neither the gathered axis nor the
    sublane tile (zero-padded voxels) is exactly the unpadded math: the FP
    twin of ``test_bp_cone_view_blocked_matches_oracle``."""
    from repro.core.geometry import cone_beam
    from repro.kernels.fp_cone import fp_cone_sf_pallas
    from repro.kernels.tune import KernelConfig
    vol = VolumeGeometry(20, 20, 8)
    g = cone_beam(5, 8, 24, vol, sod=80.0, sdd=160.0,
                  pixel_width=2.0, pixel_height=2.0)
    f = jax.random.normal(jax.random.PRNGKey(0), vol.shape)
    p_ref = ref.forward(f, g, "sf")
    p_pal = fp_cone_sf_pallas(f, g, config=KernelConfig(bg=12, bv=8))
    np.testing.assert_allclose(np.asarray(p_pal), np.asarray(p_ref),
                               rtol=3e-4, atol=3e-4)


def test_cone_pallas_fp_is_bp_transpose():
    """The exact cone FP is the BP's transpose term by term: the dense
    matrices built from unit vectors agree to float32 rounding."""
    from repro.core.geometry import cone_beam
    from repro.kernels.fp_cone import bp_cone_sf_pallas, fp_cone_sf_pallas
    vol = VolumeGeometry(8, 8, 4)
    g = cone_beam(3, 8, 12, vol, sod=40.0, sdd=80.0,
                  pixel_width=2.0, pixel_height=2.0)
    n_vox, n_pix = int(np.prod(vol.shape)), int(np.prod(g.sino_shape))
    eye_v = jnp.eye(n_vox, dtype=jnp.float32).reshape((n_vox,) + vol.shape)
    eye_p = jnp.eye(n_pix, dtype=jnp.float32).reshape((n_pix,) +
                                                      g.sino_shape)
    a_fp = np.asarray(fp_cone_sf_pallas(eye_v, g, bg=8, bv=8)).reshape(
        n_vox, n_pix).T                                  # A[pixel, voxel]
    a_bp = np.asarray(bp_cone_sf_pallas(eye_p, g, bg=8, bv=8)).reshape(
        n_pix, n_vox)                                    # (A^T)^T
    assert np.abs(a_fp).max() > 0.1 and np.count_nonzero(a_fp) > n_pix
    np.testing.assert_allclose(a_fp, a_bp, rtol=0, atol=1e-6)


def test_cone_pallas_pair_matched():
    """Registered cone pair (Pallas fwd + Pallas BP, the matched pair) —
    the BP is the exact transpose of the forward kernel."""
    from repro.core.geometry import cone_beam
    from repro.core import Projector
    vol = VolumeGeometry(16, 16, 8)
    g = cone_beam(6, 8, 24, vol, sod=80.0, sdd=160.0,
                  pixel_width=2.0, pixel_height=2.0)
    proj = Projector(g, "sf", backend="pallas")
    x = jax.random.normal(jax.random.PRNGKey(0), vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    lhs = jnp.vdot(proj(x), y)
    rhs = jnp.vdot(x, proj.T(y))
    assert abs(lhs - rhs) / abs(lhs) < 1e-4


# --------------------------------------------------------------------------- #
# Mixed precision (bf16-tile / f32-accumulate) property sweep
# --------------------------------------------------------------------------- #
@settings(max_examples=8, deadline=None)
@given(na=st.integers(3, 10), nu=st.integers(20, 40),
       du=st.floats(0.7, 1.6), seed=st.integers(0, 1000))
def test_bf16_fp_error_bound_property(na, nu, du, seed):
    """Across randomized parallel geometries, the bf16-tile FP stays within
    the documented BF16_FP_REL_BOUND of the f32 oracle while measurably
    differing from the f32 kernel run (the cast actually happened)."""
    from repro.kernels import precision
    rng = np.random.default_rng(seed)
    vol = VolumeGeometry(16, 16, 4)
    ang = np.sort(rng.uniform(0, np.pi, na))
    g = parallel_beam(na, 4, nu, vol, angles=ang, pixel_width=du)
    f = jnp.asarray(rng.normal(size=vol.shape).astype(np.float32))
    s_ref = ref.forward(f, g, "sf")
    s_b = fp_parallel_sf_pallas(f, g, compute_dtype="bfloat16")
    assert s_b.dtype == jnp.float32
    denom = float(jnp.abs(s_ref).max())
    rel = float(jnp.abs(s_b - s_ref).max()) / max(denom, 1e-9)
    assert rel < precision.BF16_FP_REL_BOUND, rel


@settings(max_examples=6, deadline=None)
@given(bs=st.integers(1, 4), bg=st.sampled_from([8, 16]),
       seed=st.integers(0, 1000))
def test_bp_stripe_reuse_exact_property(bs, bg, seed):
    """BP stripe blocking (bs) is a pure re-blocking: any (bg, bs) combo
    reproduces the oracle adjoint to f32 tolerance."""
    rng = np.random.default_rng(seed)
    vol = VolumeGeometry(16, 16, 4)
    g = parallel_beam(6, 4, 24, vol)
    y = jnp.asarray(rng.normal(size=g.sino_shape).astype(np.float32))
    b_ref = ref.adjoint(y, g, "sf")
    b_pal = bp_parallel_sf_pallas(y, g, bg=bg, bs=bs)
    np.testing.assert_allclose(np.asarray(b_pal), np.asarray(b_ref),
                               rtol=2e-4, atol=2e-4)
