"""Packed (lane-packed, axial pre-resample) cone pair: error bound + dispatch.

The packed pair approximates the exact cone SF model by pre-resampling
detector rows onto volume z-planes at the *central* magnification
(``fp_cone._z_overlap_cone_packed``), which turns the transaxial remainder
into the fan kernel and unlocks batch x n_rows lane packing.  These tests
pin the three contracts the ROADMAP item asks for:

* the packed-vs-exact sinogram error stays within the *documented* bound
  (``cone_packed_error_bound``) across a half-cone-angle sweep;
* the packed pair is itself exactly matched (adjoint dot test ~1e-6),
  including the lane-packed batched path;
* ``mode="auto"`` dispatches packed only under the tolerance gate and
  refuses geometries past it (falling back to the exact pair).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Projector, ProjectorSpec, VolumeGeometry, cone_beam
from repro.kernels import fp_cone, ops, tune
from repro.kernels.tune import LANE, KernelConfig


def _geom(sod=200.0, nz=4, nv=4, nxy=16, dz=1.0, dv=2.0):
    vol = VolumeGeometry(nxy, nxy, nz, dz=dz)
    return cone_beam(6, nv, 24, vol, sod=sod, sdd=2.0 * sod,
                     pixel_width=2.0, pixel_height=dv)


def _blob_volume(vol, seed=0):
    """Smooth test volume (Gaussian blobs) — the regime packed mode targets."""
    rng = np.random.default_rng(seed)
    x, y, z = np.meshgrid(np.linspace(-1, 1, vol.nx),
                          np.linspace(-1, 1, vol.ny),
                          np.linspace(-1, 1, vol.nz), indexing="ij")
    f = np.zeros(vol.shape, np.float32)
    for _ in range(4):
        cx, cy, cz = rng.uniform(-0.5, 0.5, 3)
        w = rng.uniform(0.15, 0.4)
        f += np.exp(-((x - cx) ** 2 + (y - cy) ** 2 + (z - cz) ** 2)
                    / (2 * w * w)).astype(np.float32)
    return jnp.asarray(f)


# --------------------------------------------------------------------------- #
# Error bound
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("sod", [400.0, 200.0, 100.0, 60.0])
def test_packed_error_within_bound_over_cone_angles(sod):
    """Half-cone-angle sweep (sod shrinking at fixed z extent): the measured
    relative L2 error must stay under the documented per-geometry bound."""
    g = _geom(sod=sod)
    f = _blob_volume(g.vol)
    y_exact = fp_cone.fp_cone_sf_pallas(f, g)
    y_pack = fp_cone.fp_cone_packed(f, g)
    err = float(jnp.linalg.norm(y_pack - y_exact)
                / jnp.linalg.norm(y_exact))
    bound = fp_cone.cone_packed_error_bound(g)
    assert err <= bound, (err, bound)


def test_packed_error_and_bound_shrink_with_cone_angle():
    """Both the bound and the measured error are monotone in the half-cone
    angle, and the bound is first-order small (vanishes in the fan limit)."""
    errs, bounds = [], []
    for sod in (60.0, 120.0, 240.0, 480.0):
        g = _geom(sod=sod)
        f = _blob_volume(g.vol)
        y_exact = fp_cone.fp_cone_sf_pallas(f, g)
        y_pack = fp_cone.fp_cone_packed(f, g)
        errs.append(float(jnp.linalg.norm(y_pack - y_exact)
                          / jnp.linalg.norm(y_exact)))
        bounds.append(fp_cone.cone_packed_error_bound(g))
    assert all(b1 > b2 for b1, b2 in zip(bounds, bounds[1:]))
    assert all(e1 >= e2 * 0.5 for e1, e2 in zip(errs, errs[1:]))  # ~monotone
    assert errs[-1] < errs[0]
    assert bounds[-1] < 0.2


def test_row_shift_scales_with_z_extent():
    shifts = [fp_cone.cone_packed_row_shift(_geom(nz=nz, nv=2 * nz))
              for nz in (2, 4, 8)]
    assert shifts[0] < shifts[1] < shifts[2]


# --------------------------------------------------------------------------- #
# Matched pair (adjoint) + batched path
# --------------------------------------------------------------------------- #
def test_packed_pair_adjoint_dot():
    g = _geom()
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    lhs = jnp.vdot(fp_cone.fp_cone_packed(f, g), y)
    rhs = jnp.vdot(f, fp_cone.bp_cone_packed(y, g))
    assert abs(lhs - rhs) / abs(lhs) < 2e-5


def test_packed_pair_adjoint_dot_batched():
    g = _geom()
    B = 3
    f = jax.random.normal(jax.random.PRNGKey(0), (B,) + g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), (B,) + g.sino_shape)
    lhs = jnp.vdot(fp_cone.fp_cone_packed(f, g), y)
    rhs = jnp.vdot(f, fp_cone.bp_cone_packed(y, g))
    assert abs(lhs - rhs) / abs(lhs) < 2e-5


def test_packed_batched_matches_per_sample():
    """The lane-packed batch fold is exactly the per-sample computation."""
    g = _geom()
    B = 3
    f = jax.random.normal(jax.random.PRNGKey(0), (B,) + g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), (B,) + g.sino_shape)
    np.testing.assert_allclose(
        np.asarray(fp_cone.fp_cone_packed(f, g)),
        np.stack([np.asarray(fp_cone.fp_cone_packed(f[i], g))
                  for i in range(B)]), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(fp_cone.bp_cone_packed(y, g)),
        np.stack([np.asarray(fp_cone.bp_cone_packed(y[i], g))
                  for i in range(B)]), rtol=2e-4, atol=2e-4)


def test_packed_kernels_match_jnp_oracle():
    """Kernel-vs-oracle anchor: fp_cone_packed against the pure-jnp packed
    oracle, and bp_cone_packed against the oracle's exact linear transpose
    (jax.vjp of the oracle — no kernels involved on the oracle side)."""
    g = _geom()
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    np.testing.assert_allclose(np.asarray(fp_cone.fp_cone_packed(f, g)),
                               np.asarray(fp_cone.fp_cone_packed_ref(f, g)),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(fp_cone.bp_cone_packed(y, g)),
                               np.asarray(fp_cone.bp_cone_packed_ref(y, g)),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------- #
# Dispatch policy (mode="exact"|"packed"|"auto")
# --------------------------------------------------------------------------- #
def test_auto_dispatches_packed_under_tolerance():
    g = _geom(sod=400.0)
    assert tune.packed_cone_ok(g)
    assert ops.resolve_mode(ProjectorSpec(g, backend="pallas")) == "packed"
    # the dispatched op really is the packed kernel
    f = _blob_volume(g.vol)
    out = ops.forward_project(f, ProjectorSpec(g, backend="pallas",
                                               mode="auto"))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(fp_cone.fp_cone_packed(f, g)),
                               rtol=1e-5, atol=1e-5)


def test_auto_refuses_past_threshold():
    """A wide-cone geometry (row shift >> tolerance) must fall back to the
    exact pair under mode="auto"."""
    g = cone_beam(4, 16, 24, VolumeGeometry(16, 16, 16, dz=2.0),
                  sod=40.0, sdd=80.0, pixel_width=2.0, pixel_height=2.0)
    assert fp_cone.cone_packed_row_shift(g) > tune.packed_cone_tolerance()
    assert not tune.packed_cone_ok(g)
    assert ops.resolve_mode(ProjectorSpec(g, backend="pallas")) == "exact"
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    out = ops.forward_project(f, ProjectorSpec(g, backend="pallas",
                                               mode="auto"))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(fp_cone.fp_cone_sf_pallas(f, g)),
                               rtol=1e-5, atol=1e-5)


def test_tolerance_env_override(monkeypatch):
    g = _geom(sod=400.0)
    assert tune.packed_cone_ok(g)
    monkeypatch.setenv("REPRO_PACKED_CONE_TOL", "1e-9")
    assert not tune.packed_cone_ok(g)
    assert ops.resolve_mode(ProjectorSpec(g, backend="pallas")) == "exact"
    # a typo'd tolerance must be loud, not a silent fallback to the default
    monkeypatch.setenv("REPRO_PACKED_CONE_TOL", "0.1rows")
    with pytest.raises(ValueError):
        tune.packed_cone_tolerance()


def test_mode_packed_forces_packed_and_exact_forces_exact():
    g = _geom(sod=60.0)     # past nothing — just distinguishable numerics
    f = _blob_volume(g.vol)
    y_pack = ops.forward_project(f, ProjectorSpec(g, backend="pallas",
                                                  mode="packed"))
    y_exact = ops.forward_project(f, ProjectorSpec(g, backend="pallas",
                                                   mode="exact"))
    np.testing.assert_allclose(np.asarray(y_pack),
                               np.asarray(fp_cone.fp_cone_packed(f, g)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_exact),
                               np.asarray(fp_cone.fp_cone_sf_pallas(f, g)),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(y_pack - y_exact))) > 0


def test_mode_validation_and_unavailable_packed():
    g = _geom()
    with pytest.raises(ValueError):
        ops.resolve_mode(ProjectorSpec(g, mode="fast"))
    with pytest.raises(ValueError):
        Projector(ProjectorSpec(g, mode="fast"))
    # no packed pair registered for parallel: forcing it must raise
    from repro.core import parallel_beam
    gp = parallel_beam(4, 2, 16, VolumeGeometry(8, 8, 2))
    with pytest.raises(NotImplementedError):
        ops.forward_project(jnp.zeros(gp.vol.shape),
                            ProjectorSpec(gp, backend="pallas", mode="packed"))
    # curved-detector cone: packed pre-resample is flat-only — explicit raise
    gc = cone_beam(4, 4, 16, VolumeGeometry(8, 8, 4), sod=200.0, sdd=400.0,
                   pixel_width=2.0, pixel_height=2.0, detector_type="curved")
    with pytest.raises(NotImplementedError):
        ops.forward_project(jnp.zeros(gc.vol.shape),
                            ProjectorSpec(gc, backend="pallas", mode="packed"))
    # off the pallas backend mode="auto" quietly stays exact (ref path)
    assert ops.resolve_mode(ProjectorSpec(g, backend="ref",
                                          mode="auto")) == "exact"


def test_projector_mode_plumbing_and_gradients():
    """mode= flows Projector -> ops; the packed pair is wired as a matched
    custom_vjp pair, so the gradient of the data term is the packed BP."""
    g = _geom(sod=400.0)
    proj = Projector(ProjectorSpec(g, backend="pallas", mode="packed"))
    f = _blob_volume(g.vol)
    y = fp_cone.fp_cone_packed(f, g)
    np.testing.assert_allclose(np.asarray(proj(f)), np.asarray(y),
                               rtol=1e-5, atol=1e-5)
    meas = jnp.zeros_like(y)
    grad = jax.grad(lambda x: 0.5 * jnp.sum((proj(x) - meas) ** 2))(f)
    expect = fp_cone.bp_cone_packed(y, g)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------- #
# Tuning integration
# --------------------------------------------------------------------------- #
def test_packed_shape_class_is_its_own_regime():
    g = _geom()
    exact_key = tune.shape_class(g, packed=False)
    packed_key = tune.shape_class(g, packed=True)
    assert exact_key != packed_key
    assert packed_key[0] == "cone-packed"


def test_packed_heuristic_lane_packs():
    """Packed cone tunes like fan: full 128-lane tile, not the physical-row
    tile of the exact cone kernel."""
    g = _geom(nv=4)
    exact = tune.heuristic_config(g)
    packed = tune.heuristic_config(g, packed=True)
    assert exact.bv < LANE          # exact tiles physical rows (nv=4 -> 8)
    assert packed.bv == LANE


def test_packed_respects_pinned_config():
    g = _geom()
    f = _blob_volume(g.vol)
    base = fp_cone.fp_cone_packed(f, g)
    pinned = fp_cone.fp_cone_packed(f, g, config=KernelConfig(bu=8, ba=2))
    np.testing.assert_allclose(np.asarray(pinned), np.asarray(base),
                               rtol=2e-4, atol=2e-4)


def test_packed_matches_fan_limit():
    """Thin central-slice geometry (nz=1, z=0): the packed path agrees with
    the exact cone path up to the voxel's own *thickness* magnification
    spread (first order in dz·R/sod — well inside the documented bound)."""
    vol = VolumeGeometry(16, 16, 1, dz=1.0)
    g = cone_beam(6, 1, 24, vol, sod=400.0, sdd=800.0,
                  pixel_width=2.0, pixel_height=2.0)
    f = jax.random.normal(jax.random.PRNGKey(0), vol.shape)
    y_exact = fp_cone.fp_cone_sf_pallas(f, g)
    y_pack = fp_cone.fp_cone_packed(f, g)
    err = float(jnp.linalg.norm(y_pack - y_exact) / jnp.linalg.norm(y_exact))
    assert err <= fp_cone.cone_packed_error_bound(g)
    assert err < 0.02
