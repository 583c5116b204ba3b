"""Lane-packed / view-folded batched Pallas paths vs the oracles.

These tests are deliberately hypothesis-free: they are the always-on
correctness anchor for every kernel code path (unbatched, view-blocked,
lane-packed batched) against the pure-jnp oracle and the seed per-sample
vmap path, plus the matched-pair adjoint property the paper requires.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Projector, VolumeGeometry, cone_beam, fan_beam,
                        parallel_beam)
from repro.core.geometry import cone_as_modular
from repro.kernels import ops, ref
from repro.kernels.fp_fan import bp_fan_sf_pallas, fp_fan_sf_pallas
from repro.kernels.fp_par import bp_parallel_sf_pallas, fp_parallel_sf_pallas
from repro.kernels.tune import KernelConfig

RTOL = ATOL = 2e-4


def _assert_close(a, b, tol=RTOL):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=tol, atol=tol)


# --------------------------------------------------------------------------- #
# Unbatched kernels vs oracle (always-on mirror of the hypothesis suite)
# --------------------------------------------------------------------------- #
SHAPES = [
    (16, 16, 4, 6, 4, 24),     # nx, ny, nz, na, nv, nu
    (24, 24, 2, 5, 2, 40),     # non-multiple-of-tile sizes
]


@pytest.mark.parametrize("shape", SHAPES)
def test_fp_bp_match_oracle(shape):
    nx, ny, nz, na, nv, nu = shape
    g = parallel_beam(na, nv, nu, VolumeGeometry(nx, ny, nz))
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    _assert_close(fp_parallel_sf_pallas(f, g), ref.forward(f, g, "sf"))
    _assert_close(bp_parallel_sf_pallas(y, g), ref.adjoint(y, g, "sf"))


@pytest.mark.parametrize("ba,bab", [(2, 2), (4, 3)])
def test_view_blocking_matches_oracle(ba, bab):
    """ba/bab > 1 (view-blocked FP/BP) is exactly the unblocked math."""
    g = parallel_beam(7, 4, 24, VolumeGeometry(16, 16, 4))
    cfg = KernelConfig(ba=ba, bab=bab)
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    _assert_close(fp_parallel_sf_pallas(f, g, config=cfg),
                  ref.forward(f, g, "sf"))
    _assert_close(bp_parallel_sf_pallas(y, g, config=cfg),
                  ref.adjoint(y, g, "sf"))


# --------------------------------------------------------------------------- #
# Lane-packed batching (parallel)
# --------------------------------------------------------------------------- #
BATCH_SHAPES = [
    (5, 16, 16, 4, 6, 4, 24),    # B, nx, ny, nz, na, nv, nu
    (8, 32, 32, 1, 12, 1, 48),   # the paper's thin-z 2D training regime
    (3, 24, 24, 2, 5, 2, 40),    # nothing tile-aligned
]


@pytest.mark.parametrize("shape", BATCH_SHAPES)
def test_lane_packed_fp_matches_vmap_and_oracle(shape):
    B, nx, ny, nz, na, nv, nu = shape
    g = parallel_beam(na, nv, nu, VolumeGeometry(nx, ny, nz))
    fb = jax.random.normal(jax.random.PRNGKey(0), (B, nx, ny, nz))
    packed = fp_parallel_sf_pallas(fb, g)
    assert packed.shape == (B,) + g.sino_shape
    vmapped = jax.vmap(lambda x: fp_parallel_sf_pallas(x, g))(fb)
    oracle = jax.vmap(lambda x: ref.forward(x, g, "sf"))(fb)
    _assert_close(packed, oracle)
    _assert_close(packed, vmapped, tol=1e-4)   # seed path agreement <= 1e-4


@pytest.mark.parametrize("shape", BATCH_SHAPES[:2])
def test_lane_packed_bp_matches_vmap_and_oracle(shape):
    B, nx, ny, nz, na, nv, nu = shape
    g = parallel_beam(na, nv, nu, VolumeGeometry(nx, ny, nz))
    yb = jax.random.normal(jax.random.PRNGKey(1), (B,) + g.sino_shape)
    packed = bp_parallel_sf_pallas(yb, g)
    assert packed.shape == (B, nx, ny, nz)
    oracle = jax.vmap(lambda q: ref.adjoint(q, g, "sf"))(yb)
    _assert_close(packed, oracle)
    _assert_close(packed, jax.vmap(lambda q: bp_parallel_sf_pallas(q, g))(yb),
                  tol=1e-4)


def test_lane_packed_pair_is_matched():
    """<A x, y> == <x, A^T y> on the batched lane-packed pallas path."""
    g = parallel_beam(10, 2, 36, VolumeGeometry(24, 24, 2))
    proj = Projector(g, "sf", backend="pallas")
    x = jax.random.normal(jax.random.PRNGKey(0), (6,) + g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), (6,) + g.sino_shape)
    lhs = jnp.vdot(proj(x), y)
    rhs = jnp.vdot(x, proj.T(y))
    assert abs(lhs - rhs) / max(abs(lhs), 1e-6) < 1e-4, (lhs, rhs)


def test_lane_packed_gradient_is_backprojection():
    """The custom_vjp wiring holds on the batched path: the gradient of the
    data-consistency loss is exactly the batched backprojection."""
    g = parallel_beam(8, 1, 30, VolumeGeometry(20, 20, 1))
    proj = Projector(g, "sf", backend="pallas")
    x = jax.random.normal(jax.random.PRNGKey(0), (4,) + g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), (4,) + g.sino_shape)
    grad = jax.grad(lambda x: 0.5 * jnp.sum((proj(x) - y) ** 2))(x)
    _assert_close(grad, proj.T(proj(x) - y), tol=1e-4)


def test_multi_leading_dims_flatten_through_kernel():
    g = parallel_beam(6, 2, 24, VolumeGeometry(16, 16, 2))
    f = jax.random.normal(jax.random.PRNGKey(0), (2, 3) + g.vol.shape)
    out = ops.forward_project(f, g, "sf", backend="pallas")
    assert out.shape == (2, 3) + g.sino_shape
    _assert_close(out[1, 2], ref.forward(f[1, 2], g, "sf"))


# --------------------------------------------------------------------------- #
# Always-on mirrors of non-property coverage that lives in hypothesis-gated
# modules (test_kernels.py skips entirely when hypothesis is missing)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 0.05)])
def test_fp_dtypes(dtype, tol):
    g = parallel_beam(6, 4, 24, VolumeGeometry(16, 16, 4))
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape).astype(dtype)
    p_ref = ref.forward(f.astype(jnp.float32), g, "sf")
    p_pal = fp_parallel_sf_pallas(f, g).astype(jnp.float32)
    err = float(jnp.abs(p_pal - p_ref).max())
    assert err <= tol * float(jnp.abs(p_ref).max()), err


def test_fp_anisotropic_pixels():
    g = parallel_beam(8, 6, 30, VolumeGeometry(20, 20, 4, dx=1.5, dy=1.5,
                                               dz=2.0),
                      pixel_width=1.1, pixel_height=1.3)
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    _assert_close(fp_parallel_sf_pallas(f, g), ref.forward(f, g, "sf"))


def test_kernel_registered_dispatch():
    assert ("parallel", "sf") in ops._KERNEL_TABLE
    assert ("cone", "sf") in ops._KERNEL_TABLE
    g = parallel_beam(6, 4, 24, VolumeGeometry(16, 16, 4))
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    out = ops.forward_project(f, g, "sf", backend="pallas")
    _assert_close(out, ref.forward(f, g, "sf"))


def _dot_test(proj, key=0, tol=1e-4):
    kx, ky = jax.random.split(jax.random.PRNGKey(key))
    x = jax.random.normal(kx, proj.vol_shape())
    y = jax.random.normal(ky, proj.sino_shape())
    lhs = jnp.vdot(proj(x), y)
    rhs = jnp.vdot(x, proj.T(y))
    assert abs(lhs - rhs) / max(abs(lhs), 1e-6) < tol, (lhs, rhs)


@pytest.mark.parametrize("model", ["joseph", "sf"])
def test_parallel_matched(model):
    _dot_test(Projector(parallel_beam(10, 6, 36, VolumeGeometry(24, 24, 6)),
                        model))


@pytest.mark.parametrize("model", ["joseph", "sf"])
def test_cone_matched(model):
    g = cone_beam(8, 12, 36, VolumeGeometry(24, 24, 8), sod=120.0, sdd=240.0,
                  pixel_width=2.0, pixel_height=2.0)
    _dot_test(Projector(g, model))


def test_cone_curved_matched():
    g = cone_beam(8, 12, 36, VolumeGeometry(24, 24, 8), sod=120.0, sdd=240.0,
                  pixel_width=2.0, pixel_height=2.0, detector_type="curved")
    _dot_test(Projector(g, "joseph"))


def test_modular_matched():
    g = cone_as_modular(cone_beam(6, 10, 30, VolumeGeometry(20, 20, 6),
                                  sod=100.0, sdd=200.0,
                                  pixel_width=2.0, pixel_height=2.0))
    _dot_test(Projector(g))


def test_double_differentiation():
    """grad of back_project (A^T)^T == A: the pair is self-consistent."""
    g = parallel_beam(6, 2, 24, VolumeGeometry(16, 16, 2))
    proj = Projector(g, "sf")
    y = jax.random.normal(jax.random.PRNGKey(0), g.sino_shape)
    x = jax.random.normal(jax.random.PRNGKey(1), g.vol.shape)
    grad_y = jax.grad(lambda y: jnp.vdot(proj.T(y), x))(y)
    _assert_close(grad_y, proj(x), tol=1e-4)


CONE_SHAPES = [
    # nx, ny, nz, na, nv, nu, sod, sdd
    (16, 16, 8, 6, 8, 24, 80.0, 160.0),
    (24, 24, 4, 5, 8, 36, 120.0, 200.0),    # non-tile-multiple views/rows
]


@pytest.mark.parametrize("shape", CONE_SHAPES)
def test_fp_cone_matches_oracle(shape):
    from repro.kernels.fp_cone import fp_cone_sf_pallas
    nx, ny, nz, na, nv, nu, sod, sdd = shape
    g = cone_beam(na, nv, nu, VolumeGeometry(nx, ny, nz), sod=sod, sdd=sdd,
                  pixel_width=2.0, pixel_height=2.0)
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    _assert_close(fp_cone_sf_pallas(f, g, bg=8, bv=8),
                  ref.forward(f, g, "sf"), tol=3e-4)


# --------------------------------------------------------------------------- #
# Lane-packed batching (fan: the pre-collapsed-axial cone case)
# --------------------------------------------------------------------------- #
FAN_BATCH_SHAPES = [
    # B, nx, ny, nz, na, nv, nu, det
    (5, 16, 16, 4, 6, 4, 24, "flat"),
    (4, 20, 20, 1, 8, 1, 32, "curved"),   # thin-z 2D training regime
]


@pytest.mark.parametrize("shape", FAN_BATCH_SHAPES)
def test_fan_lane_packed_fp_matches_vmap_and_oracle(shape):
    B, nx, ny, nz, na, nv, nu, det = shape
    g = fan_beam(na, nv, nu, VolumeGeometry(nx, ny, nz), sod=70.0, sdd=140.0,
                 pixel_width=2.0, detector_type=det)
    fb = jax.random.normal(jax.random.PRNGKey(0), (B, nx, ny, nz))
    packed = fp_fan_sf_pallas(fb, g)
    assert packed.shape == (B,) + g.sino_shape
    vmapped = jax.vmap(lambda x: fp_fan_sf_pallas(x, g))(fb)
    oracle = jax.vmap(lambda x: ref.forward(x, g, "sf"))(fb)
    _assert_close(packed, oracle)
    _assert_close(packed, vmapped, tol=1e-4)


@pytest.mark.parametrize("shape", FAN_BATCH_SHAPES[:1])
def test_fan_lane_packed_bp_matches_vmap_and_oracle(shape):
    B, nx, ny, nz, na, nv, nu, det = shape
    g = fan_beam(na, nv, nu, VolumeGeometry(nx, ny, nz), sod=70.0, sdd=140.0,
                 pixel_width=2.0, detector_type=det)
    yb = jax.random.normal(jax.random.PRNGKey(1), (B,) + g.sino_shape)
    packed = bp_fan_sf_pallas(yb, g)
    assert packed.shape == (B, nx, ny, nz)
    oracle = jax.vmap(lambda q: ref.adjoint(q, g, "sf"))(yb)
    _assert_close(packed, oracle)
    _assert_close(packed, jax.vmap(lambda q: bp_fan_sf_pallas(q, g))(yb),
                  tol=1e-4)


def test_fan_lane_packed_pair_is_matched():
    """<A x, y> == <x, A^T y> on the batched lane-packed fan pallas path."""
    g = fan_beam(8, 2, 32, VolumeGeometry(20, 20, 2), sod=70.0, sdd=140.0,
                 pixel_width=2.0)
    proj = Projector(g, "sf", backend="pallas")
    x = jax.random.normal(jax.random.PRNGKey(0), (4,) + g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), (4,) + g.sino_shape)
    lhs = jnp.vdot(proj(x), y)
    rhs = jnp.vdot(x, proj.T(y))
    assert abs(lhs - rhs) / max(abs(lhs), 1e-6) < 1e-4, (lhs, rhs)


@pytest.mark.parametrize("shape", CONE_SHAPES)
def test_bp_cone_matches_oracle(shape):
    """Always-on mirror of the hypothesis-gated cone BP-vs-oracle check."""
    from repro.kernels.fp_cone import bp_cone_sf_pallas
    nx, ny, nz, na, nv, nu, sod, sdd = shape
    g = cone_beam(na, nv, nu, VolumeGeometry(nx, ny, nz), sod=sod, sdd=sdd,
                  pixel_width=2.0, pixel_height=2.0)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    _assert_close(bp_cone_sf_pallas(y, g, bg=8, bv=8),
                  ref.adjoint(y, g, "sf"), tol=3e-4)


# --------------------------------------------------------------------------- #
# Batched cone (view-axis folding)
# --------------------------------------------------------------------------- #
def test_cone_batched_fp_matches_vmap():
    from repro.kernels.fp_cone import fp_cone_sf_pallas
    g = cone_beam(5, 8, 24, VolumeGeometry(16, 16, 8), sod=80.0, sdd=160.0,
                  pixel_width=2.0, pixel_height=2.0)
    fb = jax.random.normal(jax.random.PRNGKey(0), (3,) + g.vol.shape)
    batched = fp_cone_sf_pallas(fb, g, bg=8, bv=8)
    assert batched.shape == (3,) + g.sino_shape
    oracle = jax.vmap(lambda x: ref.forward(x, g, "sf"))(fb)
    _assert_close(batched, oracle, tol=3e-4)


def test_cone_pallas_pair_matched_unclamped_z_window():
    """Always-on mirror of the tall-stack adjoint case: nz far larger than
    the kernels' axial window NZW, so the z-window genuinely slides."""
    g = cone_beam(6, 8, 24, VolumeGeometry(16, 16, 24), sod=100.0, sdd=150.0,
                  pixel_width=2.0, pixel_height=1.0)
    proj = Projector(g, "sf", backend="pallas")
    x = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    lhs = jnp.vdot(proj(x), y)
    rhs = jnp.vdot(x, proj.T(y))
    assert abs(lhs - rhs) / max(abs(lhs), 1e-6) < 1e-4, (lhs, rhs)


def test_cone_batched_bp_matches_vmap_and_oracle():
    """Gathered-axis batch folding in the cone BP == per-sample results."""
    from repro.kernels.fp_cone import bp_cone_sf_pallas
    g = cone_beam(5, 8, 24, VolumeGeometry(16, 16, 8), sod=80.0, sdd=160.0,
                  pixel_width=2.0, pixel_height=2.0)
    yb = jax.random.normal(jax.random.PRNGKey(1), (3,) + g.sino_shape)
    batched = bp_cone_sf_pallas(yb, g, bg=8, bv=8)
    assert batched.shape == (3,) + g.vol.shape
    oracle = jax.vmap(lambda q: ref.adjoint(q, g, "sf"))(yb)
    _assert_close(batched, oracle, tol=3e-4)
    _assert_close(batched,
                  jax.vmap(lambda q: bp_cone_sf_pallas(q, g, bg=8, bv=8))(yb),
                  tol=1e-4)


def test_cone_batched_pair_is_matched():
    g = cone_beam(4, 8, 24, VolumeGeometry(16, 16, 8), sod=80.0, sdd=160.0,
                  pixel_width=2.0, pixel_height=2.0)
    proj = Projector(g, "sf", backend="pallas")
    x = jax.random.normal(jax.random.PRNGKey(0), (2,) + g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), (2,) + g.sino_shape)
    lhs = jnp.vdot(proj(x), y)
    rhs = jnp.vdot(x, proj.T(y))
    assert abs(lhs - rhs) / max(abs(lhs), 1e-6) < 1e-4, (lhs, rhs)


# --------------------------------------------------------------------------- #
# Mixed precision + BP stripe reuse (always-on anchors; the property sweep
# lives in the hypothesis-gated test_kernels.py)
# --------------------------------------------------------------------------- #
from repro.kernels import precision  # noqa: E402


@pytest.mark.parametrize("bs", [2, 4])
def test_bp_stripe_reuse_is_exact(bs):
    """bs > 1 only re-blocks the gathered axis: results are identical (to
    f32 roundoff) to the unblocked BP, both parallel and fan."""
    gp = parallel_beam(7, 4, 24, VolumeGeometry(16, 16, 4))
    gf = fan_beam(6, 4, 24, VolumeGeometry(16, 16, 4), sod=70.0, sdd=140.0,
                  pixel_width=2.0)
    yp = jax.random.normal(jax.random.PRNGKey(1), gp.sino_shape)
    yf = jax.random.normal(jax.random.PRNGKey(2), gf.sino_shape)
    _assert_close(bp_parallel_sf_pallas(yp, gp, bg=8, bs=bs),
                  ref.adjoint(yp, gp, "sf"))
    _assert_close(bp_fan_sf_pallas(yf, gf, bg=8, bs=bs),
                  ref.adjoint(yf, gf, "sf"))


def test_bp_stripe_reuse_clamps_small_volumes():
    """bs larger than the gathered axis allows is clamped, not an error."""
    g = parallel_beam(6, 2, 24, VolumeGeometry(16, 16, 2))
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    _assert_close(bp_parallel_sf_pallas(y, g, bg=16, bs=8),
                  ref.adjoint(y, g, "sf"))


# name -> (geometry, leading batch, compute dtype).  The x-gathered case's
# views all have |sin| >= |cos| and the y-gathered case's none; nx != ny so
# the two groups' gathered lines differ, and 20 is no multiple of bg = 8.
_WHOLE_LINE = {
    "x-gathered": (lambda: parallel_beam(
        5, 2, 40, VolumeGeometry(20, 12, 2),
        angles=np.deg2rad([50.0, 70.0, 90.0, 110.0, 130.0])), 0, None),
    "y-gathered": (lambda: parallel_beam(
        5, 2, 40, VolumeGeometry(12, 20, 2),
        angles=np.deg2rad([-40.0, -20.0, 0.0, 20.0, 40.0])), 0, None),
    "ng-not-multiple": (lambda: parallel_beam(
        7, 2, 40, VolumeGeometry(20, 28, 2)), 0, None),
    "batch": (lambda: parallel_beam(6, 1, 30, VolumeGeometry(20, 20, 1)),
              3, None),
    "bf16": (lambda: parallel_beam(7, 2, 40, VolumeGeometry(20, 28, 2)), 0,
             "bfloat16"),
}


@pytest.mark.parametrize("case", sorted(_WHOLE_LINE))
def test_bp_whole_line_stripe_reuse_is_bit_identical(case):
    """Stripe reuse over the whole gathered line sums each voxel's views in
    the order bs = 1 does: the result is equal, not merely close."""
    make, batch, cdt = _WHOLE_LINE[case]
    g = make()
    lead = (batch,) if batch else ()
    y = jax.random.normal(jax.random.PRNGKey(3), lead + g.sino_shape)
    bs = -(-max(g.vol.nx, g.vol.ny) // 8)
    one, line = (bp_parallel_sf_pallas(y, g, bg=8, bab=2, bs=b,
                                       compute_dtype=cdt) for b in (1, bs))
    np.testing.assert_array_equal(np.asarray(line), np.asarray(one))


def test_bp_stripe_bytes_counter_falls_with_whole_line(monkeypatch):
    """At the Table-1 512 geometry, with the TPU heuristic's whole-line bs,
    the traced parallel BP's stripe bytes fall by ngp / (2 bg) against
    bs = 2.  Traced only (``jax.eval_shape``): no kernel runs."""
    from repro.configs.leap_ct import table1_geometries
    from repro.kernels import tune
    from repro.kernels.fp_par import _view_params
    from repro.runtime import obs
    monkeypatch.setattr(tune, "_on_tpu", lambda: True)
    g = table1_geometries()["parallel_512_180"]
    cfg = tune.heuristic_config(g)
    assert cfg.bs == 512 // cfg.bg
    y = jax.ShapeDtypeStruct(g.sino_shape, jnp.float32)
    got = {}
    for bs in (2, cfg.bs):
        monkeypatch.setattr(obs, "_REC", obs.Recorder())
        jax.eval_shape(lambda q: bp_parallel_sf_pallas(q, g, config=cfg,
                                                       bs=bs), y)
        got[bs] = obs.counter("kernels.bp_par_stripe_bytes")
    # Both view groups: 1 whole-line program x 512 lines x 4 lane blocks
    # (512 detector rows) x each group's padded views x 768 columns x 128
    # lanes x 4 bytes.
    nap = sum(tune.round_up(len(p), cfg.bab) for p in _view_params(g)[:2])
    assert got[cfg.bs] == 512 * 4 * nap * 768 * 128 * 4
    assert got[2] == got[cfg.bs] * 512 // (2 * cfg.bg)


_BF16_KERNELS = [
    ("parallel", lambda: parallel_beam(6, 4, 24, VolumeGeometry(16, 16, 4))),
    ("fan", lambda: fan_beam(6, 4, 24, VolumeGeometry(16, 16, 4), sod=70.0,
                             sdd=140.0, pixel_width=2.0)),
    ("cone", lambda: cone_beam(6, 8, 24, VolumeGeometry(16, 16, 8), sod=80.0,
                               sdd=160.0, pixel_width=2.0, pixel_height=2.0)),
]


@pytest.mark.parametrize("name,mk", _BF16_KERNELS, ids=[n for n, _ in _BF16_KERNELS])
def test_bf16_fp_bp_error_within_documented_bound(name, mk):
    """compute_dtype="bfloat16" stays within BF16_FP_REL_BOUND of the f32
    oracle for every registered pair, and actually perturbs the numerics
    (i.e. the policy reached the kernel, not a silent f32 fallback)."""
    g = mk()
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    s_ref = ref.forward(f, g, "sf")
    b_ref = ref.adjoint(y, g, "sf")
    s = ops.forward_project(f, g, "sf", backend="pallas", mode="exact",
                            compute_dtype="bfloat16")
    b = ops.back_project(y, g, "sf", backend="pallas", mode="exact",
                         compute_dtype="bfloat16")
    assert s.dtype == jnp.float32 and b.dtype == jnp.float32
    rel_s = float(jnp.abs(s - s_ref).max() / jnp.abs(s_ref).max())
    rel_b = float(jnp.abs(b - b_ref).max() / jnp.abs(b_ref).max())
    assert 1e-5 < rel_s < precision.BF16_FP_REL_BOUND, rel_s
    assert 1e-5 < rel_b < precision.BF16_FP_REL_BOUND, rel_b


def test_bf16_matches_quantized_oracle():
    """The dtype-matched oracle (ref.forward(dtype="bfloat16")) quantizes
    the data stream the way the kernel tiles do, so kernel-vs-oracle
    distance shrinks well below the full bf16 bound."""
    g = parallel_beam(6, 4, 24, VolumeGeometry(16, 16, 4))
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    s_k = fp_parallel_sf_pallas(f, g, compute_dtype="bfloat16")
    s_q = ref.forward(f, g, "sf", dtype="bfloat16")
    assert s_q.dtype == jnp.float32
    rel = float(jnp.abs(s_k - s_q).max() / jnp.abs(s_q).max())
    assert rel < precision.BF16_DOT_TOL, rel


def test_bf16_batched_lane_packed_paths():
    """The lane-packed batched FP/BP honor the policy too (bf16 tiles, f32
    out) — the rows the perf gate targets."""
    g = parallel_beam(8, 1, 30, VolumeGeometry(20, 20, 1))
    fb = jax.random.normal(jax.random.PRNGKey(0), (4,) + g.vol.shape)
    yb = jax.random.normal(jax.random.PRNGKey(1), (4,) + g.sino_shape)
    s = fp_parallel_sf_pallas(fb, g, compute_dtype="bfloat16")
    b = bp_parallel_sf_pallas(yb, g, compute_dtype="bfloat16", bs=2)
    assert s.dtype == jnp.float32 and b.dtype == jnp.float32
    s_ref = jax.vmap(lambda x: ref.forward(x, g, "sf"))(fb)
    b_ref = jax.vmap(lambda q: ref.adjoint(q, g, "sf"))(yb)
    assert float(jnp.abs(s - s_ref).max()
                 / jnp.abs(s_ref).max()) < precision.BF16_FP_REL_BOUND
    assert float(jnp.abs(b - b_ref).max()
                 / jnp.abs(b_ref).max()) < precision.BF16_FP_REL_BOUND
