"""On-kernel modular beam: Pallas SF FP/BP matched pair + helical scans.

The modular pair (``kernels/fp_modular.py``) must

* agree with its jnp SF oracle (same frame math, no Pallas windowing) on
  helical and irregular trajectories — FP and BP;
* reduce *exactly* to the cone pair on axial circular trajectories
  (``cone_as_modular`` cross-checks, Pallas vs Pallas);
* reject tilted (non-axial) frames loudly on the kernel path while the ref
  backend falls back to the Joseph ray-marcher;
* batch by grid folding with bit-identical per-sample results;
* drive the iterative recon stack on a helical scan out of the box.

Adjoint dot-tests for the pair live in tests/test_adjoint.py.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Projector, ProjectorSpec, VolumeGeometry, cone_beam,
                        from_config, helical_beam, modular_beam)
from repro.core.geometry import cone_as_modular
from repro.kernels import fp_cone, fp_modular, ops, ref, tune
from repro.recon import cgls, fista_tv, sirt


def _vol(nz=8):
    return VolumeGeometry(16, 16, nz)


def _helical(vol, na=8, nv=10, nu=24, n_turns=1.0, pitch=8.0):
    return helical_beam(n_turns, pitch, na, nv, nu, vol, sod=80.0, sdd=160.0,
                        pixel_width=2.0, pixel_height=2.0)


def _wobbly(vol, na=7, nv=10, nu=24, seed=3):
    """Irregular trajectory: non-uniform angles, per-view sod/sdd/source-z
    wobble, per-view in-plane + axial detector shifts, e_v flipped on every
    other view — the frame freedoms the fixed-geometry kernels can't
    express."""
    rng = np.random.default_rng(seed)
    ang = np.sort(rng.uniform(0, 2 * np.pi, na))
    sod = 80.0 + rng.uniform(-5, 5, na)
    sdd = 160.0 + rng.uniform(-10, 10, na)
    zsrc = rng.uniform(-4, 4, na)
    c, s = np.cos(ang), np.sin(ang)
    src = np.stack([sod * c, sod * s, zsrc], -1)
    eu = np.stack([-s, c, np.zeros(na)], -1)
    evz = np.where(np.arange(na) % 2 == 0, 1.0, -1.0)
    ev = np.stack([np.zeros(na), np.zeros(na), evz], -1)
    ctr = (np.stack([(sod - sdd) * c, (sod - sdd) * s, zsrc], -1)
           + rng.uniform(-3, 3, na)[:, None] * eu
           + rng.uniform(-3, 3, na)[:, None] * ev)
    return modular_beam(src, ctr, eu, ev, n_rows=nv, n_cols=nu, vol=vol,
                        pixel_width=2.0, pixel_height=2.0)


def _tilted(vol):
    g = _wobbly(vol)
    ev = np.asarray(g.det_v).copy()
    ev[:, 0] = 0.2
    ev /= np.linalg.norm(ev, axis=1, keepdims=True)
    return modular_beam(g.source_pos, g.det_center, g.det_u, ev,
                        g.n_rows, g.n_cols, vol, g.pixel_width,
                        g.pixel_height)


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=shape).astype(np.float32))


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(
        jnp.linalg.norm(b), 1e-12))


# --------------------------------------------------------------------------- #
# Helical constructor + config round-trip
# --------------------------------------------------------------------------- #
def test_helical_frames_axial():
    g = _helical(_vol())
    assert g.geom_type == "modular"
    assert fp_modular.modular_frames_axial(g)
    src = np.asarray(g.source_pos)
    # source orbits at sod and translates pitch mm per turn, centered on z=0
    assert np.allclose(np.hypot(src[:, 0], src[:, 1]), 80.0, atol=1e-4)
    assert np.isclose(src[0, 2], -4.0, atol=1e-5)        # -span/2
    assert np.all(np.diff(src[:, 2]) > 0)
    # detector rides with the source: per-view frames stay orthonormal
    eu, ev = np.asarray(g.det_u), np.asarray(g.det_v)
    assert np.allclose(np.einsum("ai,ai->a", eu, ev), 0.0, atol=1e-6)
    assert np.allclose(np.linalg.norm(eu, axis=1), 1.0, atol=1e-6)


def test_helical_validation():
    with pytest.raises(ValueError):
        helical_beam(0.0, 8.0, 8, 4, 24, _vol(), sod=80.0, sdd=160.0)
    with pytest.raises(ValueError):
        helical_beam(1.0, -1.0, 8, 4, 24, _vol(), sod=80.0, sdd=160.0)


def test_helical_from_config_roundtrip():
    cfg = {"geom_type": "helical", "n_turns": 1.5, "pitch": 6.0,
           "n_angles": 10, "n_rows": 8, "n_cols": 24,
           "sod": 80.0, "sdd": 160.0, "pixel_width": 2.0,
           "pixel_height": 2.0, "z_start": -3.0,
           "volume": {"nx": 16, "ny": 16, "nz": 8}}
    g = from_config(json.loads(json.dumps(cfg)))       # survives file I/O
    direct = helical_beam(1.5, 6.0, 10, 8, 24, _vol(), sod=80.0, sdd=160.0,
                          pixel_width=2.0, pixel_height=2.0, z_start=-3.0)
    assert g.geom_type == "modular"
    assert g.key() == direct.key()


# --------------------------------------------------------------------------- #
# Kernel vs oracle, and modular <-> cone equivalence
# --------------------------------------------------------------------------- #
def test_sf_ref_matches_cone_ref_on_axial_trajectory():
    """cone_as_modular cross-check, oracle level: the modular SF reference
    must reproduce the cone SF reference on a circular axial scan."""
    v = _vol()
    gc = cone_beam(6, 10, 24, v, sod=80.0, sdd=160.0,
                   pixel_width=2.0, pixel_height=2.0)
    f = _rand(v.shape)
    y_cone = ref.forward(f, gc, "sf")
    y_mod = fp_modular.fp_modular_sf_ref(f, cone_as_modular(gc))
    assert _rel(y_mod, y_cone) < 2e-5


@pytest.mark.parametrize("geom_fn", [_helical, _wobbly])
def test_fp_kernel_matches_oracle(geom_fn):
    v = _vol()
    g = geom_fn(v)
    f = _rand(v.shape)
    y_pal = fp_modular.fp_modular_sf_pallas(f, g)
    y_ref = fp_modular.fp_modular_sf_ref(f, g)
    assert _rel(y_pal, y_ref) < 1e-4


@pytest.mark.parametrize("geom_fn", [_helical, _wobbly])
def test_bp_kernel_matches_oracle(geom_fn):
    v = _vol()
    g = geom_fn(v)
    y = _rand(g.sino_shape, seed=1)
    b_pal = fp_modular.bp_modular_sf_pallas(y, g)
    b_ref = fp_modular.bp_modular_sf_ref(y, g)
    assert _rel(b_pal, b_ref) < 1e-4


def test_cone_as_modular_pallas_cross_check():
    """The modular Pallas pair must agree with the cone Pallas pair on an
    axial circular trajectory — two independent kernels, same model."""
    v = _vol()
    gc = cone_beam(6, 10, 24, v, sod=80.0, sdd=160.0,
                   pixel_width=2.0, pixel_height=2.0)
    gm = cone_as_modular(gc)
    f = _rand(v.shape)
    assert _rel(fp_modular.fp_modular_sf_pallas(f, gm),
                fp_cone.fp_cone_sf_pallas(f, gc)) < 1e-4
    y = _rand(gc.sino_shape, seed=1)
    assert _rel(fp_modular.bp_modular_sf_pallas(y, gm),
                fp_cone.bp_cone_sf_pallas(y, gc)) < 1e-4


def test_tall_volume_sliding_z_window():
    """nz far larger than the kernel's axial window NZW: the z-window
    genuinely slides (not clamped to the volume) while the source itself
    translates in z — the regime unique to helical scans."""
    v = _vol(nz=24)
    g = helical_beam(1.0, 16.0, 6, 6, 24, v, sod=80.0, sdd=120.0,
                     pixel_width=2.0, pixel_height=1.0)
    f = _rand(v.shape)
    assert _rel(fp_modular.fp_modular_sf_pallas(f, g),
                fp_modular.fp_modular_sf_ref(f, g)) < 1e-4


# --------------------------------------------------------------------------- #
# Batched grid folding
# --------------------------------------------------------------------------- #
def test_batched_fold_matches_per_sample():
    v = _vol()
    g = _helical(v, na=6)
    B = 3
    fb = _rand((B,) + v.shape)
    yb = fp_modular.fp_modular_sf_pallas(fb, g)
    y_each = jnp.stack([fp_modular.fp_modular_sf_pallas(fb[i], g)
                        for i in range(B)])
    np.testing.assert_allclose(np.asarray(yb), np.asarray(y_each),
                               rtol=1e-6, atol=1e-6)
    qb = _rand((B,) + g.sino_shape, seed=1)
    bb = fp_modular.bp_modular_sf_pallas(qb, g)
    b_each = jnp.stack([fp_modular.bp_modular_sf_pallas(qb[i], g)
                        for i in range(B)])
    np.testing.assert_allclose(np.asarray(bb), np.asarray(b_each),
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------- #
# Frame gating + dispatch
# --------------------------------------------------------------------------- #
def test_tilted_frames_rejected_on_kernel_path():
    v = _vol()
    gt = _tilted(v)
    assert not fp_modular.modular_frames_axial(gt)
    f = _rand(v.shape)
    with pytest.raises(NotImplementedError):
        fp_modular.fp_modular_sf_pallas(f, gt)
    with pytest.raises(NotImplementedError):
        fp_modular.bp_modular_sf_pallas(_rand(gt.sino_shape), gt)


def test_tilted_frames_ref_falls_back_to_joseph():
    v = _vol()
    gt = _tilted(v)
    f = _rand(v.shape)
    np.testing.assert_allclose(
        np.asarray(fp_modular.fp_modular_sf_ref(f, gt)),
        np.asarray(ref.fp_modular_joseph(f, gt)), rtol=1e-6, atol=1e-6)


def test_joseph_oracle_pair_matched_tilted():
    """bp_modular_joseph_ref is the exact adjoint of the Joseph FP — the
    advertised oracle pair for tilted frames the SF kernels don't cover."""
    v = _vol()
    gt = _tilted(v)
    f = _rand(v.shape)
    y = _rand(gt.sino_shape, seed=1)
    lhs = jnp.vdot(ref.fp_modular_joseph(f, gt), y)
    rhs = jnp.vdot(f, fp_modular.bp_modular_joseph_ref(y, gt))
    assert abs(lhs - rhs) / max(abs(lhs), 1e-6) < 1e-4, (lhs, rhs)


def test_supports_gate_registered():
    entry = ops._KERNEL_TABLE[("modular", "sf")]
    assert entry.supports is fp_modular.modular_frames_axial
    assert entry.supports(_helical(_vol()))
    assert not entry.supports(_tilted(_vol()))
    # auto backend never selects an unsupported kernel (off-TPU it is ref
    # regardless; the gate is what protects the TPU path)
    assert not ops._use_pallas(_tilted(_vol()), "sf", "auto")


def test_source_inside_volume_not_axial():
    v = _vol()
    na = 4
    ang = np.linspace(0, 2 * np.pi, na, endpoint=False)
    c, s = np.cos(ang), np.sin(ang)
    src = np.stack([5.0 * c, 5.0 * s, np.zeros(na)], -1)   # inside radius
    ctr = np.stack([-100.0 * c, -100.0 * s, np.zeros(na)], -1)
    eu = np.stack([-s, c, np.zeros(na)], -1)
    ev = np.stack([np.zeros(na), np.zeros(na), np.ones(na)], -1)
    g = modular_beam(src, ctr, eu, ev, 4, 24, v)
    assert not fp_modular.modular_frames_axial(g)


def test_modular_shape_class_and_heuristics():
    g = _helical(_vol(), nv=10)
    key = tune.shape_class(g)
    assert key[0] == "modular"
    cfg = tune.heuristic_config(g)
    # modular tiles physical detector rows like the exact cone kernels:
    # small column tile, rows padded to the sublane multiple (not 128)
    assert cfg.bu == 8 and cfg.bv == 16


def test_joseph_oracle_quantitative_agreement():
    """SF and Joseph are different discretizations of the same integral —
    they must agree to a few percent on a smooth object (helical scan)."""
    v = _vol()
    g = _helical(v)
    x, y, z = np.meshgrid(np.linspace(-1, 1, v.nx), np.linspace(-1, 1, v.ny),
                          np.linspace(-1, 1, v.nz), indexing="ij")
    f = jnp.asarray(np.exp(-(x ** 2 + y ** 2 + z ** 2) / 0.18
                           ).astype(np.float32))
    y_sf = fp_modular.fp_modular_sf_ref(f, g)
    y_j = ref.fp_modular_joseph(f, g)
    assert _rel(y_sf, y_j) < 0.06


# --------------------------------------------------------------------------- #
# Projector + iterative recon on a helical scan, out of the box
# --------------------------------------------------------------------------- #
def test_projector_gradient_is_modular_bp():
    v = _vol()
    g = _helical(v, na=6)
    proj = Projector(ProjectorSpec(g, "sf", backend="pallas"))
    assert proj.model == "sf"                      # no joseph coercion left
    f = _rand(v.shape)
    y = _rand(g.sino_shape, seed=1)
    grad = jax.grad(lambda x: 0.5 * jnp.sum((proj(x) - y) ** 2))(f)
    expected = fp_modular.bp_modular_sf_pallas(proj(f) - y, g)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(expected),
                               rtol=1e-4, atol=1e-5)


def test_recon_helical_out_of_the_box():
    """sirt / cgls / fista_tv reconstruct a helical scan through the stock
    Projector (default backend) — the ROADMAP's scenario-diversity goal."""
    v = _vol()
    g = helical_beam(1.5, 6.0, 24, 10, 28, v, sod=80.0, sdd=160.0,
                     pixel_width=1.5, pixel_height=1.5)
    f = (jnp.zeros(v.shape).at[5:11, 5:11, 2:6].set(0.02)
         .at[8:13, 3:7, 3:5].set(0.03))
    proj = Projector(ProjectorSpec(g))
    y = proj(f)
    err0 = float(jnp.linalg.norm(f))
    x_s = sirt(proj, y, n_iters=30).image
    assert float(jnp.linalg.norm(x_s - f)) < 0.5 * err0
    x_c = cgls(proj, y, n_iters=15).image
    assert float(jnp.linalg.norm(x_c - f)) < 0.35 * err0
    x_t = fista_tv(proj, y, n_iters=15, beta=1e-5).image
    assert float(jnp.linalg.norm(x_t - f)) < 0.6 * err0
