"""KernelConfig registry / heuristics / autotune plumbing + the op cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Projector, ProjectorSpec, VolumeGeometry, parallel_beam
from repro.kernels import ops, ref, tune
from repro.kernels.tune import KernelConfig


@pytest.fixture(autouse=True)
def _clean_registry():
    tune.clear()
    yield
    tune.clear()


def _geom(**kw):
    return parallel_beam(6, 2, 24, VolumeGeometry(16, 16, 2), **kw)


def test_config_validation():
    with pytest.raises(ValueError):
        KernelConfig(bu=0)
    with pytest.raises(ValueError):
        KernelConfig(bv=100)          # not a sublane multiple
    with pytest.raises(ValueError):
        KernelConfig(bs=0)            # stripe-reuse factor must be >= 1
    c = KernelConfig(bu=8, ba=2)
    assert c.replace(ba=4).ba == 4 and c.ba == 2
    assert c.bs == 1                  # stripe reuse off by default
    assert c.replace(bs=4).bs == 4


def test_candidates_sweep_stripe_reuse():
    """The autotune candidate grid includes bs > 1 BP stripe-blocking
    entries."""
    cand = list(tune.default_candidates(_geom()))
    assert {c.bs for c in cand} >= {1, 2, 4}


@pytest.mark.parametrize("n,bs", [(512, 16), (1024, 32)])
def test_tpu_heuristic_parallel_bp_covers_whole_line(monkeypatch, n, bs):
    """On the TPU the parallel BP's stripe serves its whole gathered line:
    Table-1 512 and 1024 at bg = 32."""
    from repro.configs.leap_ct import table1_parallel
    monkeypatch.setattr(tune, "_on_tpu", lambda: True)
    cfg = tune.heuristic_config(table1_parallel(n, 8))
    assert (cfg.bg, cfg.bs) == (32, bs)


def test_tpu_heuristic_whole_line_respects_vmem_cap(monkeypatch):
    """A very wide volume's whole-line bs is capped so the double-buffered
    f32 output block stays within BP_OUT_VMEM."""
    monkeypatch.setattr(tune, "_on_tpu", lambda: True)
    cfg = tune.heuristic_config(
        parallel_beam(8, 1, 12288, VolumeGeometry(8192, 8192, 1)))
    assert 2 * cfg.bs * cfg.bg * cfg.bv * 4 == tune.BP_OUT_VMEM
    assert cfg.bs * cfg.bg < 8192


@pytest.mark.parametrize("kind", ["fan", "cone-packed"])
def test_tpu_heuristic_fan_bps_keep_two_sub_tiles(monkeypatch, kind):
    from repro.configs.leap_ct import table1_cone
    from repro.core import fan_beam
    monkeypatch.setattr(tune, "_on_tpu", lambda: True)
    if kind == "fan":
        cfg = tune.heuristic_config(fan_beam(
            8, 1, 768, VolumeGeometry(512, 512, 1), sod=1000.0, sdd=1500.0))
    else:
        cfg = tune.heuristic_config(table1_cone(512, 8), packed=True)
    assert cfg.bs == 2


def test_candidates_hold_the_whole_line_bs():
    g = parallel_beam(6, 2, 768, VolumeGeometry(512, 512, 2))
    cand = list(tune.default_candidates(g))
    for bg in (8, 16, 32):
        assert {c.bs for c in cand if c.bg == bg} == {1, 2, 4, 512 // bg}


def test_heuristic_defaults_off_tpu():
    cfg = tune.get_config(_geom())
    assert cfg.bv % 128 == 0
    if jax.default_backend() != "tpu":
        assert cfg.ba == 1 and cfg.bab == 1   # interpret mode: minimal programs


def test_shape_class_buckets_not_exact_values():
    g1 = _geom()
    g2 = parallel_beam(6, 2, 24, VolumeGeometry(16, 16, 2),
                       angles=np.linspace(0.1, 2.0, 6))
    assert tune.shape_class(g1) == tune.shape_class(g2)
    g3 = parallel_beam(6, 2, 500, VolumeGeometry(16, 16, 2))
    assert tune.shape_class(g1) != tune.shape_class(g3)


def test_register_config_overrides():
    g = _geom()
    pinned = KernelConfig(bu=8, ba=2, bg=8, bab=2)
    tune.register_config(tune.shape_class(g), pinned)
    assert tune.get_config(g) is pinned


def test_autotune_off_tpu_returns_heuristic_and_caches():
    g = _geom()
    cfg = tune.autotune(g)
    assert isinstance(cfg, KernelConfig)
    assert tune.get_config(g) is cfg          # cached under the shape class


def test_sweep_reports_refused_candidates_and_raises_when_all_fail():
    """A candidate the chip refuses is skipped with a warning, never in
    silence; a sweep with no candidate left raises instead of falling back
    to an unmeasured config."""
    g = _geom()
    x = jnp.ones(g.vol.shape)

    def fp(a, geom, config):
        if config.bu == 8:
            raise ValueError("tiling refused")
        return a * config.bu

    grid = {8: KernelConfig(bu=8), 16: KernelConfig(bu=16)}
    with pytest.warns(RuntimeWarning, match="tiling refused"):
        assert tune._sweep(fp, g, x, 1, "FP", grid) == 16
    with pytest.warns(RuntimeWarning), \
            pytest.raises(RuntimeError, match="every candidate failed"):
        tune._sweep(fp, g, x, 1, "FP", {8: KernelConfig(bu=8)})


def test_cone_autotune_sweeps_the_pairs_shared_gathered_tile(monkeypatch):
    """The exact cone FP tiles the gathered axis by the BP's ``bg``, so the
    cone sweep times each (bg, bab) candidate on FP and BP together and
    keeps the winner for both."""
    from repro.core import cone_beam
    from repro.kernels import fp_cone
    g = cone_beam(3, 8, 24, VolumeGeometry(8, 8, 4), sod=80.0, sdd=160.0,
                  pixel_width=2.0, pixel_height=2.0)
    seen = {}

    def fake_sweep(fn, geom, x, reps, what, grid):
        seen.update(fn=fn, x=x, what=what, grid=grid)
        return (16, 2)

    monkeypatch.setattr(tune, "_on_tpu", lambda: True)
    monkeypatch.setattr(tune, "_sweep", fake_sweep)
    monkeypatch.setattr(tune, "save_tuned", lambda key, cfg: None)
    cand = [KernelConfig(bg=8, bab=1), KernelConfig(bg=16, bab=2)]
    cfg = tune.autotune(g, candidates=cand)
    assert seen["what"] == "FP+BP"
    assert set(seen["grid"]) == {(8, 1), (16, 2)}
    assert (cfg.bg, cfg.bab) == (16, 2)
    assert tune.get_config(g) is cfg
    monkeypatch.undo()                    # back to interpret mode
    pinned = KernelConfig(bg=8, bv=8)
    fp, bp = seen["fn"](seen["x"], g, config=pinned)
    np.testing.assert_array_equal(
        fp, fp_cone.fp_cone_sf_pallas(seen["x"][0], g, config=pinned))
    np.testing.assert_array_equal(
        bp, fp_cone.bp_cone_sf_pallas(seen["x"][1], g, config=pinned))


def test_resolve_backend_reports_what_dispatches():
    """``backend="auto"`` picks the jnp oracle off the TPU, and for a
    geometry outside a kernel's ``supports`` gate even on it;
    ``resolve_backend`` makes that visible instead of silent."""
    from repro.core import modular_beam
    g = _geom()
    on_tpu = jax.default_backend() == "tpu"
    assert ops.resolve_backend(ProjectorSpec(g)) == \
        ("pallas" if on_tpu else "ref")
    assert ops.resolve_backend(ProjectorSpec(g, backend="pallas")) == "pallas"
    assert ops.resolve_backend(ProjectorSpec(g, backend="ref")) == "ref"
    # a tilted detector frame: no SF kernel covers it
    na = 4
    tilt = np.array([[0.0, 0.6, 0.8]] * na)
    tilted = modular_beam(
        source_pos=np.array([[-50.0, 0.0, 0.0]] * na),
        det_center=np.array([[50.0, 0.0, 0.0]] * na),
        det_u=np.array([[0.0, 1.0, 0.0]] * na), det_v=tilt,
        n_rows=4, n_cols=8, vol=VolumeGeometry(8, 8, 4))
    assert ops.resolve_backend(ProjectorSpec(tilted)) == "ref"


def test_pinned_config_produces_correct_kernels():
    g = _geom()
    tune.register_config(tune.shape_class(g), KernelConfig(bu=8, ba=3, bab=2))
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    out = ops.forward_project(f, ProjectorSpec(g, "sf", backend="pallas"))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.forward(f, g, "sf")),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------- #
# Disk persistence (~/.cache/repro/tune.json by default; tests point
# REPRO_TUNE_CACHE_PATH at tmp via the conftest autouse fixture)
# --------------------------------------------------------------------------- #
def test_tune_cache_roundtrip(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE_PATH", str(path))
    g = _geom()
    key = tune.shape_class(g)
    cfg = KernelConfig(bu=32, ba=2, bg=32, bab=2, bs=2)
    tune.save_tuned(key, cfg)
    assert path.exists()
    assert tune.load_tuned(key) == cfg
    # a fresh process (cleared in-process registries) picks it up
    tune.clear()
    assert tune.get_config(g) == cfg
    # keyed by shape class: another class misses
    g2 = parallel_beam(6, 2, 500, VolumeGeometry(16, 16, 2))
    assert tune.load_tuned(tune.shape_class(g2)) is None


def test_tune_cache_escape_hatch(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE_PATH", str(path))
    monkeypatch.setenv("REPRO_TUNE_CACHE", "0")
    key = tune.shape_class(_geom())
    tune.save_tuned(key, KernelConfig(bu=32))
    assert not path.exists()                   # writes disabled
    assert tune.load_tuned(key) is None        # reads disabled too
    cfg = tune.get_config(_geom())             # falls back to heuristics
    assert cfg == tune.heuristic_config(_geom())


def test_tune_cache_corrupt_or_stale_file_ignored(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE_PATH", str(path))
    key = tune.shape_class(_geom())
    path.write_text("{not json")
    assert tune.load_tuned(key) is None
    # a stale schema (bad field values) is ignored, then overwritten cleanly
    path.write_text('{"%s": {"bu": "huge"}}' % tune._disk_key(key))
    assert tune.load_tuned(key) is None
    tune.save_tuned(key, KernelConfig(bu=16))
    assert tune.load_tuned(key) == KernelConfig(bu=16)


def test_tune_cache_pre_stripe_entry_still_loads(tmp_path, monkeypatch):
    """Entries written before the bs knob existed (no "bs" field) load with
    the field default instead of being discarded as stale."""
    path = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE_PATH", str(path))
    key = tune.shape_class(_geom())
    path.write_text('{"%s": {"bu": 16, "bv": 128, "ba": 2, "bg": 16, '
                    '"bab": 2}}' % tune._disk_key(key))
    cfg = tune.load_tuned(key)
    assert cfg == KernelConfig(bu=16, bv=128, ba=2, bg=16, bab=2, bs=1)


# --------------------------------------------------------------------------- #
# Op cache: content-keyed, bounded, config round-trip
# --------------------------------------------------------------------------- #
def test_ops_cache_content_keyed():
    """Two distinct but equal geometry objects share one op entry."""
    fp1, _ = ops.get_ops(ProjectorSpec(_geom(), "sf", "ref"))
    fp2, _ = ops.get_ops(ProjectorSpec(_geom(), "sf", "ref"))
    assert fp1 is fp2


def test_ops_cache_bounded_eviction():
    ops.clear_cache()
    for i in range(ops._OPS_CACHE_SIZE + 40):
        g = parallel_beam(6, 2, 24, VolumeGeometry(16, 16, 2,
                                                   offset_x=1e-3 * (i + 1)))
        ops.get_ops(ProjectorSpec(g, "sf", "ref"))
    assert len(ops._OPS_CACHE) <= ops._OPS_CACHE_SIZE


def test_config_roundtrip_no_retrace():
    """Equal configs map to the same cached ops, so an outer jit never
    retraces; a different config is a different entry."""
    g = _geom()
    fp1, bp1 = ops.get_ops(ProjectorSpec(g, "sf", "pallas",
                                         config=KernelConfig(ba=2)))
    fp2, bp2 = ops.get_ops(ProjectorSpec(g, "sf", "pallas",
                                         config=KernelConfig(ba=2)))
    assert fp1 is fp2 and bp1 is bp2
    fp3, _ = ops.get_ops(ProjectorSpec(g, "sf", "pallas",
                                       config=KernelConfig(ba=3)))
    assert fp3 is not fp1


def test_dtype_keyed_config_reachable():
    """Configs registered for a non-f32 dtype class are found by the kernel
    entry points (the input dtype is threaded into resolution)."""
    g = _geom()
    pinned = KernelConfig(bu=8, ba=2)
    tune.register_config(tune.shape_class(g, 1, jnp.bfloat16), pinned)
    assert tune.get_config(g, dtype=jnp.bfloat16) is pinned
    assert tune.get_config(g) is not pinned
    from repro.kernels import fp_par
    f = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape).astype(
        jnp.bfloat16)
    seen = []
    orig = tune.get_config

    def spy(geom, batch=1, dtype=jnp.float32, **kw):
        seen.append(jnp.dtype(dtype).name)
        return orig(geom, batch=batch, dtype=dtype, **kw)

    tune.get_config = spy
    try:
        fp_par.fp_parallel_sf_pallas(f, g)
        ops.clear_cache()
        # dispatch path too
        ops.forward_project(f, ProjectorSpec(g, "sf", backend="pallas"))
    finally:
        tune.get_config = orig
    assert seen.count("bfloat16") >= 2


def test_batched_dispatch_resolves_with_real_batch(monkeypatch):
    """The public dispatch path must resolve configs against the actual
    leading batch size (batch-aware shape classes), not batch=1."""
    g = _geom()
    calls = []
    orig = tune.get_config

    def spy(geom, batch=1, **kw):
        calls.append(batch)
        return orig(geom, batch=batch, **kw)

    monkeypatch.setattr(tune, "get_config", spy)
    ops.clear_cache()
    f = jax.random.normal(jax.random.PRNGKey(0), (8,) + g.vol.shape)
    out = ops.forward_project(f, ProjectorSpec(g, "sf", backend="pallas"))
    assert out.shape == (8,) + g.sino_shape
    assert 8 in calls


def test_ops_cache_dtype_keyed():
    """The op cache keys the dtype pair: compute_dtype variants and input
    dtypes get distinct bundles (a cdt=None bundle follows its input's
    dtype, so f32 and bf16 callers must never share traced closures)."""
    g = _geom()
    fp32, _ = ops.get_ops(ProjectorSpec(g, "sf", "ref"))
    fpb, _ = ops.get_ops(ProjectorSpec(g, "sf", "ref",
                                       compute_dtype="bfloat16"))
    assert fp32 is not fpb
    # alias normalizes into the same key
    fpb2, _ = ops.get_ops(ProjectorSpec(g, "sf", "ref", compute_dtype="bf16"))
    assert fpb is fpb2
    # input dtype is part of the content key even on the default-f32 path
    ops.clear_cache()
    f32 = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    out32 = ops.forward_project(f32, ProjectorSpec(g, "sf", backend="pallas"))
    n1 = len(ops._OPS_CACHE)
    out16 = ops.forward_project(f32.astype(jnp.bfloat16),
                                ProjectorSpec(g, "sf", backend="pallas"))
    assert len(ops._OPS_CACHE) == n1 + 1
    assert out32.dtype == jnp.float32 and out16.dtype == jnp.bfloat16


def test_projector_compute_dtype_roundtrip():
    """Projector(compute_dtype=...) reaches the kernels: bf16 tiles change
    the numerics measurably (vs the f32 run) while the output keeps the
    caller's f32 dtype; bad values raise at construction."""
    g = _geom()
    x = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    from repro.kernels import precision
    s32 = Projector(ProjectorSpec(g, "sf", backend="pallas"))(x)
    sb = Projector(ProjectorSpec(g, "sf", backend="pallas",
                                 compute_dtype="bf16"))(x)
    assert sb.dtype == jnp.float32
    rel = float(jnp.abs(sb - s32).max() / jnp.abs(s32).max())
    assert 0.0 < rel < precision.BF16_FP_REL_BOUND
    with pytest.raises(ValueError):
        Projector(ProjectorSpec(g, compute_dtype="float64"))


def test_projector_accepts_config():
    g = _geom()
    cfg = KernelConfig(bu=8, ba=2)
    proj = Projector(ProjectorSpec(g, "sf", backend="pallas", config=cfg))
    x = jax.random.normal(jax.random.PRNGKey(0), g.vol.shape)
    y = jax.random.normal(jax.random.PRNGKey(1), g.sino_shape)
    lhs = jnp.vdot(proj(x), y)
    rhs = jnp.vdot(x, proj.T(y))
    assert abs(lhs - rhs) / max(abs(lhs), 1e-6) < 1e-4
    with pytest.raises(TypeError):
        Projector(ProjectorSpec(g, "sf", config="big"))  # not a KernelConfig


def test_fbp_accepts_config():
    from repro.core.fbp import fbp
    g = _geom()
    sino = jnp.ones(g.sino_shape)
    rec = fbp(sino, g, config=KernelConfig())
    assert rec.shape == g.vol.shape
