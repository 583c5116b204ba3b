"""Unit tests for the projector-in-the-loop training subsystem
(:mod:`repro.launch.ct_train`): config validation, a short end-to-end fit on
each model family, and the trainer-state checkpoint round-trip (params +
optimizer state + EMA + data-pipeline cursor)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.launch.ct_train import (GEOMETRIES, CTTrainer, TrainConfig,
                                   build_geometry, smoke_config)


def tiny(geometry="sparse_fan", **kw):
    base = dict(geometry=geometry, n=12, steps=3, batch=2, base=8, levels=1,
                depth=1, warmup=1, ema_warmup=2, refine_iters=5,
                model="unet" if geometry != "limited_angle" else "auto")
    base.update(kw)
    return TrainConfig(**base)


# --------------------------------------------------------------------------- #
# Config
# --------------------------------------------------------------------------- #
def test_config_validation():
    with pytest.raises(ValueError):
        TrainConfig(geometry="cone_spiral")
    with pytest.raises(ValueError):
        TrainConfig(geometry="helical", model="hybrid")
    with pytest.raises(ValueError):
        TrainConfig(geometry="helical", nz=1)
    with pytest.raises(ValueError):
        TrainConfig(n=4)
    with pytest.raises(ValueError):
        TrainConfig(dc_weight=-0.1)


def test_config_auto_resolution():
    cfg = TrainConfig(geometry="limited_angle")
    assert cfg.nz == 1 and cfg.resolved_model == "hybrid"
    assert cfg.mask_mode == "limited_angle"
    cfg = TrainConfig(geometry="helical")
    assert cfg.nz == 8 and cfg.resolved_model == "unet"
    assert cfg.mask_mode == "few_view"
    assert cfg.replace(nz=4).nz == 4


def test_smoke_configs_build_for_all_geometries():
    for g in GEOMETRIES:
        cfg = smoke_config(g)
        geom = build_geometry(cfg)
        assert geom.vol.shape == (cfg.n, cfg.n, cfg.nz)
        assert geom.n_angles >= 8


# --------------------------------------------------------------------------- #
# Training end-to-end (tiny)
# --------------------------------------------------------------------------- #
def test_fit_and_evaluate_unet():
    trainer = CTTrainer(tiny("sparse_fan"))
    losses = trainer.fit(log_every=0)
    assert len(losses) == 3 and all(np.isfinite(losses))
    m = trainer.evaluate(n_test=1)
    for k in ("psnr_net", "ssim_net", "psnr_refined", "ssim_refined",
              "dc_net", "dc_refined"):
        assert np.isfinite(m[k]), k
    assert 0.0 <= m["ssim_refined"] <= 1.0
    assert m["dc_refined"] <= m["dc_net"] + 1e-6


def test_fit_hybrid_limited_angle():
    trainer = CTTrainer(tiny("limited_angle"))
    assert set(trainer.params) == {"ctnet", "unet"}
    losses = trainer.fit(log_every=0)
    assert all(np.isfinite(losses))
    # hybrid predict returns a completed sinogram alongside the volume
    imgs, masks = trainer.pipe.batch(0)
    sino = trainer.proj(trainer._as_volume(imgs))
    m4 = jnp.asarray(masks)[:, :, None, None]
    pred, completed = trainer.predict(trainer.params, sino * m4,
                                      jnp.asarray(masks))
    assert pred.shape == (2, 12, 12, 1)
    assert completed is not None and completed.shape == sino.shape


@pytest.mark.slow
def test_fit_helical_volumetric():
    trainer = CTTrainer(tiny("helical", nz=2, n=12, batch=1))
    losses = trainer.fit(log_every=0)
    assert all(np.isfinite(losses))
    m = trainer.evaluate(n_test=1)
    assert np.isfinite(m["psnr_refined"])


def test_loss_grads_flow_through_dc_term():
    """dc_weight must change the gradient — the projector really is inside
    the differentiation path, not just the data generator."""
    trainer_on = CTTrainer(tiny("sparse_fan", dc_weight=1.0))
    trainer_off = CTTrainer(tiny("sparse_fan", dc_weight=0.0))
    imgs, masks = trainer_on.pipe.batch(0)
    gt = trainer_on._as_volume(imgs)
    sino = trainer_on.proj(gt)
    g_on = jax.grad(trainer_on.loss_fn)(trainer_on.params, sino,
                                        jnp.asarray(masks), gt)
    g_off = jax.grad(trainer_off.loss_fn)(trainer_off.params, sino,
                                          jnp.asarray(masks), gt)
    diff = sum(float(jnp.abs(a - b).sum()) for a, b in
               zip(jax.tree.leaves(g_on), jax.tree.leaves(g_off)))
    assert diff > 0


# --------------------------------------------------------------------------- #
# Checkpoint round-trip
# --------------------------------------------------------------------------- #
def test_checkpoint_roundtrip_full_trainer_state(tmp_path):
    cfg = tiny("sparse_fan", steps=4, ckpt_dir=str(tmp_path / "ck"),
               ckpt_every=2)
    t1 = CTTrainer(cfg)
    losses = t1.fit(log_every=0)
    assert len(losses) == 4

    t2 = CTTrainer(cfg)
    assert t2.resume() == 4
    for a, b in zip(jax.tree.leaves(t1.params), jax.tree.leaves(t2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(t1.ema), jax.tree.leaves(t2.ema)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(t1.opt_state),
                    jax.tree.leaves(t2.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert t2.pipe.state_dict() == t1.pipe.state_dict()
    # fit() on the restored trainer is a no-op (schedule already finished)
    assert t2.fit(log_every=0) == []


def test_resume_without_checkpoint_is_fresh_start(tmp_path):
    cfg = tiny("sparse_fan", ckpt_dir=str(tmp_path / "never_written"))
    t = CTTrainer(cfg)
    assert t.resume() == 0 and t.step == 0


def test_ct_path_keeps_dryrun_xla_flags_out():
    """The CT entry points never import ``launch/dryrun.py``, which rewrites
    ``XLA_FLAGS`` (512 forced host devices) when imported.  Checked in a
    fresh interpreter, so other tests' imports cannot mask it."""
    import os
    import subprocess
    import sys
    code = ("import sys\n"
            "import repro.launch.ct_train, repro.launch.ct_serve\n"
            "import repro.core.distributed, repro.recon\n"
            "assert 'repro.launch.dryrun' not in sys.modules\n")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
