"""Projector-in-the-loop CT training subsystem (the flagship trained-model
path).

The paper's entire point is the differentiable FP/BP pair *inside* deep
learning pipelines; this module is the subsystem that actually trains recon
networks through it, across the three hard geometry classes:

  * ``limited_angle``  — parallel beam, a contiguous missing angular wedge
                         (paper §4; hybrid CT-Net + U-Net supported);
  * ``sparse_fan``     — fan beam, randomly decimated views (sparse-view CT);
  * ``helical``        — modular-frame helical trajectory over a 3D volume,
                         sparse views along the helix.

One :class:`TrainConfig` (frozen, validated) describes a run; one
:class:`CTTrainer` executes it:

    cfg = TrainConfig(geometry="sparse_fan", n=48, steps=300)
    trainer = CTTrainer(cfg)
    losses = trainer.fit()             # auto-resumes from cfg.ckpt_dir
    metrics = trainer.evaluate()       # PSNR/SSIM + DC residual, EMA params

Training loss = supervised reconstruction MSE + the paper's masked
data-consistency term through the matched projector pair (+ a sinogram-
completion term for the hybrid model).  Evaluation runs the full paper-§4
inference pipeline (network prediction, then CG data-consistency
refinement) and reports both image quality (PSNR/SSIM) and the relative
projection-consistency residual per geometry — the same numbers the
``fig3_data_consistency`` benchmark feeds to the CI quality gate.

Scale-out: ``data_parallel=True`` runs the train step under
``compat.shard_map`` over the local mesh's data axis — params/opt/EMA
replicated, the batch sharded, grads+loss pmean'd — the repo's one
data-parallel CT training path (the projector stays local per shard; a
spec carrying a
:class:`~repro.core.spec.ShardSpec` is stripped the same way, because DP
and operator sharding compose through
:class:`~repro.core.distributed.DistributedProjector`, not through this
step).  ``compute_dtype`` threads the bf16-tile / f32-accumulate kernel
policy straight into the in-loop projector.

CLI (what the CI ``training-smoke`` job runs)::

    PYTHONPATH=src python -m repro.launch.ct_train \
        --geometry all --smoke --check --metrics-json metrics.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.geometry import (CTGeometry, VolumeGeometry, fan_beam,
                                 helical_beam, parallel_beam)
from repro.core.projector import Projector
from repro.core.spec import ProjectorSpec
from repro.data.metrics import psnr, ssim
from repro.data.pipeline import CTDataPipeline
from repro.launch.mesh import dp_size, make_local_mesh
from repro.nn.ctnet import ctnet_apply, ctnet_init
from repro.nn.unet import unet_apply, unet_init
from repro.optim import (adamw, apply_updates, ema_init, ema_params,
                         ema_update, warmup_cosine)
from repro.recon.completion import complete_and_refine, projection_residual
from repro.runtime import checkpoint as CKPT
from repro.runtime import compile_cache, obs

__all__ = ["GEOMETRIES", "TrainConfig", "CTTrainer", "build_geometry",
           "smoke_config", "main"]

GEOMETRIES = ("limited_angle", "sparse_fan", "helical")
_MODELS = ("auto", "unet", "hybrid")


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Frozen description of one projector-in-the-loop training run.

    Geometry/data:
        geometry:      one of :data:`GEOMETRIES`.
        n:             transaxial volume size (``n x n`` voxels).
        nz:            axial size; 0 = auto (8 for helical, 1 otherwise).
        available_deg: angular coverage for ``limited_angle`` masks.
        n_views_few:   measured views for the sparse modes; 0 = auto
                       (half of the geometry's views).
    Model:
        model:         "auto" | "unet" | "hybrid".  "auto" picks the paper's
                       hybrid CT-Net + U-Net for ``limited_angle`` and the
                       image-domain U-Net elsewhere; "hybrid" needs a 2D
                       (single detector row) geometry.
        base/levels:   U-Net width/depth;  ``depth`` is the CT-Net depth.
    Optimization:
        steps/batch/lr/warmup: the usual; AdamW + warmup-cosine.
        dc_weight:     weight of the masked data-consistency loss through
                       the projector (0 disables — ablation).
        sino_weight:   weight of the sinogram-completion loss (hybrid only).
        ema_decay/ema_warmup: eval-parameter averaging (see optim/ema.py).
    Infrastructure:
        compute_dtype: kernel tile precision for the in-loop projector
                       ("bfloat16" | "float32" | None = follow input).
        data_parallel: shard the batch over the local mesh's data axis.
        ckpt_dir/ckpt_every: checkpoint location and cadence (None = off).
        refine_iters/refine_beta: CG data-consistency refinement used by
                       :meth:`CTTrainer.evaluate`.
    """

    geometry: str = "limited_angle"
    n: int = 48
    nz: int = 0
    available_deg: float = 60.0
    n_views_few: int = 0
    model: str = "auto"
    base: int = 16
    levels: int = 2
    depth: int = 3
    steps: int = 120
    batch: int = 4
    lr: float = 2e-3
    warmup: int = 20
    dc_weight: float = 0.1
    sino_weight: float = 0.5
    ema_decay: float = 0.999
    ema_warmup: int = 10
    compute_dtype: Optional[str] = None
    data_parallel: bool = False
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    refine_iters: int = 20
    refine_beta: float = 0.05
    seed: int = 0

    def __post_init__(self):
        if self.geometry not in GEOMETRIES:
            raise ValueError(f"unknown geometry {self.geometry!r}; expected "
                             f"one of {GEOMETRIES}")
        if self.model not in _MODELS:
            raise ValueError(f"unknown model {self.model!r}; expected one "
                             f"of {_MODELS}")
        if self.n < 8:
            raise ValueError(f"n must be >= 8, got {self.n}")
        if self.nz == 0:
            object.__setattr__(self, "nz",
                               8 if self.geometry == "helical" else 1)
        if self.nz < 1:
            raise ValueError(f"nz must be >= 1 (or 0 = auto), got {self.nz}")
        if self.geometry == "helical" and self.nz < 2:
            raise ValueError("helical training needs a volumetric object "
                             f"(nz >= 2), got nz={self.nz}")
        if self.steps < 1 or self.batch < 1:
            raise ValueError(f"steps/batch must be >= 1, got "
                             f"{(self.steps, self.batch)}")
        if self.resolved_model == "hybrid" and self.geometry == "helical":
            raise ValueError("the hybrid CT-Net path operates on 2D "
                             "(single-row) sinograms; helical geometries "
                             "need model='unet'")
        if not 0.0 <= self.dc_weight:
            raise ValueError(f"dc_weight must be >= 0, got {self.dc_weight}")

    @property
    def resolved_model(self) -> str:
        if self.model != "auto":
            return self.model
        return "hybrid" if self.geometry == "limited_angle" else "unet"

    @property
    def mask_mode(self) -> str:
        return ("limited_angle" if self.geometry == "limited_angle"
                else "few_view")

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)


def build_geometry(cfg: TrainConfig) -> CTGeometry:
    """The scanner for a config — one representative hard geometry per
    class, sized relative to ``cfg.n`` so every knob scales together."""
    n = cfg.n
    if cfg.geometry == "limited_angle":
        vol = VolumeGeometry(n, n, 1)
        return parallel_beam(int(1.5 * n), 1, int(1.5 * n), vol)
    if cfg.geometry == "sparse_fan":
        vol = VolumeGeometry(n, n, 1)
        return fan_beam(int(1.5 * n), 1, int(2.2 * n), vol,
                        sod=2.0 * n, sdd=3.0 * n, angular_range=360.0)
    # helical: 2 turns covering the volume's z extent, detector rows wide
    # enough (at magnification 1.5) to see the whole pitch per view.
    vol = VolumeGeometry(n, n, cfg.nz)
    return helical_beam(n_turns=2.0, pitch=cfg.nz / 2.0,
                        n_angles=int(1.5 * n), n_rows=max(6, cfg.nz // 2 + 2),
                        n_cols=int(2.2 * n), vol=vol,
                        sod=2.0 * n, sdd=3.0 * n, pixel_height=2.0)


def smoke_config(geometry: str, **overrides) -> TrainConfig:
    """Tiny CPU-trainable config (~40 steps) — what the CI ``training-smoke``
    job and the quality benchmark run."""
    base = dict(geometry=geometry, n=32, steps=40, batch=4, base=8,
                levels=2, depth=2, lr=2e-3, warmup=5, ema_warmup=5,
                refine_iters=15)
    if geometry == "helical":
        base.update(n=20, nz=4, batch=2)
    base.update(overrides)
    return TrainConfig(**base)


class CTTrainer:
    """Spec-first projector-in-the-loop trainer: ``fit`` / ``evaluate`` /
    ``resume`` (see module docstring)."""

    def __init__(self, cfg: TrainConfig):
        self.cfg = cfg
        self.geom = build_geometry(cfg)
        self.spec = ProjectorSpec(self.geom,
                                  compute_dtype=cfg.compute_dtype)
        self.proj = Projector(self.spec)
        n_few = cfg.n_views_few or max(8, self.geom.n_angles // 2)
        self.pipe = CTDataPipeline(self.geom, batch_size=cfg.batch,
                                   seed=cfg.seed, mode=cfg.mask_mode,
                                   available_deg=cfg.available_deg,
                                   n_views_few=n_few)
        key = jax.random.PRNGKey(cfg.seed)
        self.params = self._init_params(key)
        self.opt = adamw(warmup_cosine(cfg.lr, cfg.warmup, cfg.steps))
        self.opt_state = self.opt.init(self.params)
        self.ema = ema_init(self.params)
        self.step = 0
        self._step_fn = None
        self._mesh = None

    # -- model ------------------------------------------------------------- #
    def _init_params(self, key):
        cfg = self.cfg
        in_ch = cfg.nz
        unet = unet_init(jax.random.fold_in(key, 1), base=cfg.base,
                         levels=cfg.levels, in_ch=in_ch, out_ch=in_ch)
        if cfg.resolved_model == "hybrid":
            return {"ctnet": ctnet_init(key, base=cfg.base, depth=cfg.depth),
                    "unet": unet}
        return {"unet": unet}

    def _initial_recon(self, sino_masked, mask):
        """Network input from the ill-posed data: masked FBP where an
        analytic inverse exists (parallel/fan), mask-normalized
        backprojection for modular/helical frames (no analytic helical
        recon in the stack — ROADMAP)."""
        m4 = mask[:, :, None, None]
        if self.geom.geom_type in ("parallel", "fan"):
            return self.proj.fbp(sino_masked * m4)
        # SIRT-style normalization A^T(M y) / A^T(M A 1): the denominator
        # carries the ray path lengths, so x0 lands at attenuation scale
        # (a plain ray-count normalization overshoots by ~L, the chord
        # length through the volume).
        fp_ones = self.proj(jnp.ones(self.geom.vol.shape,
                                     sino_masked.dtype))
        norm = self.proj.T(m4 * fp_ones[None])
        x0 = self.proj.T(m4 * sino_masked)
        floor = 1e-3 * jnp.max(norm, axis=(1, 2, 3), keepdims=True) + 1e-12
        return x0 / jnp.maximum(norm, floor)

    def predict(self, params, sino_masked, mask):
        """(B, na, nv, nu) masked sinogram + (B, na) view mask ->
        ``(volume (B, nx, ny, nz), completed sinogram or None)``."""
        if self.cfg.resolved_model == "hybrid":
            mask2d = mask[:, :, None] * jnp.ones((1, 1, self.geom.n_cols),
                                                 sino_masked.dtype)
            completed = ctnet_apply(params["ctnet"], sino_masked[:, :, 0, :],
                                    mask2d)
            x_in = self.proj.fbp(completed[:, :, None, :])
            pred = unet_apply(params["unet"], x_in)
            return pred, completed[:, :, None, :]
        x_in = self._initial_recon(sino_masked, mask)
        return unet_apply(params["unet"], x_in), None

    # -- loss / step ------------------------------------------------------- #
    def loss_fn(self, params, sino, mask, gt_vol):
        """Supervised MSE + masked data-consistency through the matched
        pair (+ completion loss for the hybrid model)."""
        cfg = self.cfg
        m4 = mask[:, :, None, None]
        pred, completed = self.predict(params, sino * m4, mask)
        loss = jnp.mean(jnp.square(pred - gt_vol))
        if cfg.dc_weight:
            dc = jnp.mean(jnp.square((self.proj(pred) - sino) * m4))
            loss = loss + cfg.dc_weight * dc
        if completed is not None:
            loss = loss + cfg.sino_weight * jnp.mean(
                jnp.square(completed - sino))
        return loss

    def _make_step(self):
        def _step(params, opt_state, ema, sino, mask, gt_vol):
            loss, grads = jax.value_and_grad(self.loss_fn)(
                params, sino, mask, gt_vol)
            if self._mesh is not None:
                grads = jax.lax.pmean(grads, "data")
                loss = jax.lax.pmean(loss, "data")
            updates, opt_state = self.opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            ema = ema_update(ema, params, decay=self.cfg.ema_decay,
                             warmup=self.cfg.ema_warmup)
            return params, opt_state, ema, loss

        if self._mesh is None:
            return jax.jit(_step)
        repl, shard = P(), P("data")
        return jax.jit(compat.shard_map(
            _step, self._mesh,
            in_specs=(repl, repl, repl, shard, shard, shard),
            out_specs=(repl, repl, repl, repl), check_vma=False))

    def _as_volume(self, imgs):
        a = jnp.asarray(imgs)
        return a if a.ndim == 4 else a[..., None]

    # -- public API -------------------------------------------------------- #
    def resume(self) -> int:
        """Restore params/opt/EMA + the data-pipeline cursor from the latest
        checkpoint under ``cfg.ckpt_dir``.  Returns the restored step (0
        when there is nothing to restore)."""
        cfg = self.cfg
        if not cfg.ckpt_dir or CKPT.latest_step(cfg.ckpt_dir) is None:
            return 0
        tree = (self.params, self.opt_state, self.ema)
        (self.params, self.opt_state, self.ema), extra, self.step = \
            CKPT.restore(cfg.ckpt_dir, tree)
        self.pipe.load_state_dict(extra["data"])
        return self.step

    def fit(self, log_every: int = 20, on_step=None):
        """Run the configured schedule (auto-resuming first); returns the
        per-step loss list.  ``on_step(i, loss)`` is an optional callback
        (progress reporting / benchmark timing)."""
        cfg = self.cfg
        start = self.resume()
        if self._step_fn is None:
            if cfg.data_parallel and jax.device_count() > 1:
                self._mesh = make_local_mesh()
                if cfg.batch % dp_size(self._mesh):
                    raise ValueError(
                        f"batch={cfg.batch} must divide over the "
                        f"{dp_size(self._mesh)}-way data axis")
            self._step_fn = self._make_step()
        ckpt = (CKPT.AsyncCheckpointer(cfg.ckpt_dir)
                if cfg.ckpt_dir else None)
        losses = []
        t0 = time.time()
        for i in range(start, cfg.steps):
            with obs.span("train.step", step=i):
                imgs, masks = self.pipe.batch(i)
                gt_vol = self._as_volume(imgs)
                sino = self.proj(gt_vol)
                self.params, self.opt_state, self.ema, loss = self._step_fn(
                    self.params, self.opt_state, self.ema, sino,
                    jnp.asarray(masks), gt_vol)
                loss = float(loss)
            losses.append(loss)
            self.step = i + 1
            if on_step is not None:
                on_step(i, loss)
            if log_every and i % log_every == 0:
                print(f"[{cfg.geometry}] step {i:4d}  loss {loss:.6f}  "
                      f"({(time.time() - t0) / max(i - start + 1, 1):.2f}"
                      f"s/step)")
            if ckpt and self.step % cfg.ckpt_every == 0:
                ckpt.save(self.step, (self.params, self.opt_state, self.ema),
                          {"data": self.pipe.state_dict()})
        if ckpt:
            ckpt.save(self.step, (self.params, self.opt_state, self.ema),
                      {"data": self.pipe.state_dict()})
            ckpt.wait()
        return losses

    def evaluate(self, n_test: int = 4, use_ema: bool = True,
                 params=None) -> dict:
        """Held-out phantoms through the full paper-§4 inference pipeline.

        Returns per-geometry quality numbers (means over ``n_test``):
        ``psnr_net``/``ssim_net`` for the raw network prediction,
        ``psnr_refined``/``ssim_refined`` after CG data-consistency
        refinement, and the relative projection residuals ``dc_net`` /
        ``dc_refined``.  Uses the EMA parameters by default — the weights a
        deployment would serve."""
        cfg = self.cfg
        if params is None:
            params = ema_params(self.ema) if use_ema else self.params
        acc = {k: 0.0 for k in ("psnr_net", "ssim_net", "psnr_refined",
                                "ssim_refined", "dc_net", "dc_refined")}
        for k in range(n_test):
            img, mask = self.pipe.sample(10_000 + k, 0)
            gt_vol = self._as_volume(img[None])[0]
            sino = self.proj(gt_vol)
            m3 = jnp.asarray(mask)[:, None, None]
            pred, _ = self.predict(params, (sino * m3)[None],
                                   jnp.asarray(mask)[None])
            pred = pred[0]
            xr, _ = complete_and_refine(self.proj, pred, sino, m3,
                                        n_iters=cfg.refine_iters,
                                        beta=cfg.refine_beta)
            gt_np, pred_np = np.asarray(gt_vol), np.asarray(pred)
            xr_np = np.asarray(xr)
            peak = float(gt_np.max())
            acc["psnr_net"] += psnr(pred_np, gt_np, peak)
            acc["ssim_net"] += ssim(pred_np, gt_np, peak)
            acc["psnr_refined"] += psnr(xr_np, gt_np, peak)
            acc["ssim_refined"] += ssim(xr_np, gt_np, peak)
            acc["dc_net"] += float(projection_residual(self.proj, pred,
                                                       sino, m3))
            acc["dc_refined"] += float(projection_residual(self.proj, xr,
                                                           sino, m3))
        return {k: v / n_test for k, v in acc.items()}


# --------------------------------------------------------------------------- #
# CLI — also the CI training-smoke entry point
# --------------------------------------------------------------------------- #
def _check_run(geometry: str, losses, metrics) -> list:
    """The training-smoke acceptance conditions; returns failure strings."""
    fails = []
    q = max(len(losses) // 4, 1)
    head, tail = float(np.mean(losses[:q])), float(np.mean(losses[-q:]))
    if not tail < head:
        fails.append(f"{geometry}: loss did not decrease "
                     f"(first-quarter mean {head:.6f} -> last-quarter "
                     f"mean {tail:.6f})")
    if not metrics["psnr_refined"] > metrics["psnr_net"]:
        fails.append(f"{geometry}: data-consistency refinement did not "
                     f"improve PSNR ({metrics['psnr_net']:.3f} dB -> "
                     f"{metrics['psnr_refined']:.3f} dB)")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--geometry", default="all",
                    choices=GEOMETRIES + ("all",))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU-trainable config (CI training-smoke)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--size", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--model", default=None, choices=_MODELS)
    ap.add_argument("--dc-weight", type=float, default=None)
    ap.add_argument("--compute-dtype", default=None)
    ap.add_argument("--data-parallel", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--n-test", type=int, default=4)
    ap.add_argument("--metrics-json", default=None,
                    help="write per-geometry losses+metrics as JSON")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless loss decreases and DC refinement "
                         "improves PSNR on held-out phantoms (CI gate)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    overrides = {}
    for field, name in (("steps", "steps"), ("n", "size"),
                        ("batch", "batch"), ("model", "model"),
                        ("dc_weight", "dc_weight"),
                        ("compute_dtype", "compute_dtype")):
        v = getattr(args, name)
        if v is not None:
            overrides[field] = v
    if args.data_parallel:
        overrides["data_parallel"] = True

    geometries = GEOMETRIES if args.geometry == "all" else (args.geometry,)
    results, failures = {}, []
    for geometry in geometries:
        per_geom = dict(overrides)
        if args.ckpt_dir:
            per_geom["ckpt_dir"] = f"{args.ckpt_dir}/{geometry}"
        cfg = (smoke_config(geometry, **per_geom) if args.smoke
               else TrainConfig(geometry=geometry, **per_geom))
        print(f"=== {geometry}: {cfg.resolved_model} model, "
              f"{cfg.steps} steps, vol {build_geometry(cfg).vol.shape} ===")
        trainer = CTTrainer(cfg)
        t0 = time.time()
        losses = trainer.fit()
        train_s = time.time() - t0
        metrics = trainer.evaluate(n_test=args.n_test)
        print(f"    loss {losses[0]:.6f} -> {losses[-1]:.6f}   "
              f"net {metrics['psnr_net']:.3f} dB -> refined "
              f"{metrics['psnr_refined']:.3f} dB   "
              f"dc {metrics['dc_net']:.4f} -> {metrics['dc_refined']:.4f}")
        results[geometry] = {"config": dataclasses.asdict(cfg),
                             "losses": losses, "train_seconds": train_s,
                             "metrics": metrics}
        if args.check:
            failures.extend(_check_run(geometry, losses, metrics))

    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {args.metrics_json}")
    for f in failures:
        print(f"FAIL: {f}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
