#!/usr/bin/env python3
"""Bring-up smoke test: the projector stack's main path on TPU.

Runs in one process from the root of a checkout (it puts ``src`` on
``sys.path`` itself) and refuses to run anywhere but on a TPU:

    python chip_smoke.py             # one chip: phases table1 .. serve
    python chip_smoke.py --chips 4   # four chips: the sharded phase only

One chip, each through the user-facing entry points (``ProjectorSpec`` ->
``kernels.ops`` -> the Pallas pair, ``CTTrainer``, ``CTServer``):

  table1       Table-1 FP and BP at 512^3/180 for the parallel, exact cone
               and packed cone pairs, plus one bf16 run: each against the
               ``kernels/ref.py`` oracle in f32 and a full-size dot test.
  fan_helical  FP and BP of the fan and helical training geometries
               (``launch/ct_train.py``, n=512, nz=64 for helical).
  train        ``CTTrainer``, limited angle, n=512, batch 8, 3 steps.
  serve        ``CTServer(max_batch=8)``: 8 fbp + 4 sirt requests at
               512^2/720.

Four chips (``--chips 4``):

  sharded      ``DistributedProjector`` with 4 z-slabs on the 512^3 cone
               geometry against the one-chip pair, and the parallel
               1024^3/720 Table-1 cell (which one chip cannot hold) with a
               dot test and 3 sirt iterations.

The oracles are the repo's jnp reference projectors.  They scan over views,
so at full volume and detector size each is run on a few views of the
geometry (``ORACLE_VIEWS``): a view's projection, and the backprojection of
a sinogram that is zero outside those views, depend on those views only.
The cone oracles do not fit the chip's memory at 512^3, so both cone pairs
are also run at ``CONE_ORACLE_N``^3, built the Table-1 way, and compared
there.  The exact cone and helical kernels run on a stride of their views
(``VIEW_STRIDE``): volume and detector keep their full size, and each
phase line says how many views ran.

Every check prints one line with shapes, the kernel config, compile and
run seconds and its errors against their tolerances.  The last line of
standard output is one JSON object naming the device; a failed phase
prints no such line and the script exits 1.  Kernel configs come from the
committed heuristics only (the autotuner's disk cache is ignored).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent

# Views each oracle comparison uses, per geometry (a few, spread over the
# scan so both of the kernels' view groups are covered).
ORACLE_VIEWS = 4
# Every VIEW_STRIDE-th view of these geometries runs on the kernels: the
# full view counts would take most of the smoke's time budget (the exact
# cone FP and BP at 512^3 take ~0.5 s per view on a v5e).
VIEW_STRIDE = {"cone": 18, "helical": 4}
# The cone oracles' adjoint on ORACLE_VIEWS views needs 9.4 GB at 256^3 and
# more than the chip's 16 GB from 320^3 up: both cone pairs are compared at
# this size, built the Table-1 way (same ratios).
CONE_ORACLE_N = 256
N = 512                 # transaxial size of the training/serving phases
HELICAL_NZ = 64
EXACT_TOL = 2e-4        # Pallas vs oracle, f32 (max-abs / max-abs)
DOT_TOL = 1e-4          # |<Ax, y> - <x, A'y>| / |<Ax, y>|, f32
SEED = 0
KERNEL_MARK = "tpu_custom_call"     # what a compiled Pallas TPU kernel shows


def _fail(msg: str, code: int = 2):
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(code)


def _say(phase: str, **fields):
    parts = [f"{k}={v}" for k, v in fields.items()]
    print(f"[{phase}] " + " ".join(parts), flush=True)


def _progress(msg: str):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------- #
# Measuring helpers
# --------------------------------------------------------------------------- #
def _compile(fn, *args):
    """AOT-compile ``fn`` for ``args``; returns (compiled, seconds).  Fails
    unless the program holds a Pallas TPU kernel."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    dt = time.perf_counter() - t0
    if KERNEL_MARK not in compiled.as_text():
        raise AssertionError("compiled program holds no Pallas TPU kernel")
    return compiled, dt


def _run(compiled, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def _rel(a, b) -> float:
    """Max-abs error relative to the reference's max-abs."""
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _dot_rel(s, y, f, b) -> float:
    import jax.numpy as jnp
    lhs, rhs = jnp.vdot(s, y), jnp.vdot(f, b)
    return float(jnp.abs(lhs - rhs) / jnp.abs(lhs))


def _views(n_angles: int, k: int = ORACLE_VIEWS):
    import numpy as np
    return np.arange(k) * n_angles // k + n_angles // (2 * k)


def _fmt(x: float) -> str:
    return f"{x:.3g}"


class _Check:
    """Collects the pass/fail of one phase's comparisons."""

    def __init__(self, phase: str):
        self.phase, self.failed = phase, []

    def within(self, label: str, what: str, value: float, tol: float) -> str:
        ok = value == value and value <= tol          # NaN fails
        if not ok:
            self.failed.append(f"{label} {what}={value!r} > {tol!r}")
        return f"{_fmt(value)}<={_fmt(tol)}" + ("" if ok else "!FAIL")

    def done(self):
        if self.failed:
            raise AssertionError("; ".join(self.failed))


# --------------------------------------------------------------------------- #
# One matched pair against its oracle
# --------------------------------------------------------------------------- #
def _inputs(geom, seed: int = SEED):
    """A volume and a sinogram, uniform in [0, 1): non-negative like
    attenuation and line integrals, so the dot test's inner products have
    no cancellation to hide a mismatch behind."""
    import jax
    kf, ky = jax.random.split(jax.random.PRNGKey(seed))
    f = jax.random.uniform(kf, geom.vol.shape, "float32")
    y = jax.random.uniform(ky, geom.sino_shape, "float32")
    return jax.block_until_ready((f, y))


def _oracle(fp_ref, bp_ref, geom, f, y):
    """A zero-argument callable that computes (once) the oracle results on
    ``ORACLE_VIEWS`` views of ``geom``: the FP of ``f`` and the BP of ``y``
    restricted to those views.  Returns (views, s_ref, b_ref, seconds)."""
    import jax
    memo = []

    def run():
        if not memo:
            idx = _views(geom.n_angles)
            t0 = time.perf_counter()
            sub = geom.subset(idx)
            s_ref = jax.jit(lambda x: fp_ref(x, sub))(f)
            b_ref = jax.jit(lambda p: bp_ref(p, sub))(y[idx])
            jax.block_until_ready((s_ref, b_ref))
            dt = time.perf_counter() - t0
            _progress(f"oracle on {len(idx)} views of {geom.geom_type} "
                      f"{geom.vol.shape}: {dt:.1f}s")
            memo.append((idx, s_ref, b_ref, dt))
        return memo[0]
    return run


def check_pair(chk: _Check, label: str, spec, f, y, refs=None,
               tol: float = EXACT_TOL, dot_tol: float = DOT_TOL,
               note: str = ""):
    """Compile and run the spec's FP and BP through ``kernels.ops`` and
    dot-test the pair.  With ``refs`` (from :func:`_oracle`), also compare
    the FP on the oracle's views, and the BP of ``y`` restricted to them,
    with the oracle.  Returns the FP output."""
    import jax.numpy as jnp
    from repro.kernels import ops, precision, tune
    geom = spec.geom
    backend = ops.resolve_backend(spec)
    if backend != "pallas":
        raise AssertionError(f"{label}: resolved to backend {backend!r}")
    mode = ops.resolve_mode(spec)
    cdt = precision.resolve(spec.compute_dtype, f.dtype)
    cfg = tune.get_config(geom, 1, cdt, packed=mode == "packed")
    fp_c, t_fc = _compile(lambda x: ops.forward_project(x, spec), f)
    bp_c, t_bc = _compile(lambda p: ops.back_project(p, spec), y)
    _progress(f"{label}: compiled fp {t_fc:.1f}s bp {t_bc:.1f}s")
    s, t_fr = _run(fp_c, f)
    b, t_br = _run(bp_c, y)
    _progress(f"{label}: ran fp {t_fr:.2f}s bp {t_br:.2f}s")
    dot = _dot_rel(s, y, f, b)
    fields = {}
    if refs is not None:
        idx, s_ref, b_ref, t_or = refs()
        bm, _ = _run(bp_c, jnp.zeros_like(y).at[idx].set(y[idx]))
        fields = dict(
            oracle_views=",".join(str(int(i)) for i in idx),
            oracle_s=f"{t_or:.1f}",
            err_fp=chk.within(label, "err_fp", _rel(s[idx], s_ref), tol),
            err_bp=chk.within(label, "err_bp", _rel(bm, b_ref), tol))
    _say(chk.phase, pair=label, vol=geom.vol.shape, sino=geom.sino_shape,
         mode=mode, dtype=cdt.name, cfg=cfg,
         compile_s=f"fp:{t_fc:.2f},bp:{t_bc:.2f}",
         run_s=f"fp:{t_fr:.3f},bp:{t_br:.3f}", **fields,
         dot=chk.within(label, "dot", dot, dot_tol),
         **({"note": note} if note else {}))
    return s


# --------------------------------------------------------------------------- #
# Phases
# --------------------------------------------------------------------------- #
def phase_table1():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.leap_ct import table1_cone, table1_parallel
    from repro.core.spec import ProjectorSpec
    from repro.kernels import fp_cone, ops, precision, ref
    chk = _Check("table1")
    fp_sf = lambda x, g: ref.forward(x, g, "sf")          # noqa: E731
    bp_sf = lambda p, g: ref.adjoint(p, g, "sf")          # noqa: E731

    par = table1_parallel(512, 180)
    f, y = _inputs(par)
    refs = _oracle(fp_sf, bp_sf, par, f, y)
    check_pair(chk, "parallel", ProjectorSpec(par), f, y, refs)
    check_pair(chk, "parallel-bf16",
               ProjectorSpec(par, compute_dtype="bfloat16"), f, y, refs,
               tol=precision.BF16_FP_REL_BOUND,
               dot_tol=precision.BF16_DOT_TOL)
    del f, y, refs

    stride = VIEW_STRIDE["cone"]
    for n in (512, CONE_ORACLE_N):
        full = table1_cone(n, 180)
        cone = full.subset(np.arange(0, full.n_angles, stride))
        f, y = _inputs(cone)
        note = f"views={cone.n_angles}/{full.n_angles}"
        exact = packed = None
        if n == CONE_ORACLE_N:
            note += f",oracle_at={n}^3:512^3_oracle_exceeds_HBM"
            exact = _oracle(fp_sf, bp_sf, cone, f, y)
            packed = _oracle(fp_cone.fp_cone_packed_ref,
                             fp_cone.bp_cone_packed_ref, cone, f, y)
        auto = ops.resolve_mode(ProjectorSpec(cone))
        s_exact = check_pair(chk, "cone-exact",
                             ProjectorSpec(cone, mode="exact"), f, y, exact,
                             note=f"{note},auto_mode={auto}")
        s_packed = check_pair(chk, "cone-packed",
                              ProjectorSpec(cone, mode="packed"), f, y,
                              packed, note=note)
        # The packed pair approximates the exact one within its derived
        # bound (relative L2 over the sinogram).
        l2 = float(jnp.linalg.norm(s_packed - s_exact)
                   / jnp.linalg.norm(s_exact))
        _say(chk.phase, pair="cone-packed-vs-exact", vol=cone.vol.shape,
             rel_l2=chk.within("cone-packed", "rel_l2", l2,
                               fp_cone.cone_packed_error_bound(cone)),
             row_shift=_fmt(fp_cone.cone_packed_row_shift(cone)))
        del f, y, s_exact, s_packed, exact, packed
    jax.clear_caches()
    chk.done()


def phase_fan_helical():
    import jax
    import numpy as np
    from repro.core.spec import ProjectorSpec
    from repro.kernels import fp_modular, ref
    from repro.launch.ct_train import TrainConfig, build_geometry
    chk = _Check("fan_helical")
    fan = build_geometry(TrainConfig(geometry="sparse_fan", n=N))
    f, y = _inputs(fan)
    check_pair(chk, "fan", ProjectorSpec(fan), f, y,
               _oracle(lambda x, g: ref.forward(x, g, "sf"),
                       lambda p, g: ref.adjoint(p, g, "sf"), fan, f, y))
    del f, y

    full = build_geometry(TrainConfig(geometry="helical", n=N,
                                      nz=HELICAL_NZ))
    hel = full.subset(np.arange(0, full.n_angles, VIEW_STRIDE["helical"]))
    f, y = _inputs(hel)
    check_pair(chk, "helical", ProjectorSpec(hel), f, y,
               _oracle(fp_modular.fp_modular_sf_ref,
                       fp_modular.bp_modular_sf_ref, hel, f, y),
               note=f"views={hel.n_angles}/{full.n_angles}")
    jax.clear_caches()
    chk.done()


def phase_train():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.launch.ct_train import CTTrainer, TrainConfig
    cfg = TrainConfig(geometry="limited_angle", n=N, batch=8, steps=3)
    tr = CTTrainer(cfg)
    backend = ops.resolve_backend(tr.spec)
    if backend != "pallas":
        raise AssertionError(f"trainer projector resolved to {backend!r}")
    imgs, masks = tr.pipe.batch(0)
    gt = tr._as_volume(imgs)
    jaxpr = str(jax.make_jaxpr(jax.grad(tr.loss_fn))(
        tr.params, tr.proj(gt), jnp.asarray(masks), gt))
    if "pallas_call" not in jaxpr:
        raise AssertionError("train step holds no Pallas kernel")
    marks = [time.perf_counter()]
    losses = tr.fit(log_every=0,
                    on_step=lambda i, loss: marks.append(time.perf_counter()))
    steps = np.diff(marks)
    if not (len(losses) == cfg.steps and np.all(np.isfinite(losses))):
        raise AssertionError(f"losses {losses}")
    _say("train", geometry=cfg.geometry, model=cfg.resolved_model,
         vol=tr.geom.vol.shape, sino=tr.geom.sino_shape, batch=cfg.batch,
         backend=backend, mode=ops.resolve_mode(tr.spec),
         first_step_s=f"{steps[0]:.2f}",
         later_steps_s=",".join(f"{s:.3f}" for s in steps[1:]),
         losses=",".join(f"{v:.6g}" for v in losses))
    jax.clear_caches()


def phase_serve():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.leap_ct import limited_angle_geometry
    from repro.core.projector import Projector
    from repro.core.spec import ProjectorSpec
    from repro.data.phantoms import random_ellipse_phantom
    from repro.kernels import ops
    from repro.launch.ct_serve import CTServer, ReconRequest
    geom = limited_angle_geometry()
    spec = ProjectorSpec(geom)
    backend = ops.resolve_backend(spec)
    if backend != "pallas":
        raise AssertionError(f"serving projector resolved to {backend!r}")
    imgs = np.stack([random_ellipse_phantom(SEED + i, geom.vol)[0]
                     for i in range(8)]) * 0.02
    sinos = np.asarray(Projector(spec)(jnp.asarray(imgs)[..., None]))
    srv = CTServer(max_batch=8)
    rids = [srv.submit(ReconRequest(spec=spec, sino=sinos[i], solver="fbp"))
            for i in range(8)]
    rids += [srv.submit(ReconRequest(spec=spec, sino=sinos[i],
                                     solver="sirt",
                                     solver_kwargs={"n_iters": 10}))
             for i in range(4)]
    t0 = time.perf_counter()
    done = srv.drain()
    wall = time.perf_counter() - t0
    bad = [(r, done[r].error) for r in rids if not done[r].ok]
    if bad:
        raise AssertionError(f"failed responses: {bad}")
    reruns = [rec for rec in srv.dispatch_log
              if rec["rerun"] or rec["error"]]
    if reruns:
        raise AssertionError(f"per-request fallback ran: {reruns}")
    sizes = sorted((rec["solver"], rec["size_class"], len(rec["rids"]))
                   for rec in srv.dispatch_log)
    if sizes != [("fbp", 8, 8), ("sirt", 4, 4)]:
        raise AssertionError(f"unexpected dispatches {sizes}")
    err = []
    for i, r in enumerate(rids):
        img = np.asarray(done[r].image)[..., 0]
        if not np.all(np.isfinite(img)):
            raise AssertionError(f"request {r}: non-finite image")
        truth = imgs[i % 8]
        err.append(np.linalg.norm(img - truth) / np.linalg.norm(truth))
        hist = np.asarray(done[r].result.residual_history)
        if hist.size and not hist[-1] < hist[0]:
            raise AssertionError(f"request {r}: sirt residual {hist}")
    _say("serve", vol=geom.vol.shape, sino=geom.sino_shape, backend=backend,
         dispatches=";".join(f"{rec['solver']}x{len(rec['rids'])}"
                             f"@{rec['wall_s']:.2f}s"
                             for rec in srv.dispatch_log),
         drain_s=f"{wall:.2f}",
         fbp_rel_l2=_fmt(max(err[:8])), sirt_rel_l2=_fmt(max(err[8:])))
    jax.clear_caches()


def phase_sharded():
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat
    from repro.configs.leap_ct import table1_cone, table1_parallel
    from repro.core.distributed import distribute
    from repro.core.spec import ProjectorSpec
    from repro.kernels import ops
    from repro.recon.sirt import sirt
    chk = _Check("sharded")
    devs = jax.devices()[:4]
    if len(devs) < 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = compat.make_mesh((1, 4), ("data", "model"), devices=devs)

    def placement(x, axis):
        """Device id and first index along the sharded axis, per shard."""
        return ",".join(f"dev{s.device.id}@{s.index[axis].start or 0}"
                        for s in x.addressable_shards)

    full = table1_cone(512, 180)
    cone = full.subset(np.arange(0, full.n_angles,
                                 VIEW_STRIDE["cone"]))
    spec = ProjectorSpec(cone, mode="exact")
    dp = distribute(spec, mesh, z_axis="model")
    f, y = _inputs(cone)
    one = jax.devices()[0]
    s1 = jax.jit(lambda x: ops.forward_project(x, spec))(
        jax.device_put(f, one))
    b1 = jax.jit(lambda p: ops.back_project(p, spec))(jax.device_put(y, one))
    fs, ys = dp.shard_volume(f), dp.shard_sino(y)
    fp_c, t_fc = _compile(dp.fp, fs)
    bp_c, t_bc = _compile(dp.bp, ys)
    s, t_fr = _run(fp_c, fs)
    b, t_br = _run(bp_c, ys)
    _say("sharded", pair="cone-z4", vol=cone.vol.shape, sino=cone.sino_shape,
         halo=dp.shard.halo, views=f"{cone.n_angles}/{full.n_angles}",
         compile_s=f"fp:{t_fc:.2f},bp:{t_bc:.2f}",
         run_s=f"fp:{t_fr:.3f},bp:{t_br:.3f}",
         vol_shards=placement(b, 2),
         err_fp_vs_1chip=chk.within("cone-z4", "err_fp", _rel(
             jax.device_put(s, one), s1), EXACT_TOL),
         err_bp_vs_1chip=chk.within("cone-z4", "err_bp", _rel(
             jax.device_put(b, one), b1), EXACT_TOL),
         dot=chk.within("cone-z4", "dot",
                        _dot_rel(s, ys, fs, b), DOT_TOL))
    del f, y, s1, b1, fs, ys, s, b

    par = table1_parallel(1024, 720)
    dp = distribute(ProjectorSpec(par), mesh, z_axis="model")
    kf, ky = jax.random.split(jax.random.PRNGKey(SEED))
    # Made in place on the mesh: one chip cannot hold the whole volume and
    # sinogram next to the solver's work arrays.
    vol_sh = NamedSharding(mesh, P(None, None, "model"))
    sino_sh = NamedSharding(mesh, P("data", "model", None))
    f = jax.jit(lambda k: jax.random.uniform(k, par.vol.shape),
                out_shardings=vol_sh)(kf)
    y = jax.jit(lambda k: jax.random.uniform(k, par.sino_shape),
                out_shardings=sino_sh)(ky)
    fp_c, t_fc = _compile(dp.fp, f)
    bp_c, t_bc = _compile(dp.bp, y)
    s, t_fr = _run(fp_c, f)
    b, t_br = _run(bp_c, y)
    dot = _dot_rel(s, y, f, b)
    t0 = time.perf_counter()
    res = jax.block_until_ready(jax.jit(lambda p: sirt(dp, p, n_iters=3))(s))
    t_sirt = time.perf_counter() - t0
    hist = np.asarray(res.residual_history)
    if not (np.all(np.isfinite(hist)) and hist[-1] < hist[0]):
        chk.failed.append(f"sirt residual history {hist}")
    _say("sharded", pair="parallel-1024-z4", vol=par.vol.shape,
         sino=par.sino_shape, compile_s=f"fp:{t_fc:.2f},bp:{t_bc:.2f}",
         run_s=f"fp:{t_fr:.3f},bp:{t_br:.3f}", sino_shards=placement(s, 1),
         vol_shards=placement(b, 2),
         dot=chk.within("parallel-1024-z4", "dot", dot, DOT_TOL),
         sirt3_s=f"{t_sirt:.2f}",
         sirt_residual=",".join(_fmt(h) for h in hist),
         sirt_image_shards=placement(res.image, 2))
    chk.done()


# (name, phase, compares with an oracle: runs under "highest" XLA matmul
# precision so the oracle is f32-exact)
PHASES_1 = [("table1", phase_table1, True),
            ("fan_helical", phase_fan_helical, True),
            ("train", phase_train, False), ("serve", phase_serve, False)]
PHASES_4 = [("sharded", phase_sharded, True)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip phase")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no src/repro beside {pathlib.Path(__file__).name}: run it "
              f"from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    # Kernel configs from committed code only: no autotune sweep, no
    # persisted measurements from an earlier run.
    os.environ["REPRO_TUNE_CACHE"] = "0"
    os.environ["REPRO_AUTOTUNE"] = "0"
    import jax
    from repro.runtime import compile_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} devices, JAX found "
              f"{len(devices)}")
    cache = compile_cache.enable()
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, "
          f"jax {jax.__version__}, compile cache {cache}", flush=True)

    failed = []
    for name, phase, highest in (PHASES_4 if args.chips == 4 else PHASES_1):
        t0 = time.perf_counter()
        try:
            with jax.default_matmul_precision("highest" if highest
                                              else None):
                phase()
        except Exception as e:                        # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
            _say(name, status="FAILED",
                 error=f"{type(e).__name__}: {e}".splitlines()[0][:300])
        else:
            _say(name, status="ok",
                 seconds=f"{time.perf_counter() - t0:.1f}")
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
