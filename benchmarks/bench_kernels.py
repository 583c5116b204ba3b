"""Kernel microbenchmark: Pallas (interpret on CPU) vs pure-jnp oracle at
matched shapes, plus the jnp backend at production-ish 2D sizes.  On real
TPU the pallas path is the production backend; interpret-mode timing is a
correctness artifact, not a perf number — flagged in `derived`.

From the lane-packing PR onward this also records, on every runner:

* backprojection and forward+VJP (gradient) timings,
* the paper's flagship batched 2D training shape (nz=1, n_rows=1, batch>=8)
  on BOTH the seed per-sample vmap path and the lane-packed batched path,
  sweeping view-block configs — so the lane-packing win (up to 128x lane
  occupancy) is tracked in BENCH_*.json across PRs.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ProjectorSpec, VolumeGeometry, cone_beam, fan_beam,
                        helical_beam, parallel_beam)
from repro.kernels import ref
from repro.kernels.fp_cone import (bp_cone_packed, bp_cone_sf_pallas,
                                   cone_packed_row_shift, fp_cone_packed,
                                   fp_cone_sf_pallas)
from repro.kernels.fp_fan import bp_fan_sf_pallas, fp_fan_sf_pallas
from repro.kernels.fp_modular import (bp_modular_sf_pallas,
                                      fp_modular_sf_pallas,
                                      fp_modular_sf_ref)
from repro.kernels.fp_par import bp_parallel_sf_pallas, fp_parallel_sf_pallas
from repro.kernels.tune import KernelConfig


def _t(fn, *a, reps=2):
    out = fn(*a)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(csv_rows: list):
    on_tpu = jax.default_backend() == "tpu"
    mode = "tpu" if on_tpu else "interpret-mode(correctness-only)"
    reps = 5 if on_tpu else 1

    # ---- 3D kernel shape: fp / bp / grad, oracle vs pallas --------------- #
    # Interpret mode executes one Python step per grid point — use a small
    # grid off-TPU so the suite stays inside the harness budget.
    if on_tpu:
        vol = VolumeGeometry(64, 64, 8)
        g = parallel_beam(24, 8, 96, vol)
    else:
        vol = VolumeGeometry(32, 32, 4)
        g = parallel_beam(12, 4, 48, vol)
    f = jnp.asarray(np.random.default_rng(0).normal(
        size=vol.shape).astype(np.float32))
    y = jnp.asarray(np.random.default_rng(1).normal(
        size=g.sino_shape).astype(np.float32))
    t_ref = _t(jax.jit(lambda x: ref.forward(x, g, "sf")), f)
    csv_rows.append(("kernel/fp_par_sf/jnp_oracle", t_ref * 1e6, "cpu-jit"))
    t_bp_ref = _t(jax.jit(lambda p: ref.adjoint(p, g, "sf")), y)
    csv_rows.append(("kernel/bp_par_sf/jnp_oracle", t_bp_ref * 1e6, "cpu-jit"))
    t_pal = _t(lambda x: fp_parallel_sf_pallas(x, g), f, reps=reps)
    csv_rows.append(("kernel/fp_par_sf/pallas", t_pal * 1e6, mode))
    t_bp = _t(lambda p: bp_parallel_sf_pallas(p, g), y, reps=reps)
    csv_rows.append(("kernel/bp_par_sf/pallas", t_bp * 1e6, mode))

    # view-block sweep (the ba knob the autotuner searches)
    for ba in (1, 4):
        t = _t(lambda x: fp_parallel_sf_pallas(
            x, g, config=KernelConfig(ba=ba)), f, reps=reps)
        csv_rows.append((f"kernel/fp_par_sf/pallas_ba{ba}", t * 1e6, mode))

    # mixed precision: bf16 tiles / f32 accumulate.  check_regression pairs
    # every `_bf16` row with its f32 sibling (suffix stripped) and, on TPU,
    # requires the bf16 variant to win on the batched BP rows below.
    t = _t(lambda x: fp_parallel_sf_pallas(x, g, compute_dtype="bfloat16"),
           f, reps=reps)
    csv_rows.append(("kernel/fp_par_sf/pallas_bf16", t * 1e6,
                     f"{mode};speedup_vs_f32={t_pal / max(t, 1e-12):.2f}x"))
    t = _t(lambda p: bp_parallel_sf_pallas(p, g, compute_dtype="bfloat16"),
           y, reps=reps)
    csv_rows.append(("kernel/bp_par_sf/pallas_bf16", t * 1e6,
                     f"{mode};speedup_vs_f32={t_bp / max(t, 1e-12):.2f}x"))
    # BP stripe reuse (the bs knob): one sinogram stripe stays resident in
    # VMEM across bs gathered-axis output tiles instead of being re-fetched.
    t = _t(lambda p: bp_parallel_sf_pallas(p, g, bs=4), y, reps=reps)
    csv_rows.append(("kernel/bp_par_sf/pallas_bs4", t * 1e6,
                     f"{mode};speedup_vs_bs1={t_bp / max(t, 1e-12):.2f}x"))

    # ---- batched 2D training shape: seed vmap path vs lane packing ------- #
    # The paper's limited-angle DL regime: thin-z volume, single detector
    # row, per-step training batch.  This is where lane packing turns
    # 1/128 lane occupancy into full tiles.
    B = 8
    if on_tpu:
        vol2 = VolumeGeometry(128, 128, 1)
        g2 = parallel_beam(90, 1, 192, vol2)
    else:
        vol2 = VolumeGeometry(32, 32, 1)
        g2 = parallel_beam(12, 1, 48, vol2)
    fb = jnp.asarray(np.random.default_rng(2).normal(
        size=(B,) + vol2.shape).astype(np.float32))
    yb = jnp.asarray(np.random.default_rng(3).normal(
        size=(B,) + g2.sino_shape).astype(np.float32))

    t_vmap = _t(lambda x: jax.vmap(
        lambda s: fp_parallel_sf_pallas(s, g2))(x), fb, reps=reps)
    csv_rows.append((f"kernel/fp2d_b{B}/pallas_vmap_seed", t_vmap * 1e6, mode))
    t_pack = _t(lambda x: fp_parallel_sf_pallas(x, g2), fb, reps=reps)
    csv_rows.append((f"kernel/fp2d_b{B}/pallas_lane_packed", t_pack * 1e6,
                     f"{mode};speedup_vs_vmap={t_vmap / max(t_pack, 1e-12):.2f}x"))

    # batched BP at the same training shape: the memory-bound row the
    # mixed-precision tentpole targets.  f32 lane-packed is the baseline;
    # the `_bf16` sibling adds bf16 tiles AND bs=4 stripe reuse — the
    # acceptance row for the >=1.5x batched-BP speedup (gated on TPU by
    # check_regression's dtype-sibling pass).
    t_bp_pack = _t(lambda p: bp_parallel_sf_pallas(p, g2), yb, reps=reps)
    csv_rows.append((f"kernel/bp2d_b{B}/pallas_lane_packed",
                     t_bp_pack * 1e6, mode))
    t_bp_mp = _t(lambda p: bp_parallel_sf_pallas(
        p, g2, bs=4, compute_dtype="bfloat16"), yb, reps=reps)
    csv_rows.append((f"kernel/bp2d_b{B}/pallas_lane_packed_bf16",
                     t_bp_mp * 1e6,
                     f"{mode};speedup_vs_f32="
                     f"{t_bp_pack / max(t_bp_mp, 1e-12):.2f}x"))

    # forward + VJP (one training step's projector work), both batch paths.
    # Gradients route through the registered matched pair (custom_vjp), so
    # the VJP is the backprojection kernel, not autodiff through pallas_call.
    from repro.kernels import ops

    spec2 = ProjectorSpec(g2, "sf", backend="pallas")

    def loss_ops(x):
        p = ops.forward_project(x, spec2)
        return 0.5 * jnp.sum((p - yb) ** 2)

    t_grad_vmap = _t(lambda x: jax.grad(
        lambda z: 0.5 * jnp.sum(
            (jax.vmap(lambda s: ops.forward_project(
                s, spec2))(z) - yb) ** 2))(x),
        fb, reps=reps)
    csv_rows.append((f"kernel/grad2d_b{B}/pallas_vmap_seed",
                     t_grad_vmap * 1e6, mode))
    t_grad_pack = _t(lambda x: jax.grad(loss_ops)(x), fb, reps=reps)
    csv_rows.append((f"kernel/grad2d_b{B}/pallas_lane_packed",
                     t_grad_pack * 1e6,
                     f"{mode};speedup_vs_vmap="
                     f"{t_grad_vmap / max(t_grad_pack, 1e-12):.2f}x"))

    # ---- fan beam: pallas FP/BP vs oracle, plus the lane-packed batch ---- #
    if on_tpu:
        volf = VolumeGeometry(64, 64, 8)
        gf = fan_beam(24, 8, 96, volf, sod=150.0, sdd=300.0, pixel_width=2.0)
    else:
        volf = VolumeGeometry(32, 32, 4)
        gf = fan_beam(12, 4, 48, volf, sod=80.0, sdd=160.0, pixel_width=2.0)
    ff = jnp.asarray(np.random.default_rng(5).normal(
        size=volf.shape).astype(np.float32))
    yf = jnp.asarray(np.random.default_rng(6).normal(
        size=gf.sino_shape).astype(np.float32))
    t = _t(jax.jit(lambda x: ref.forward(x, gf, "sf")), ff)
    csv_rows.append(("kernel/fp_fan_sf/jnp_oracle", t * 1e6, "cpu-jit"))
    t_fpf = _t(lambda x: fp_fan_sf_pallas(x, gf), ff, reps=reps)
    csv_rows.append(("kernel/fp_fan_sf/pallas", t_fpf * 1e6, mode))
    t_bpf = _t(lambda p: bp_fan_sf_pallas(p, gf), yf, reps=reps)
    csv_rows.append(("kernel/bp_fan_sf/pallas", t_bpf * 1e6, mode))
    t = _t(lambda x: fp_fan_sf_pallas(x, gf, compute_dtype="bfloat16"),
           ff, reps=reps)
    csv_rows.append(("kernel/fp_fan_sf/pallas_bf16", t * 1e6,
                     f"{mode};speedup_vs_f32={t_fpf / max(t, 1e-12):.2f}x"))
    t = _t(lambda p: bp_fan_sf_pallas(p, gf, compute_dtype="bfloat16"),
           yf, reps=reps)
    csv_rows.append(("kernel/bp_fan_sf/pallas_bf16", t * 1e6,
                     f"{mode};speedup_vs_f32={t_bpf / max(t, 1e-12):.2f}x"))

    # thin-z lane-packed fan batch (seed vmap path vs packed path)
    gf2 = fan_beam(g2.n_angles, 1, g2.n_cols, vol2,
                   sod=4.0 * vol2.radius, sdd=8.0 * vol2.radius,
                   pixel_width=2.0)
    t_vmapf = _t(lambda x: jax.vmap(
        lambda s: fp_fan_sf_pallas(s, gf2))(x), fb, reps=reps)
    csv_rows.append((f"kernel/fp_fan2d_b{B}/pallas_vmap", t_vmapf * 1e6, mode))
    t_packf = _t(lambda x: fp_fan_sf_pallas(x, gf2), fb, reps=reps)
    csv_rows.append((f"kernel/fp_fan2d_b{B}/pallas_lane_packed", t_packf * 1e6,
                     f"{mode};speedup_vs_vmap="
                     f"{t_vmapf / max(t_packf, 1e-12):.2f}x"))

    # ---- cone beam: the Pallas FP/BP matched pair ------------------------ #
    # The BP is the exact transpose of the FP (transposed transaxial
    # contraction + per-element axial matvec in the adjoint direction); the
    # bp_over_fp ratio is the number the CI regression gate tracks.
    if on_tpu:
        volc = VolumeGeometry(64, 64, 16)
        gc = cone_beam(24, 16, 96, volc, sod=150.0, sdd=300.0,
                       pixel_width=2.0, pixel_height=2.0)
    else:
        volc = VolumeGeometry(16, 16, 8)
        gc = cone_beam(4, 8, 24, volc, sod=80.0, sdd=160.0,
                       pixel_width=2.0, pixel_height=2.0)
    fc = jnp.asarray(np.random.default_rng(7).normal(
        size=volc.shape).astype(np.float32))
    yc = jnp.asarray(np.random.default_rng(8).normal(
        size=gc.sino_shape).astype(np.float32))
    t = _t(jax.jit(lambda x: ref.forward(x, gc, "sf")), fc)
    csv_rows.append(("kernel/fp_cone_sf/jnp_oracle", t * 1e6, "cpu-jit"))
    t = _t(jax.jit(lambda p: ref.adjoint(p, gc, "sf")), yc)
    csv_rows.append(("kernel/bp_cone_sf/jnp_oracle", t * 1e6, "cpu-jit"))
    t_fpc = _t(lambda x: fp_cone_sf_pallas(x, gc), fc, reps=reps)
    csv_rows.append(("kernel/fp_cone_sf/pallas", t_fpc * 1e6, mode))
    t_bpc = _t(lambda p: bp_cone_sf_pallas(p, gc), yc, reps=reps)
    csv_rows.append(("kernel/bp_cone_sf/pallas", t_bpc * 1e6,
                     f"{mode};bp_over_fp={t_bpc / max(t_fpc, 1e-12):.2f}x"))
    t = _t(lambda x: fp_cone_sf_pallas(x, gc, compute_dtype="bfloat16"),
           fc, reps=reps)
    csv_rows.append(("kernel/fp_cone_sf/pallas_bf16", t * 1e6,
                     f"{mode};speedup_vs_f32={t_fpc / max(t, 1e-12):.2f}x"))
    t = _t(lambda p: bp_cone_sf_pallas(p, gc, compute_dtype="bfloat16"),
           yc, reps=reps)
    csv_rows.append(("kernel/bp_cone_sf/pallas_bf16", t * 1e6,
                     f"{mode};speedup_vs_f32={t_bpc / max(t, 1e-12):.2f}x"))

    # ---- modular beam (helical): the Pallas SF matched pair -------------- #
    # The modular pair is the cone pair generalized to per-view frames
    # (scalar-prefetched 24-float rows); a helical trajectory is the
    # canonical workload no fixed-geometry kernel can express.  Both rows
    # are gated by check_regression (and grepped by benchmarks-smoke).
    if on_tpu:
        volm = VolumeGeometry(64, 64, 16)
        gm = helical_beam(1.0, 16.0, 24, 16, 96, volm, sod=150.0, sdd=300.0,
                          pixel_width=2.0, pixel_height=2.0)
    else:
        volm = VolumeGeometry(16, 16, 8)
        gm = helical_beam(1.0, 8.0, 4, 8, 24, volm, sod=80.0, sdd=160.0,
                          pixel_width=2.0, pixel_height=2.0)
    fm = jnp.asarray(np.random.default_rng(11).normal(
        size=volm.shape).astype(np.float32))
    ym = jnp.asarray(np.random.default_rng(12).normal(
        size=gm.sino_shape).astype(np.float32))
    t = _t(jax.jit(lambda x: fp_modular_sf_ref(x, gm)), fm)
    csv_rows.append(("kernel/fp_modular_sf/jnp_oracle", t * 1e6, "cpu-jit"))
    t_fpm = _t(lambda x: fp_modular_sf_pallas(x, gm), fm, reps=reps)
    csv_rows.append(("kernel/fp_modular_sf/pallas", t_fpm * 1e6, mode))
    t_bpm = _t(lambda p: bp_modular_sf_pallas(p, gm), ym, reps=reps)
    csv_rows.append(("kernel/bp_modular_sf/pallas", t_bpm * 1e6,
                     f"{mode};bp_over_fp={t_bpm / max(t_fpm, 1e-12):.2f}x"))
    t = _t(lambda x: fp_modular_sf_pallas(x, gm, compute_dtype="bfloat16"),
           fm, reps=reps)
    csv_rows.append(("kernel/fp_modular_sf/pallas_bf16", t * 1e6,
                     f"{mode};speedup_vs_f32={t_fpm / max(t, 1e-12):.2f}x"))
    t = _t(lambda p: bp_modular_sf_pallas(p, gm, compute_dtype="bfloat16"),
           ym, reps=reps)
    csv_rows.append(("kernel/bp_modular_sf/pallas_bf16", t * 1e6,
                     f"{mode};speedup_vs_f32={t_bpm / max(t, 1e-12):.2f}x"))

    # ---- batched multi-row cone: exact view-folded batch vs lane packing - #
    # The ROADMAP's last kernel item: the exact cone pair folds batches into
    # the *grid* (one program per (sample, view)); the packed pair
    # pre-resamples rows axially and lane-packs batch x n_rows like fan.
    # The speedup column is the acceptance number for the packed tentpole.
    from repro.kernels.tune import packed_cone_ok
    Bc = 4
    if on_tpu:
        volp = VolumeGeometry(64, 64, 8)
        gp = cone_beam(24, 8, 96, volp, sod=1000.0, sdd=2000.0,
                       pixel_width=2.0, pixel_height=2.0)
    else:
        volp = VolumeGeometry(16, 16, 4)
        gp = cone_beam(4, 4, 24, volp, sod=200.0, sdd=400.0,
                       pixel_width=2.0, pixel_height=2.0)
    assert packed_cone_ok(gp), cone_packed_row_shift(gp)  # packed-eligible
    fp_b = jnp.asarray(np.random.default_rng(9).normal(
        size=(Bc,) + volp.shape).astype(np.float32))
    yp_b = jnp.asarray(np.random.default_rng(10).normal(
        size=(Bc,) + gp.sino_shape).astype(np.float32))
    t_exact_b = _t(lambda x: fp_cone_sf_pallas(x, gp), fp_b, reps=reps)
    csv_rows.append((f"kernel/fp_cone3d_b{Bc}/pallas_exact_batched",
                     t_exact_b * 1e6, mode))
    t_packed_b = _t(lambda x: fp_cone_packed(x, gp), fp_b, reps=reps)
    csv_rows.append((f"kernel/fp_cone3d_b{Bc}/pallas_packed",
                     t_packed_b * 1e6,
                     f"{mode};speedup_vs_exact="
                     f"{t_exact_b / max(t_packed_b, 1e-12):.2f}x"))
    t_bp_exact_b = _t(lambda p: bp_cone_sf_pallas(p, gp), yp_b, reps=reps)
    csv_rows.append((f"kernel/bp_cone3d_b{Bc}/pallas_exact_batched",
                     t_bp_exact_b * 1e6, mode))
    t_bp_packed_b = _t(lambda p: bp_cone_packed(p, gp), yp_b, reps=reps)
    csv_rows.append((f"kernel/bp_cone3d_b{Bc}/pallas_packed",
                     t_bp_packed_b * 1e6,
                     f"{mode};speedup_vs_exact="
                     f"{t_bp_exact_b / max(t_bp_packed_b, 1e-12):.2f}x"))
    # second batched-BP dtype-gate target: packed cone with bf16 tiles and
    # bs=2 stripe reuse vs its f32 sibling row above.
    t_bp_packed_mp = _t(lambda p: bp_cone_packed(
        p, gp, bs=2, compute_dtype="bfloat16"), yp_b, reps=reps)
    csv_rows.append((f"kernel/bp_cone3d_b{Bc}/pallas_packed_bf16",
                     t_bp_packed_mp * 1e6,
                     f"{mode};speedup_vs_f32="
                     f"{t_bp_packed_b / max(t_bp_packed_mp, 1e-12):.2f}x"))

    # ---- 2D production-ish slice (the paper's 512^2 limited-angle) ------- #
    vol3 = VolumeGeometry(256, 256, 1)
    g3 = parallel_beam(180, 1, 384, vol3)
    f3 = jnp.asarray(np.random.default_rng(4).normal(
        size=vol3.shape).astype(np.float32))
    t2 = _t(jax.jit(lambda x: ref.forward(x, g3, "sf")), f3)
    csv_rows.append(("kernel/fp_256x256x180", t2 * 1e6, "cpu-jit"))
