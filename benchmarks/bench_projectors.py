"""Paper Table 1: forward/back-projection performance.

On this CPU container we (a) measure wall time at CPU-feasible reduced
shapes for every geometry x model x direction, and (b) report the projected
TPU-v5e time for the paper's full shapes from the roofline model (SF is
HBM-bound; see EXPERIMENTS.md §Perf-CT).  Output CSV: name,us_per_call,derived.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.leap_ct import table1_geometries
from repro.core import Projector, ProjectorSpec
from repro.launch import roofline


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def projected_tpu_seconds(geom, model="sf") -> float:
    """SF projection is HBM-bound: traffic ~ footprint-K reads of the volume
    + one sinogram write (+ z-matmul traffic)."""
    v = geom.vol
    K = geom.max_footprint_cols()
    vol_bytes = v.nx * v.ny * v.nz * 4
    sino_bytes = int(np.prod(geom.sino_shape)) * 4
    # per angle: one streamed pass over the (z-contracted) volume + tile output
    traffic = geom.n_angles * (v.nx * v.ny * max(geom.n_rows, v.nz) * 4) \
        + sino_bytes + vol_bytes
    return traffic / roofline.peaks("TPU v5 lite")["hbm_bw"]


def run(csv_rows: list):
    cells = table1_geometries(reduced=True)
    full = table1_geometries(reduced=False)
    for name, geom in cells.items():
        proj = Projector(ProjectorSpec(geom, "sf"))
        f = jnp.asarray(np.random.default_rng(0).normal(
            size=geom.vol.shape).astype(np.float32))
        fp = jax.jit(lambda x: proj(x))
        t_fp = _time(fp, f)
        y = fp(f)
        bp = jax.jit(lambda s: proj.T(s))
        t_bp = _time(bp, y)
        tpu_est = projected_tpu_seconds(full[name])
        csv_rows.append((f"table1/{name}/fp", t_fp * 1e6,
                         f"tpu_v5e_est_full={tpu_est:.3f}s"))
        csv_rows.append((f"table1/{name}/bp", t_bp * 1e6,
                         f"reduced_shape={geom.vol.shape}x{geom.n_angles}"))
