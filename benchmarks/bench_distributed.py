"""Weak-scaling benchmark for the distributed projector subsystem.

``dist/<op>/ws<n>`` rows time the sharded FP, overlap-comm BP, and a full
distributed SIRT loop on angle-sharded meshes of 1/2/4/8 shards with the
*per-shard* work held constant (n_angles grows with the mesh) — classic
weak scaling, so a perfectly scaling stack prints a flat column.

The regression gate (``check_regression``) normalizes every ``ws<n>`` row
by the same op's ``ws1`` row from the same run, which cancels machine
speed and makes the committed baseline a *scaling-shape* gate: a PR that
breaks comm overlap or serializes the mesh shows up as ws4/ws8 drifting
up relative to ws1, on any runner.  On the CI CPU the 8 "devices" are
forced host threads on a shared core, so the absolute column is ~linear
in shards (all shards timeshare one core); the gate tracks the *shape* of
that line, and real parallel speedups are a TPU-pod measurement.

Emits nothing when fewer than 8 devices are visible (dev boxes without
``XLA_FLAGS=--xla_force_host_platform_device_count=8``): the gate only
compares dist rows when the fresh CSV contains the suite.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core import ProjectorSpec, VolumeGeometry, parallel_beam
from repro.core.distributed import distribute
from repro.recon.sirt import sirt

SHARD_COUNTS = (1, 2, 4, 8)
ANGLES_PER_SHARD = 8


def _t(fn, *a, reps=2):
    out = fn(*a)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(csv_rows: list):
    if jax.device_count() < max(SHARD_COUNTS):
        return
    backend = jax.default_backend()
    vol = VolumeGeometry(24, 24, 8)
    rng = np.random.default_rng(0)
    f = jnp.asarray(rng.normal(size=vol.shape), jnp.float32)

    for ws in SHARD_COUNTS:
        mesh = compat.make_mesh((ws, 1), ("data", "model"),
                             devices=jax.devices()[:ws])
        g = parallel_beam(ANGLES_PER_SHARD * ws, 8, 32, vol)
        dp = distribute(ProjectorSpec(g), mesh)
        tag = f"{backend}-weak-{ws}shard"

        fv = dp.shard_volume(f)
        csv_rows.append((f"dist/fp_par/ws{ws}", _t(dp.fp, fv) * 1e6, tag))
        y = dp(fv)
        ys = dp.shard_sino(y)
        csv_rows.append((f"dist/bp_par/ws{ws}", _t(dp.bp, ys) * 1e6, tag))

        # the eager sirt loop would re-trace its scan per call: jit the
        # whole solve once so the row times the mesh program, not tracing
        solve = jax.jit(lambda sino, dp=dp: sirt(dp, sino, n_iters=4).image)
        csv_rows.append((f"dist/sirt_par/ws{ws}",
                         _t(solve, ys) * 1e6, tag))
