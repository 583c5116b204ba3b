"""The four-chip cell ``par1024.fp_bp.z4`` on the CPU.

Its run, its control and a planted fault go on a toy parallel geometry
with four host devices forced, in a child process: only a process that
has not started JAX can set the device count, and the suite's own
processes have one device.  What needs no mesh runs here: the per-slab
reference against the whole volume's, the per-chip work, and the
collective-time reader on a synthetic trace."""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from chipbench import cells, reference, trace, work
from chipbench.steps import fp_bp_sharded

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "par1024.fp_bp.z4"
TOY = {"geom_type": "parallel", "volume": {"nx": 8, "ny": 8, "nz": 8},
       "n_angles": 8, "n_rows": 8, "n_cols": 16, "angular_range": 180.0}


def toy_cell() -> cells.Cell:
    """The committed cell, its checks and traffic, on a toy geometry cut
    into the cell's four z-slabs of two slices and two rows."""
    cell = cells.load(CELL)
    shard = dict(cell.cfg["shard"], slab_slices=2, slab_rows=2)
    cfg = dict(cell.cfg, geometry=TOY, shard=shard,
               projector={"model": "sf", "backend": "pallas", "mode": "auto"})
    traffic = dict(cell.traffic, measured={"low": 0.0, "high": 8.0})
    return dataclasses.replace(cell, cfg=cfg, traffic=traffic,
                               checks=dict(cell.checks,
                                           sample={"bp_rows": 4}))


def _swap_first_slabs(build):
    """The pair with slab 0's detector rows swapped with slab 1's in A x,
    as if two chips held each other's row blocks."""
    def wrapped(spec, mesh):
        fp, *rest = build(spec, mesh)
        n = spec.geom.n_rows // spec.shard.z_shards

        def swapped(f):
            s = fp(f)
            return jnp.concatenate([s[:, n:2 * n], s[:, :n], s[:, 2 * n:]],
                                   axis=1)
        return (swapped, *rest)
    return wrapped


def _child():
    """Runs in the child: the cell's run, its control and the planted
    fault, each reported as one JSON line."""
    import time
    from chipbench import run
    from repro.core import distributed
    cell = toy_cell()
    res = run.execute(cell, 2 ** 40 + 7, 0.0, 0, jax.devices()[:4],
                      time.perf_counter())
    res["collectives"] = cells.reader("collectives.setup")(None)
    print(json.dumps({"run": res}), flush=True)

    step = cell.step(11, compute_dtype="bfloat16")
    step.setup()
    out = jax.block_until_ready(step.run())
    step.release()
    checks = run.judge(step.check(out), cell.checks["limits"])
    print(json.dumps({"control": checks}), flush=True)

    distributed._build_distributed = _swap_first_slabs(
        distributed._build_distributed)
    res = run.execute(cell, 2 ** 40 + 8, 0.0, 0, jax.devices()[:4],
                      time.perf_counter())
    print(json.dumps({"fault": res}), flush=True)


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu", REPRO_TUNE_CACHE="0", REPRO_AUTOTUNE="0",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    code = ("from chipbench.tests.test_chipbench_sharded import _child; "
            "_child()")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    found = {}
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            found.update(json.loads(line))
    return found


def test_cell_runs_correct_on_four_devices(child):
    res = child["run"]
    assert res["correct"], res["checks"]
    assert res["attempted"] == 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"step_s", "setup_s"}
    assert res["device"]["count"] == 4
    assert res["program"] == {"backend": "pallas", "mode": "exact",
                              "devices": 4, "z_shards": 4, "halo": 0,
                              "comm_blocks": 1}
    assert res["collectives"] == 0


@pytest.mark.parametrize("case", ["control", "fault"])
def test_control_and_planted_fault_are_not_correct(child, case):
    """The bfloat16 tile path, and slab 0's rows swapped with slab 1's."""
    if case == "control":
        assert not all(c["ok"] for c in child["control"].values()), child
    else:
        assert not child["fault"]["correct"], child["fault"]["checks"]


def test_slab_reference_is_the_whole_reference():
    """Slab by slab, the reference of one slab's configuration gives the
    reference of the whole toy volume: r whole and g on sampled rows."""
    cfg = toy_cell().cfg
    slab = fp_bp_sharded.slab_config(cfg)["geometry"]
    k = jax.random.split(jax.random.key(5))
    x = jax.random.uniform(k[0], (8, 8, 8))
    b = jax.random.uniform(k[1], (8, 8, 16), maxval=8.0)
    rows = np.array([1, 4, 6])
    r, g = reference.sf_fp_bp(TOY, x, b, rows)
    parts = [reference.sf_fp_bp(slab, x[..., 2 * s:2 * s + 2],
                                b[:, 2 * s:2 * s + 2], rows)
             for s in range(4)]
    np.testing.assert_allclose(np.concatenate([p[0] for p in parts], 1), r,
                               rtol=1e-6, atol=1e-6 * float(jnp.abs(r).max()))
    np.testing.assert_allclose(np.concatenate([p[1] for p in parts], 2), g,
                               rtol=1e-6, atol=1e-6 * float(jnp.abs(g).max()))


def test_slab_config_refuses_dependent_slabs():
    cfg = cells.load(CELL).cfg
    geom = dict(cfg["geometry"], pixel_height=2.0)
    with pytest.raises(ValueError, match="same height"):
        fp_bp_sharded.slab_config(dict(cfg, geometry=geom))


def test_work_is_one_chip_share():
    cell = cells.load(CELL)
    step = cell.step(1)
    whole = work.sf_call(cell.cfg)
    for d in ("fp", "bp"):
        for k in ("flops", "bytes"):
            assert step.work()[d][k] == pytest.approx(whole[d][k] / 4,
                                                      rel=1e-12)


def _op(name, dev, ms, kind="other", scope="bp"):
    return trace.Op(name, scope, kind, 0.0, ms * 1e6, dev)


def test_comm_ms_reads_collectives_only():
    read = cells.reader("comm_ms.step")
    ops = [_op("all-reduce-start.1", "d0", 3.0),
           _op("all-reduce-done.1", "d0", 1.0),
           _op("collective-permute.2", "d1", 2.0),
           _op("all-gather-start", "d1", 0.5),
           _op("reduce-scatter.4", "d0", 0.25),
           _op("all-to-all.9", "d1", 0.25),
           _op("fusion.3", "d0", 50.0),
           _op("sf_par_bp_g0.1", "d1", 80.0, kind="pallas"),
           _op("copy.1", "d0", 5.0, scope="")]
    t = trace.Trace((0.0, 1e9), ops, [], ["d0", "d1"])
    r = trace.Reading(t, 2, {}, {})
    assert read(r) == pytest.approx(7.0 / 2 / 2)
    none = trace.Reading(trace.Trace((0.0, 1e9), ops[6:], [], ["d0", "d1"]),
                         2, {}, {})
    assert read(none) == 0.0
    assert read(trace.Reading(trace.Trace((0.0, 1e9), [], [], []), 1, {},
                              {})) is None
