"""Each cell's run, minus the look for a chip, on a toy geometry on the
CPU with the Pallas kernels in interpret mode: the run is correct, and
its control (the next precision down) is not."""
from __future__ import annotations

import dataclasses
import sys
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from chipbench import cells, reference, run
from chipbench.tests.toy import execute, toy_cell


@pytest.fixture(autouse=True)
def _committed_configs(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", "0")
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")


CELLS = ["par512.fp_bp", "cone512.fp_bp", "par512.fbp"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct(name):
    res = execute(toy_cell(name))
    assert res["correct"], res["checks"]
    assert res["attempted"] == 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    timed = cells.load(name).timed_metrics
    assert set(res["metrics"]) == set(timed) | {"setup_s"}
    assert res["device"]["count"] == len(jax.devices())
    if name.endswith("fp_bp"):
        assert res["program"] == {"backend": "pallas", "mode": "exact"}


def test_step_kind_with_its_own_window(monkeypatch):
    """A traffic mix whose step kind drives its own loop (an open loop of
    requests, say) reports the metrics its ``window`` returns, with the
    units BENCHMARK.json gives them: no edit of the harness."""
    from chipbench.steps import fp_bp

    class Step(fp_bp.Step):
        def window(self, seconds):
            outs = [jax.block_until_ready(self.run()) for _ in range(3)]
            return outs[-1], 3, {"served_rps": 3.5, "request_p95_s": 0.25}

    mod = types.ModuleType("chipbench.steps.toy_open")
    mod.Step, mod.SCOPES = Step, fp_bp.SCOPES
    monkeypatch.setitem(sys.modules, "chipbench.steps.toy_open", mod)
    cell = toy_cell("par512.fp_bp")
    extra = [{"name": "served_rps", "unit": "1/s"},
             {"name": "request_p95_s", "unit": "s"}]
    cell = dataclasses.replace(
        cell, end_to_end=cell.end_to_end + extra,
        traffic=dict(cell.traffic, step="toy_open",
                     metrics=["served_rps", "request_p95_s"]))
    res = execute(cell)
    assert res["correct"] and res["attempted"] == 3
    assert res["metrics"] == {
        "served_rps": {"value": 3.5, "unit": "1/s"},
        "request_p95_s": {"value": 0.25, "unit": "s"},
        "setup_s": res["metrics"]["setup_s"]}


@pytest.mark.parametrize("name", ["par512.fp_bp", "cone512.fp_bp"])
def test_control_bf16_tiles_is_not_correct(name):
    """The control of the projector cells: the program's own bfloat16 tile
    path in place of the float32 one."""
    cell = toy_cell(name)
    step = cell.step(11, compute_dtype="bfloat16")
    step.setup()
    out = jax.block_until_ready(step.run())
    step.release()
    checks = run.judge(step.check(out), cell.checks["limits"])
    assert not all(c["ok"] for c in checks.values()), checks


def test_control_bf16_fbp_is_not_correct():
    """The control of the FBP cell: the reference in bfloat16, put in the
    program's place."""
    cell = toy_cell("par512.fbp")
    step = cell.step(11)
    step.setup()
    nz = cell.cfg["geometry"]["volume"]["nz"]
    out = reference.fbp_slices(cell.cfg["geometry"], step.sino,
                               np.arange(nz), dtype=jnp.bfloat16)
    step.release()
    checks = run.judge(step.check(out), cell.checks["limits"])
    assert not all(c["ok"] for c in checks.values()), checks
