"""Each cell's run, minus the look for a chip, on a toy geometry on the
CPU, with the timed path broken underneath: ``correct`` comes out false
once for each fault the cell can have.  A step keeps no state and runs on
one chip, so "state returned unchanged" and "exchange between chips left
out" do not apply."""
from __future__ import annotations

import importlib

import numpy as np
import pytest
import jax.numpy as jnp

from chipbench.tests.toy import execute, toy_cell


@pytest.fixture(autouse=True)
def _committed_configs(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", "0")
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")


def _altered(fn):
    """Output of ``fn`` with its first leading slice off by 1% of the max."""
    def wrapped(*a, **k):
        out = fn(*a, **k)
        return out.at[0].add(0.01 * jnp.max(jnp.abs(out)))
    return wrapped


def _half_views_bp(fn):
    """A^T over every other view, doubled: half the batch left out and the
    mean taken over the rest."""
    def wrapped(p, *a, **k):
        return 2.0 * fn(p.at[..., 1::2, :, :].set(0.0), *a, **k)
    return wrapped


def _half_views_fbp(fn):
    def wrapped(sino, geom, *a, **k):
        keep = np.arange(0, geom.n_angles, 2)
        return fn(sino[keep], geom.subset(keep), *a, **k)
    return wrapped


FAULTS = [
    ("par512.fp_bp", "repro.kernels.ops.forward_project", _altered),
    ("par512.fp_bp", "repro.kernels.ops.back_project", _half_views_bp),
    ("cone512.fp_bp", "repro.kernels.ops.forward_project", _altered),
    ("cone512.fp_bp", "repro.kernels.ops.back_project", _half_views_bp),
    ("par512.fbp", "repro.core.fbp.fbp_parallel", _altered),
    ("par512.fbp", "repro.core.fbp.fbp_parallel", _half_views_fbp),
]


@pytest.mark.parametrize("name,target,fault", FAULTS,
                         ids=[f"{n}-{f.__name__}" for n, _, f in FAULTS])
def test_broken_path_is_not_correct(monkeypatch, name, target, fault):
    mod, attr = target.rsplit(".", 1)
    mod = importlib.import_module(mod)
    monkeypatch.setattr(mod, attr, fault(getattr(mod, attr)))
    res = execute(toy_cell(name))
    assert not res["correct"], res["checks"]
    assert res["failed"] == res["attempted"]


