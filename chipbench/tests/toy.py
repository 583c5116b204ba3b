"""Each committed cell on a toy geometry, for runs on the CPU with the
Pallas kernels in interpret mode."""
from __future__ import annotations

import dataclasses
import time

import jax

from chipbench import cells, run

PALLAS = {"model": "sf", "backend": "pallas", "mode": "auto"}
TOY = {
    "parallel": {"geom_type": "parallel",
                 "volume": {"nx": 8, "ny": 8, "nz": 8},
                 "n_angles": 8, "n_rows": 8, "n_cols": 16,
                 "angular_range": 180.0},
    "cone": {"geom_type": "cone", "volume": {"nx": 8, "ny": 8, "nz": 8},
             "n_angles": 2, "n_rows": 8, "n_cols": 16,
             "pixel_width": 2.0, "pixel_height": 2.0, "sod": 32.0,
             "sdd": 64.0, "detector_type": "flat",
             "angles": [0.6283185307179586, 1.2566370614359172]},
}


def toy_cell(name: str) -> cells.Cell:
    """The committed cell, its checks and traffic, on a toy geometry."""
    cell = cells.load(name)
    geom = TOY[cell.cfg["geometry"]["geom_type"]]
    cfg = dict(cell.cfg, geometry=geom, projector=PALLAS)
    traffic = dict(cell.traffic)
    if "measured" in traffic:
        traffic["measured"] = {"low": 0.0, "high": 8.0}
    sample = {"bp_rows": 4} if "bp_rows" in cell.checks["sample"] else {}
    if "phantom" in traffic:
        sample = {"slices": 3}
    return dataclasses.replace(cell, cfg=cfg, traffic=traffic,
                               checks=dict(cell.checks, sample=sample))


def execute(cell, seed=2 ** 40 + 7):
    return run.execute(cell, seed, 0.0, 0, jax.devices(), time.perf_counter())


