"""The necessary work of one projector call, counted from shapes alone.

A call's roofline time is the larger of its floating-point operations over
the chip's peak rate and its bytes over the HBM bandwidth.  Both counts
depend only on the configuration, never on how the program computes the
call, so a kernel that is replaced or fused cannot make its share read
high.

* Bytes: one read of the input and one write of the output (volume
  ``nx*ny*nz`` and sinogram ``n_angles*n_rows*n_cols`` elements of the
  configuration's dtype).  Weights are made on the fly from the geometry.
* Operations: two per multiply-add of the Separable-Footprint model
  (Long, Fessler & Balter 2010): each voxel adds into every detector pixel
  its footprint covers, once per view.  The count per voxel and view is
  ``max(1, w_u / du) * max(1, w_v / dv)``, the footprint's transaxial and
  axial widths at the rotation centre in pixels: ``w_u = M dx (|cos a| +
  |sin a|)`` and ``w_v = M dz``, with ``M = sdd / sod`` for a cone and 1
  for a parallel beam.  Footprints cover ``width / pixel + 1`` pixels on
  average, so this is a lower bound and never overstates the work.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import Geom

_ITEMSIZE = {"float32": 4, "bfloat16": 2}


def sf_call(cfg: dict) -> dict:
    """{"fp": {...}, "bp": {...}}, each with ``flops`` and ``bytes``."""
    g = Geom.from_config(cfg["geometry"])
    item = _ITEMSIZE[cfg["dtype"]]
    n_vox = g.nx * g.ny * g.nz
    n_pix = len(g.angles) * g.n_rows * g.n_cols
    m = g.sdd / g.sod if g.kind == "cone" else 1.0
    a = np.asarray(g.angles, np.float64)
    cols = np.maximum(1.0, m * g.dx * (np.abs(np.cos(a)) + np.abs(np.sin(a)))
                      / g.du)
    rows = max(1.0, m * g.dz / g.dv)
    macs = float(n_vox * rows * cols.sum())
    one = {"flops": 2.0 * macs, "bytes": float((n_vox + n_pix) * item)}
    return {"fp": dict(one), "bp": dict(one)}


def roofline_share(work: dict, seconds: float, peak: dict) -> tuple:
    """(percent of the roofline, "compute" or "memory") for ``work`` done
    in ``seconds`` of device time."""
    t_c = work["flops"] / peak["flops"]
    t_m = work["bytes"] / peak["hbm_bytes_per_s"]
    return 100.0 * max(t_c, t_m) / seconds, ("compute" if t_c > t_m
                                             else "memory")
