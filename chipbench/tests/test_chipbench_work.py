"""The benchmark's count of each call's necessary work, and its peaks."""
from __future__ import annotations

import math

import pytest

from chipbench import peaks, work

PAR = {"geometry": {"geom_type": "parallel",
                    "volume": {"nx": 4, "ny": 4, "nz": 2},
                    "n_rows": 2, "n_cols": 6,
                    "angles": [0.0, math.pi / 4]},
       "dtype": "float32"}
CONE = {"geometry": {"geom_type": "cone", "volume": {"nx": 4, "ny": 4, "nz": 4},
                     "n_rows": 3, "n_cols": 5, "sod": 8.0, "sdd": 16.0,
                     "angles": [0.0, math.pi / 2]},
        "dtype": "float32"}


def test_parallel_counts():
    w = work.sf_call(PAR)
    # 32 voxels; footprint 1 column wide at 0 degrees, sqrt(2) at 45; one
    # row (voxel and pixel both 1 mm).  Bytes: 32 voxels + 24 pixels, f32.
    assert w["fp"]["flops"] == pytest.approx(2 * 32 * (1 + math.sqrt(2)))
    assert w["fp"]["bytes"] == (32 + 2 * 2 * 6) * 4
    assert w["bp"] == w["fp"]


def test_cone_counts():
    w = work.sf_call(CONE)
    # Magnification 16/8 = 2: 2 columns and 2 rows of 1 mm pixels per voxel
    # and view; 64 voxels, 2 views, 30 pixels.
    assert w["fp"]["flops"] == pytest.approx(2 * 64 * 2 * (2 + 2))
    assert w["fp"]["bytes"] == (64 + 2 * 3 * 5) * 4
    bf16 = work.sf_call(dict(CONE, dtype="bfloat16"))
    assert bf16["bp"]["bytes"] == (64 + 2 * 3 * 5) * 2


def test_roofline_share_and_bound():
    peak = {"flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_share({"flops": 50.0, "bytes": 20.0}, 4.0, peak) \
        == (50.0, "memory")
    assert work.roofline_share({"flops": 500.0, "bytes": 20.0}, 10.0, peak) \
        == (50.0, "compute")


def test_peaks_by_device_kind():
    assert peaks.peaks("TPU v5 lite") == {"flops": 197e12,
                                          "hbm_bytes_per_s": 819e9}
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("cpu")
