"""The benchmark's plain references agree with the system's own jnp
oracles on small geometries (CPU, float32): both follow the same
definitions, written apart."""
from __future__ import annotations

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from chipbench import reference

PAR = {"geom_type": "parallel",
       "volume": {"nx": 16, "ny": 12, "nz": 8, "offset_z": 0.3},
       "n_angles": 12, "n_rows": 8, "n_cols": 24, "angular_range": 180.0}
CONE = {"geom_type": "cone", "volume": {"nx": 16, "ny": 16, "nz": 16},
        "n_angles": 3, "n_rows": 16, "n_cols": 24, "pixel_width": 2.0,
        "pixel_height": 2.0, "sod": 32.0, "sdd": 64.0,
        "detector_type": "flat", "angles": [0.3, 1.9, 4.0]}


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("cfg", [PAR, CONE], ids=["parallel", "cone"])
def test_sf_matches_program_oracle(cfg):
    from repro.core.geometry import from_config
    from repro.kernels import ref
    geom = from_config(cfg)
    kx, kb = jax.random.split(jax.random.key(3))
    x = jax.random.uniform(kx, geom.vol.shape)
    b = 8.0 * jax.random.uniform(kb, geom.sino_shape)
    with jax.default_matmul_precision("highest"):
        r0 = ref.forward(x, geom, "sf") - b
        g0 = ref.adjoint(r0, geom, "sf")
    r, g = jax.jit(lambda x, b: reference.sf_fp_bp(
        cfg, x, b, rows_per_block=4))(x, b)
    assert _rel(r, r0) < 2e-6 and _rel(g, g0) < 2e-6
    rows = np.array([1, 6, 9, 14])
    _, gs = reference.sf_fp_bp(cfg, x, b, bp_rows=rows, rows_per_block=2)
    assert _rel(gs, g0[rows]) < 2e-6


def test_fbp_matches_program():
    from repro.core.fbp import fbp
    from repro.core.geometry import from_config
    geom = from_config(PAR)
    sino = jax.random.uniform(jax.random.key(5), geom.sino_shape)
    with jax.default_matmul_precision("highest"):
        rec = fbp(sino, geom)
    zi = np.array([0, 3, 7])
    assert _rel(reference.fbp_slices(PAR, sino, zi), rec[..., zi]) < 2e-6


def test_view_weights_cover_the_range():
    a = np.deg2rad([0.0, 10.0, 30.0, 100.0])
    w = reference.view_weights(a, np.pi)
    assert w.sum() == pytest.approx(np.pi, rel=1e-6)
    assert w[1] == pytest.approx(np.deg2rad(15.0), rel=1e-6)
