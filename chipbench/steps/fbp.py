"""Step kind ``fbp``: one whole analytic reconstruction through the user
entry point ``repro.core.fbp.fbp`` (ramp filter, then the interpolation
backprojector), of a sinogram of random ellipsoids made at set-up.

The correctness check compares the window's last reconstruction on
``sample["slices"]`` z slices drawn from the seed with
:func:`chipbench.reference.fbp_slices`: every voxel of those slices, every
view, the filter and the backprojection.  Every reconstruction of a window
has the same input, so the last one stands for all of them.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from chipbench import inputs, reference

SCOPES = ("fbp",)


class Step:
    """One cell's program and input.  ``precision`` overrides the
    configuration's matmul precision: the control runs the program at
    JAX's default (one bfloat16 pass on the TPU)."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, sample: dict,
                 compute_dtype=None, precision=None):
        if compute_dtype is not None:
            raise ValueError("fbp takes no compute_dtype")
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.sample = sample
        self.precision = precision or cfg["matmul_precision"]
        self.notes = {}

    def setup(self):
        from repro.core.fbp import fbp
        from repro.core.geometry import from_config
        geom = from_config(self.cfg["geometry"])
        self.sino = inputs.ellipsoid_sinogram(
            self.cfg["geometry"], self.traffic["phantom"], self.seed)
        filt = self.traffic["filter"]

        def recon(p):
            with jax.named_scope("fbp"):
                return fbp(p, geom, filter_name=filt)

        with jax.default_matmul_precision(self.precision):
            self.compiled = jax.jit(recon).lower(self.sino).compile()
        self.notes = {"filter": filt}

    def run(self):
        return self.compiled(self.sino)

    def hlo_text(self) -> str:
        return self.compiled.as_text()

    def work(self) -> dict:
        return {}

    def release(self):
        self.compiled = None
        jax.clear_caches()

    def slices(self) -> np.ndarray:
        nz = self.cfg["geometry"]["volume"]["nz"]
        k = min(nz, int(self.sample["slices"]))
        rng = np.random.default_rng(int(self.seed) % 2 ** 64)
        return np.sort(rng.choice(nz, size=k, replace=False))

    def check(self, out) -> dict:
        zi = self.slices()
        ref = jax.jit(lambda p: reference.fbp_slices(
            self.cfg["geometry"], p, zi))(self.sino)
        got = out[..., jnp.asarray(zi)]
        return {"err_rec": float(jnp.max(jnp.abs(got - ref))
                                 / jnp.max(jnp.abs(ref)))}
