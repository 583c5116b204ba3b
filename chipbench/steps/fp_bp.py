"""Step kind ``fp_bp``: the data-consistency gradient that iterative and
learned reconstructions take every iteration, through the user entry
points ``Projector(spec)`` and ``Projector(spec).T``:

    r = A x - b,   g = A^T r

over every view of the configuration, as one jitted program.  The
correctness check compares both outputs of the window's last step with
:func:`chipbench.reference.sf_fp_bp` on the same inputs: ``r`` whole, and
``g`` whole or, where the cell's check file sets ``bp_rows``, on that many
x rows drawn from the seed.  The Pallas kernels and the XLA wrappers
around them (line-major transposes, padding, the axial contraction) are
all inside.  Every step of a window computes the same function of the
same inputs, so the last one stands for all of them.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from chipbench import inputs, reference, work

SCOPES = ("fp", "residual", "bp")


def _rel(a, b) -> float:
    """Largest absolute error over the reference's largest magnitude."""
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


class Step:
    """One cell's program and inputs.  ``compute_dtype`` and ``precision``
    override the configuration: the control runs the program's own
    bfloat16 path through them."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, sample: dict,
                 compute_dtype=None, precision=None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.sample = sample
        self.compute_dtype = compute_dtype
        self.precision = precision or cfg["matmul_precision"]
        self.notes = {}

    def setup(self):
        from repro.core.geometry import from_config
        from repro.core.projector import Projector
        from repro.core.spec import ProjectorSpec
        from repro.kernels import ops
        p = self.cfg["projector"]
        spec = ProjectorSpec(from_config(self.cfg["geometry"]),
                             model=p["model"], backend=p["backend"],
                             mode=p["mode"],
                             compute_dtype=self.compute_dtype)
        proj = Projector(spec)
        self.x, self.b = inputs.uniform_pair(self.cfg["geometry"],
                                             self.traffic, self.seed)

        def step(x, b):
            with jax.named_scope("fp"):
                s = proj(x)
            with jax.named_scope("residual"):
                r = s - b
            with jax.named_scope("bp"):
                g = proj.T(r)
            return r, g

        with jax.default_matmul_precision(self.precision):
            self.compiled = jax.jit(step).lower(self.x, self.b).compile()
        self.notes = {"backend": ops.resolve_backend(spec),
                      "mode": ops.resolve_mode(spec)}

    def run(self):
        return self.compiled(self.x, self.b)

    def hlo_text(self) -> str:
        return self.compiled.as_text()

    def work(self) -> dict:
        return work.sf_call(self.cfg)

    def release(self):
        """Drop the program: its executable and the op caches."""
        from repro.kernels import ops
        self.compiled = None
        ops.clear_cache()
        jax.clear_caches()

    def bp_rows(self):
        """The x rows of ``g`` the check compares: all, or a sample."""
        nx = self.cfg["geometry"]["volume"]["nx"]
        k = self.sample.get("bp_rows", nx)
        if k >= nx:
            return None
        rng = np.random.default_rng(int(self.seed) % 2 ** 64)
        return np.sort(rng.choice(nx, size=k, replace=False))

    def check(self, out) -> dict:
        r, g = out
        rows = self.bp_rows()
        r_ref, g_ref = jax.jit(lambda x, b: reference.sf_fp_bp(
            self.cfg["geometry"], x, b, rows))(self.x, self.b)
        if rows is not None:
            g = g[jnp.asarray(rows)]
        return {"err_r": _rel(r, r_ref), "err_g": _rel(g, g_ref)}
