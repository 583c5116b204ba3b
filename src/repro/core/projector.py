"""The ``Projector`` module — the library's main user-facing class.

This is the JAX analogue of the paper's ``torch.nn.Module``-derived
``Projector`` (their Listing 1): a differentiable object that can be dropped
into any training/inference pipeline.

    >>> spec = ProjectorSpec(geom)             # frozen op description
    >>> proj = Projector(spec)                 # the spec alone
    >>> sino = proj(volume)                    # A x        (differentiable)
    >>> vol  = proj.backproject(sino)          # A^T y      (differentiable)
    >>> rec  = proj.fbp(sino)                  # filtered backprojection
    >>> loss = proj.data_consistency(volume, measured)   # ||Ax - y||^2 term

Batched inputs (leading dims) are supported; gradients flow through every
method via the matched custom_vjp pairs in ``repro.kernels.ops``.  On the
Pallas backend every geometry (parallel, fan, cone, and axial-frame
modular — incl. helical scans) runs a kernel matched pair — the
backprojection (and therefore every gradient) is the exact transpose of
the forward kernel, never a fallback adjoint.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp

from repro.core.fbp import fbp as _fbp
from repro.core.geometry import CTGeometry
from repro.core.spec import ProjectorSpec, _require_spec
from repro.kernels import ops
from repro.kernels.tune import KernelConfig


class Projector:
    def __init__(self, spec: ProjectorSpec):
        """``Projector(ProjectorSpec(geom, ...))`` — the spec is the single
        frozen description of the operator and doubles as the op-cache /
        serving-bucket key; anything else raises ``TypeError``.

        The spec's ``mode`` selects between the exact kernels and the
        approximate lane-packed cone pair: "exact" always uses the exact
        kernels, "packed" forces the packed pair (small-cone-angle
        pre-resample), "auto" (default) uses packed only when the
        geometry's derived error bound is under tolerance (see
        ``repro.kernels.tune.packed_cone_ok``).
        Non-cone geometries are unaffected by ``mode``.

        Its ``compute_dtype`` sets the kernel tile precision ("bfloat16" |
        "float32"; None follows the input dtype): tiles stream at that
        dtype, accumulation stays f32, outputs keep the input's dtype —
        see kernels/precision.py for the policy and its tolerance model.

        Modular geometries run the SF matched pair like every other
        geometry (Pallas for axial frames — incl. helical — via the
        registered `supports` gate); tilted frames fall back to the Joseph
        ray-marcher inside the ref dispatch, so "sf" is always safe here."""
        self.spec = _require_spec(spec, "Projector")

    # The spec's fields, read through the projector.
    @property
    def geom(self) -> CTGeometry:
        return self.spec.geom

    @property
    def model(self) -> str:
        return self.spec.model

    @property
    def backend(self) -> str:
        return self.spec.backend

    @property
    def config(self) -> Optional[KernelConfig]:
        return self.spec.config

    @property
    def mode(self) -> str:
        return self.spec.mode

    @property
    def compute_dtype(self):
        return self.spec.compute_dtype

    # -- linear ops -------------------------------------------------------- #
    def __call__(self, volume):
        return ops.forward_project(volume, self.spec)

    forward = __call__

    def backproject(self, sino):
        return ops.back_project(sino, self.spec)

    @property
    def T(self):
        return self.backproject

    # -- analytic reconstruction ------------------------------------------ #
    def fbp(self, sino, filter_name: str = "ramp",
            short_scan: Optional[bool] = None):
        """``short_scan`` applies Parker weighting for fan beams (``None``
        auto-detects from the geometry's angular span)."""
        op = functools.partial(_fbp, geom=self.geom, model=self.model,
                               backend=self.backend, filter_name=filter_name,
                               config=self.config, short_scan=short_scan)
        return ops._batched(op, sino, 3)

    # -- DL integration ---------------------------------------------------- #
    def data_consistency(self, volume, measured, mask=None):
        """0.5 * || M (A x - y) ||^2 / n  — the paper's data-consistency loss.

        ``mask`` selects measured views/pixels (limited-angle / few-view)."""
        r = self(volume) - measured
        if mask is not None:
            r = r * mask
        return 0.5 * jnp.mean(jnp.square(r))

    def complete_sinogram(self, volume, measured, mask):
        """Sinogram completion (paper §3): keep measured views, fill the rest
        from the forward projection of the predicted volume."""
        synth = self(volume)
        return mask * measured + (1.0 - mask) * synth

    # -- misc --------------------------------------------------------------- #
    def sino_shape(self):
        return self.geom.sino_shape

    def vol_shape(self):
        return self.geom.vol.shape

    def __repr__(self):
        g = self.geom
        mode = f", mode={self.mode}" if self.mode != "auto" else ""
        cdt = (f", compute_dtype={self.compute_dtype}"
               if self.compute_dtype is not None else "")
        return (f"Projector({g.geom_type}, model={self.model}{mode}{cdt}, "
                f"vol={g.vol.shape}, sino={g.sino_shape})")
