"""``ProjectorSpec`` — the single immutable description of a projection op.

Before this module the projector stack passed five loose keyword arguments
``(model, backend, mode, compute_dtype, config)`` alongside every geometry,
and the op cache in :mod:`repro.kernels.ops` re-assembled them into an
ad-hoc tuple key at every call site.  ``ProjectorSpec`` consolidates the
whole configuration into one frozen, hashable value:

    >>> spec = ProjectorSpec(geom, model="sf", compute_dtype="bf16")
    >>> proj = Projector(spec)
    >>> fp, bp = get_ops(spec)
    >>> sino = forward_project(f, spec)

The spec is simultaneously

  * the **op-cache key** (``spec.cache_key()`` replaces the old tuple key),
  * the **serving admission-bucket key** (``spec.bucket_key()``): two recon
    requests may share a dynamically packed batch iff their specs hash
    equal — same geometry content, same kernels, same precision — so one
    compiled executable serves them all, and
  * the **validation point**: bad model/mode/backend/dtype values raise here,
    once, instead of deep inside dispatch.

The spec is the only way to name an operator: every entry point
(``Projector``, ``get_ops``, ``forward_project``, ``back_project``,
``resolve_mode``, ``resolve_backend``) takes one and raises ``TypeError``
for anything else, a bare geometry included.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Optional, Tuple, TYPE_CHECKING

from repro.core.geometry import CTGeometry

if TYPE_CHECKING:                                     # pragma: no cover
    from repro.kernels.tune import KernelConfig

__all__ = ["ProjectorSpec", "ShardSpec"]

_MODELS = ("sf", "joseph")
_BACKENDS = ("auto", "pallas", "ref")
_MODES = ("auto", "exact", "packed")


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Frozen description of how a projection operator is laid out on a mesh.

    The shard layout is part of the *operator identity*: two distributed
    projectors with different layouts compile different programs, exchange
    different halos, and must not share op-cache entries or serving buckets,
    so ``ShardSpec`` participates in ``ProjectorSpec.cache_key()`` /
    ``bucket_key()`` exactly like ``model`` or ``compute_dtype``.

    Fields:
        mesh_axes:     ``(angle_axis, z_axis)`` mesh-axis names.  ``z_axis``
                       may be ``None`` when ``z_shards == 1`` (pure angle
                       sharding).
        angle_shards:  shards along the view/angle axis (the data axis of
                       the X-ray transform — views are independent in the
                       forward direction, summed in the adjoint).
        z_shards:      shards along the volume z axis (the model axis —
                       axial slabs).
        halo:          z-slab halo width in voxels exchanged between
                       neighbouring slabs (``jax.lax.ppermute``).  Must be 0
                       for parallel/fan (their slabs are exactly
                       independent) and positive for cone/modular z-slabs
                       (diverging / z-travelling rays read into the
                       neighbour slab).
        comm_blocks:   backprojection reduction schedule: the local views
                       are split into this many blocks, with one psum per
                       block, so the collective for block *b* overlaps the
                       Pallas BP of block *b+1*; 1 is a single synchronous
                       psum after all local views are backprojected.  0
                       (default) means auto: the largest divisor of the
                       per-shard view count that is <= 4 and aligned with
                       the kernels' ``bab`` view-blocking granularity, and
                       1 with one angle shard, where there is nothing to
                       reduce and no psum.
    """

    mesh_axes: Tuple[Optional[str], ...] = ("data", "model")
    angle_shards: int = 1
    z_shards: int = 1
    halo: int = 0
    comm_blocks: int = 0

    def __post_init__(self):
        axes = tuple(self.mesh_axes)
        if len(axes) != 2:
            raise ValueError(
                f"mesh_axes must be (angle_axis, z_axis), got {axes!r}")
        if not isinstance(axes[0], str) or not axes[0]:
            raise ValueError(
                f"angle axis (mesh_axes[0]) must be a mesh-axis name, "
                f"got {axes[0]!r}")
        if axes[1] is not None and (not isinstance(axes[1], str)
                                    or axes[1] == axes[0]):
            raise ValueError(
                f"z axis (mesh_axes[1]) must be None or a mesh-axis name "
                f"distinct from the angle axis, got {axes!r}")
        object.__setattr__(self, "mesh_axes", axes)
        if self.angle_shards < 1 or self.z_shards < 1:
            raise ValueError(
                f"angle_shards/z_shards must be >= 1, got "
                f"{(self.angle_shards, self.z_shards)}")
        if self.z_shards > 1 and axes[1] is None:
            raise ValueError(
                f"z_shards={self.z_shards} needs a z mesh axis "
                f"(mesh_axes[1] is None)")
        if self.halo < 0:
            raise ValueError(f"halo must be >= 0, got {self.halo}")
        if self.z_shards == 1 and self.halo != 0:
            raise ValueError(
                f"halo={self.halo} is meaningless with z_shards=1; "
                f"set halo=0")
        if self.comm_blocks < 0:
            raise ValueError(
                f"comm_blocks must be >= 0 (0 = auto), got {self.comm_blocks}")

    @property
    def angle_axis(self) -> str:
        return self.mesh_axes[0]

    @property
    def z_axis(self) -> Optional[str]:
        return self.mesh_axes[1]

    def replace(self, **kw) -> "ShardSpec":
        return dataclasses.replace(self, **kw)

    def _identity(self) -> Tuple:
        return (self.mesh_axes, self.angle_shards, self.z_shards, self.halo,
                self.comm_blocks)


@dataclasses.dataclass(frozen=True, eq=False)
class ProjectorSpec:
    """Frozen, hashable description of one projection operator.

    Fields:
        geom:          scanner geometry (content-hashed — two specs built
                       from equal geometries compare/hash equal even when
                       the geometry objects differ).
        model:         footprint model, ``"sf"`` | ``"joseph"``.
        backend:       ``"auto"`` | ``"pallas"`` | ``"ref"``.
        mode:          packed-kernel policy, ``"auto"`` | ``"exact"`` |
                       ``"packed"`` (cone only; see kernels/ops.py).
        compute_dtype: kernel tile precision, ``"bfloat16"`` | ``"float32"``
                       | None (follow the input dtype); aliases like
                       ``"bf16"`` are canonicalized at construction.
        config:        explicit :class:`~repro.kernels.tune.KernelConfig`
                       pin, or None to let the registry/autotuner resolve.
        shard:         :class:`ShardSpec` describing a multi-device layout,
                       or None for a single-device operator.  A spec with a
                       shard attached must be realized through
                       :class:`repro.core.distributed.DistributedProjector`
                       — the local op cache rejects it (the shard layout
                       changes the compiled program, the collectives, and
                       the halo wiring, none of which a local bundle
                       carries).
    """

    geom: CTGeometry
    model: str = "sf"
    backend: str = "auto"
    mode: str = "auto"
    compute_dtype: Optional[str] = None
    config: Optional["KernelConfig"] = None
    shard: Optional[ShardSpec] = None

    def __post_init__(self):
        # Late imports: repro.kernels imports this module at its top level
        # (ops.py), so the kernels package cannot be imported here eagerly.
        from repro.kernels import precision
        from repro.kernels.tune import KernelConfig
        if not isinstance(self.geom, CTGeometry):
            raise TypeError(
                f"ProjectorSpec.geom must be a CTGeometry, got {self.geom!r}")
        if self.model not in _MODELS:
            raise ValueError(f"unknown projector model {self.model!r}")
        if self.backend not in _BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; expected "
                             f"one of {_BACKENDS}")
        if self.mode not in _MODES:
            raise ValueError(f"unknown mode {self.mode!r}; expected "
                             f"'auto', 'exact' or 'packed'")
        if self.config is not None and not isinstance(self.config, KernelConfig):
            raise TypeError(f"config must be a KernelConfig, "
                            f"got {self.config!r}")
        if self.shard is not None and not isinstance(self.shard, ShardSpec):
            raise TypeError(f"shard must be a ShardSpec, got {self.shard!r}")
        # Validates eagerly (raises ValueError on junk) and canonicalizes
        # aliases ("bf16" -> "bfloat16") so the cache key is stable.
        object.__setattr__(self, "compute_dtype",
                           precision.normalize(self.compute_dtype))

    def replace(self, **kw) -> "ProjectorSpec":
        return dataclasses.replace(self, **kw)

    # -- identity ----------------------------------------------------------- #
    def _identity(self) -> Tuple:
        """Content identity: geometry by canonical hash, the rest by value."""
        return (self.geom.canonical_hash(), self.model, self.backend,
                self.mode, self.compute_dtype, self.config,
                None if self.shard is None else self.shard._identity())

    def __eq__(self, other):
        if not isinstance(other, ProjectorSpec):
            return NotImplemented
        return self._identity() == other._identity()

    def __hash__(self):
        return hash(self._identity())

    # -- keys --------------------------------------------------------------- #
    def cache_key(self, resolved_mode: Optional[str] = None,
                  in_dtype: Optional[str] = None) -> Tuple:
        """The op-cache key (replaces the old ad-hoc tuple in ops.py).

        ``resolved_mode`` is the concrete pair dispatch would pick
        ("exact" | "packed") so that ``mode="auto"`` and an explicit
        equivalent share one bundle; ``in_dtype`` is the dtype name of the
        array the ops are first applied to (a ``compute_dtype=None`` bundle
        follows its input's dtype, so f32 and bf16 callers must not share
        traced closures)."""
        return (self.geom.canonical_hash(), self.model, self.backend,
                self.config, resolved_mode or self.mode, self.compute_dtype,
                in_dtype,
                None if self.shard is None else self.shard._identity())

    def bucket_key(self) -> str:
        """Short stable digest for serving admission: requests whose specs
        share this key are compatible for dynamic batch packing (identical
        geometry content, kernels, mode policy, and precision — one compiled
        executable covers the packed batch)."""
        cfg = (None if self.config is None
               else sorted(dataclasses.asdict(self.config).items()))
        shard = (None if self.shard is None
                 else sorted(dataclasses.asdict(self.shard).items(),
                             key=lambda kv: kv[0]))
        payload = json.dumps(
            [self.geom.canonical_hash(), self.model, self.backend,
             self.mode, self.compute_dtype, cfg, shard])
        return hashlib.sha256(payload.encode()).hexdigest()[:16]

    def __repr__(self):
        g = self.geom
        extras = []
        if self.mode != "auto":
            extras.append(f"mode={self.mode}")
        if self.compute_dtype is not None:
            extras.append(f"compute_dtype={self.compute_dtype}")
        if self.config is not None:
            extras.append(f"config={self.config}")
        if self.shard is not None:
            extras.append(f"shard={self.shard}")
        tail = (", " + ", ".join(extras)) if extras else ""
        return (f"ProjectorSpec({g.geom_type}, model={self.model}, "
                f"backend={self.backend}{tail}, vol={g.vol.shape}, "
                f"sino={g.sino_shape})")


def _require_spec(spec, api: str) -> ProjectorSpec:
    """The one refusal of every entry point: a spec passes, anything else
    (a bare geometry included) raises."""
    if not isinstance(spec, ProjectorSpec):
        raise TypeError(
            f"{api} takes a ProjectorSpec, got {type(spec).__name__}; "
            f"build one with ProjectorSpec(geom, model=..., backend=...)")
    return spec
