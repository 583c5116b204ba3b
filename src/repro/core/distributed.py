"""Distributed CT projection — the paper's operators at pod scale
(beyond-paper contribution; LEAP itself is single-GPU and PYRO-NN's
TensorFlow operators are per-device).

Two orthogonal sharding axes, matching the physics:

* **angle sharding** (data axis): the X-ray transform is a concatenation of
  independent per-view operators, so forward projection is embarrassingly
  parallel over views; the adjoint is a *sum* over views — an all-reduce
  which the backprojector overlaps with compute (see
  ``ShardSpec.comm_blocks``).
* **z-slab sharding** (model axis): axial slabs of the volume.  Three
  regimes, in increasing generality:

  - *parallel / fan*: slabs are exactly independent (rays stay in
    z-planes), so the slab decomposition is communication-free and the
    halo must be 0.
  - *cone* (circular, source at z=0): detector **row blocks** pair with
    volume slabs; a row block's rays diverge into the neighbour slab by at
    most the magnification overshoot, so each shard projects its slab
    extended by a ``halo`` of voxels exchanged with ``halo_exchange_z``.
  - *modular / helical* (**sliding-z pipeline**): the source travels in z,
    so contiguous **view bands** pair with volume slabs — the mesh-level
    lift of the modular kernel's intra-device sliding-z window.  Each
    shard holds only its slab plus halo; a long-object volume that cannot
    fit in one device's memory reconstructs end to end.

Matched-pair note: forward is ``select-rows ∘ local-A ∘ halo-exchange ∘
broadcast`` per shard; the backprojector is the exact term-by-term adjoint
``psum ∘ halo-reduce ∘ local-Aᵀ ∘ inject-rows`` (``halo_reduce_z`` is the
adjoint of ``halo_exchange_z``, psum the adjoint of broadcast), and the
pair is additionally wired through ``jax.custom_vjp`` — so the distributed
pair is exactly matched and differentiable (tested in
tests/test_distributed_ct.py).

API: build a :class:`~repro.core.spec.ProjectorSpec` with a
:class:`~repro.core.spec.ShardSpec` attached and realize it with
:class:`DistributedProjector` (or the :func:`distribute` convenience).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.core.geometry import CTGeometry
from repro.core.spec import ProjectorSpec, ShardSpec, _require_spec
from repro.kernels import ops, tune
from repro.runtime import obs

__all__ = [
    "ShardSpec",
    "DistributedProjector",
    "distribute",
    "suggest_halo",
    "halo_exchange_z",
    "halo_reduce_z",
]


def _angle_chunks(geom: CTGeometry, n: int) -> List[CTGeometry]:
    if geom.n_angles % n != 0:
        raise ValueError(
            f"n_angles={geom.n_angles} must be divisible by the "
            f"{n} angle shards — pad or subset the scan to a multiple "
            f"(e.g. {geom.n_angles - geom.n_angles % n} views)")
    per = geom.n_angles // n
    return [geom.subset(np.arange(i * per, (i + 1) * per)) for i in range(n)]


# --------------------------------------------------------------------------- #
# z-halo collectives (matched pair: reduce is the exact adjoint of exchange)
# --------------------------------------------------------------------------- #
def halo_exchange_z(f, axis: str, halo: int):
    """Exchange z-halos between neighbouring slab shards.

    ``f``: (nx, ny, nz_local) inside ``shard_map``.  Returns ``f`` extended
    to ``nz_local + 2*halo`` with the neighbours' boundary slices (zeros at
    the fleet edges — the world outside the volume has no voxels).  This is
    the production building block of the cone/modular z-slab paths; its
    exact adjoint is :func:`halo_reduce_z`.
    """
    if halo < 0:
        raise ValueError(f"halo must be >= 0, got {halo}")
    if halo == 0:
        return f
    if halo >= f.shape[2]:
        raise ValueError(
            f"halo={halo} must be smaller than the local slab depth "
            f"nz_local={f.shape[2]} (a halo spanning a whole slab would "
            f"need second-neighbour exchange; use fewer z shards)")
    lo = f[:, :, :halo]
    hi = f[:, :, -halo:]
    n = compat.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [((i + 1) % n, i) for i in range(n)]
    from_prev = jax.lax.ppermute(hi, axis, fwd)     # neighbour below's top
    from_next = jax.lax.ppermute(lo, axis, bwd)     # neighbour above's bottom
    obs.count("distributed.collectives", 2)
    from_prev = jnp.where(idx == 0, 0.0, from_prev)
    from_next = jnp.where(idx == n - 1, 0.0, from_next)
    return jnp.concatenate([from_prev, f, from_next], axis=2)


def halo_reduce_z(g, axis: str, halo: int):
    """Exact adjoint of :func:`halo_exchange_z`.

    ``g``: (nx, ny, nz_local + 2*halo) inside ``shard_map`` — a quantity
    accumulated on the halo-extended slab (e.g. a backprojection).  Sends
    each halo slab back to the neighbour that owns those voxels and adds it
    onto their boundary; fleet-edge halos are dropped (they are ghost
    voxels outside the volume).  Returns the owned (nx, ny, nz_local) core.
    """
    if halo < 0:
        raise ValueError(f"halo must be >= 0, got {halo}")
    if halo == 0:
        return g
    if 2 * halo >= g.shape[2]:
        raise ValueError(
            f"halo={halo} inconsistent with extended slab depth "
            f"{g.shape[2]} (needs nz_local = depth - 2*halo >= 1)")
    lo = g[:, :, :halo]                 # contributions to the lower neighbour
    core = g[:, :, halo:-halo]
    hi = g[:, :, -halo:]                # contributions to the upper neighbour
    n = compat.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [((i + 1) % n, i) for i in range(n)]
    from_next = jax.lax.ppermute(lo, axis, bwd)     # neighbour above's lo
    from_prev = jax.lax.ppermute(hi, axis, fwd)     # neighbour below's hi
    obs.count("distributed.collectives", 2)
    from_next = jnp.where(idx == n - 1, 0.0, from_next)
    from_prev = jnp.where(idx == 0, 0.0, from_prev)
    core = core.at[:, :, -halo:].add(from_next)
    core = core.at[:, :, :halo].add(from_prev)
    return core


# --------------------------------------------------------------------------- #
# Halo sizing — conservative world-z extent of a view set's rays
# --------------------------------------------------------------------------- #
def _views_z_extent(geom: CTGeometry, view_idx: np.ndarray,
                    v_lo: float, v_hi: float) -> Tuple[float, float]:
    """Conservative world-z interval touched by the rays of ``view_idx``
    hitting detector rows in ``[v_lo, v_hi]`` (mm, row-coordinate edges).

    Bounds the ray–cylinder chord analytically: with source transaxial
    distance ``|s_xy|``, cylinder radius R, and per-ray transaxial reach
    ``|d_xy|``, the chord parameter lies in ``[(|s_xy|-R)/max|d_xy|,
    (|s_xy|+R)/min|d_xy|]``; z is bilinear in (t, d_z) so corner evaluation
    is exact.  One voxel of margin covers the SF footprint spread.
    """
    vol = geom.vol
    R = vol.radius + max(vol.dx, vol.dz)
    if geom.geom_type == "modular":
        src = np.asarray(geom.source_pos, np.float64)[view_idx]
        ctr = np.asarray(geom.det_center, np.float64)[view_idx]
        eu = np.asarray(geom.det_u, np.float64)[view_idx]
        ev = np.asarray(geom.det_v, np.float64)[view_idx]
    elif geom.geom_type == "cone":
        ang = np.asarray(geom.angles, np.float64)[view_idx]
        c, s = np.cos(ang), np.sin(ang)
        z0 = np.zeros_like(ang)
        src = np.stack([geom.sod * c, geom.sod * s, z0], -1)
        ctr = np.stack([(geom.sod - geom.sdd) * c,
                        (geom.sod - geom.sdd) * s, z0], -1)
        eu = np.stack([-s, c, z0], -1)
        ev = np.stack([z0, z0, np.ones_like(ang)], -1)
    else:
        raise ValueError(
            f"z extent bound only applies to cone/modular geometries, "
            f"got {geom.geom_type!r}")

    u = geom.u_coords()
    u0 = float(u[0]) - geom.pixel_width / 2.0
    u1 = float(u[-1]) + geom.pixel_width / 2.0
    v_abs = max(abs(v_lo), abs(v_hi))

    s_xy = np.hypot(src[:, 0], src[:, 1])
    C = ctr[:, :2] - src[:, :2]                     # transaxial source→center
    E = eu[:, :2]
    ev_xy = np.hypot(ev[:, 0], ev[:, 1])

    def _dxy(uv):
        d = C + uv * E
        return np.hypot(d[:, 0], d[:, 1])

    # |C + uE| over [u0, u1]: convex in u — max at the endpoints, min at the
    # clamped projection u* = -C·E/|E|².
    e2 = np.sum(E * E, axis=1)
    u_star = np.where(e2 > 1e-12, -np.sum(C * E, axis=1) / np.maximum(e2, 1e-12),
                      0.0)
    u_star = np.clip(u_star, u0, u1)
    d_star = np.hypot(C[:, 0] + u_star * E[:, 0], C[:, 1] + u_star * E[:, 1])
    dxy_min = np.minimum(d_star, np.minimum(_dxy(u0), _dxy(u1)))
    dxy_max = np.maximum(_dxy(u0), _dxy(u1))
    # A tilted row axis moves pixels transaxially by up to |v|·|ev_xy|.
    dxy_min = np.maximum(dxy_min - v_abs * ev_xy, 1e-6)
    dxy_max = dxy_max + v_abs * ev_xy

    t_lo = np.maximum(s_xy - R, 0.0) / dxy_max
    t_hi = (s_xy + R) / dxy_min

    # d_z over the (u, v) rectangle: linear, so corner evaluation is exact.
    base = ctr[:, 2] - src[:, 2]
    dz_terms = [base + uu * eu[:, 2] + vv * ev[:, 2]
                for uu in (u0, u1) for vv in (v_lo, v_hi)]
    dz_min = np.minimum.reduce(dz_terms)
    dz_max = np.maximum.reduce(dz_terms)

    cand = [t * d for t in (t_lo, t_hi) for d in (dz_min, dz_max)]
    z_min = np.min(src[:, 2] + np.minimum.reduce(cand)) - vol.dz
    z_max = np.max(src[:, 2] + np.maximum.reduce(cand)) + vol.dz
    return float(z_min), float(z_max)


def suggest_halo(geom: CTGeometry, z_shards: int) -> int:
    """Smallest safe z-halo (voxels) for slab-sharding ``geom`` over
    ``z_shards`` devices: cone pairs detector row blocks with slabs,
    modular/helical pairs contiguous view bands with slabs (the sliding-z
    assignment).  Conservative — derived from the analytic ray-extent bound
    in :func:`_views_z_extent`, clamped to the volume.  Returns 0 for
    parallel/fan (exact slab independence) and for ``z_shards <= 1``.
    """
    if z_shards <= 1 or geom.geom_type in ("parallel", "fan"):
        return 0
    vol = geom.vol
    if vol.nz % z_shards != 0:
        raise ValueError(
            f"vol.nz={vol.nz} must be divisible by z_shards={z_shards}")
    nzl = vol.nz // z_shards
    zc = vol.z_coords()
    dz = vol.dz
    vol_lo, vol_hi = float(zc[0]) - dz / 2, float(zc[-1]) + dz / 2
    v = geom.v_coords()
    dv = geom.pixel_height
    need = 0
    for k in range(z_shards):
        if geom.geom_type == "cone":
            if geom.n_rows % z_shards != 0:
                raise ValueError(
                    f"n_rows={geom.n_rows} must be divisible by "
                    f"z_shards={z_shards} for cone row-block slabs")
            nvl = geom.n_rows // z_shards
            v_lo = float(v[k * nvl]) - dv / 2
            v_hi = float(v[(k + 1) * nvl - 1]) + dv / 2
            idx = np.arange(geom.n_angles)
        else:
            if geom.n_angles % z_shards != 0:
                raise ValueError(
                    f"n_angles={geom.n_angles} must be divisible by "
                    f"z_shards={z_shards} for sliding-z view bands")
            band = geom.n_angles // z_shards
            idx = np.arange(k * band, (k + 1) * band)
            v_lo = float(v[0]) - dv / 2
            v_hi = float(v[-1]) + dv / 2
        z_min, z_max = _views_z_extent(geom, idx, v_lo, v_hi)
        z_min, z_max = max(z_min, vol_lo), min(z_max, vol_hi)
        slab_lo = float(zc[k * nzl]) - dz / 2
        slab_hi = float(zc[(k + 1) * nzl - 1]) + dz / 2
        need = max(need,
                   int(math.ceil(max(slab_lo - z_min, 0.0) / dz)),
                   int(math.ceil(max(z_max - slab_hi, 0.0) / dz)))
    return need


# --------------------------------------------------------------------------- #
# Layout construction
# --------------------------------------------------------------------------- #
def _ext_slab_vol(vol, z_shards: int, k: int, halo: int):
    """The halo-extended slab sub-volume of shard ``k`` — same voxel grid as
    the corresponding world-z window of the global volume (frames and cone
    sources are world-space, so only the volume block changes)."""
    nzl = vol.nz // z_shards
    start = k * nzl - halo
    length = nzl + 2 * halo
    off = (start + (length - 1) / 2.0 - (vol.nz - 1) / 2.0) * vol.dz \
        + vol.offset_z
    return dataclasses.replace(vol, nz=length, offset_z=off)


def _row_block_geom(geom: CTGeometry, z_shards: int, k: int) -> CTGeometry:
    """Geometry restricted to detector row block ``k`` (cone z-slabs)."""
    nvl = geom.n_rows // z_shards
    cr = geom.center_row + geom.pixel_height * (
        k * nvl + (nvl - 1) / 2.0 - (geom.n_rows - 1) / 2.0)
    return dataclasses.replace(geom, n_rows=nvl, center_row=cr)


def _auto_comm_blocks(per: int, lgeom: CTGeometry,
                      config) -> int:
    """Comm granularity for the overlap schedule: the most blocks (<= 4)
    that keep every block a whole number of ``bab`` view-blocks — the BP
    kernels' own view-blocking is the natural unit the reduction can
    overlap.  The caller takes one block where the angle axis has one
    shard: there is no reduction to overlap."""
    cfg = config if config is not None else tune.heuristic_config(lgeom)
    bab = max(1, cfg.bab or 1)
    for nb in (4, 3, 2):
        if per % nb == 0 and (per // nb) % bab == 0:
            return nb
    return 1


def _validate_mesh(shard: ShardSpec, mesh: Mesh) -> None:
    for ax, n, what in ((shard.angle_axis, shard.angle_shards, "angle"),
                        (shard.z_axis, shard.z_shards, "z")):
        if ax is None:
            continue
        if ax not in mesh.shape:
            raise ValueError(
                f"mesh has no axis {ax!r} (axes: {tuple(mesh.axis_names)}); "
                f"fix ShardSpec.mesh_axes or the mesh")
        if int(mesh.shape[ax]) != n:
            raise ValueError(
                f"ShardSpec.{what}_shards={n} does not match mesh axis "
                f"{ax!r} of size {int(mesh.shape[ax])}")


@obs.spanned("distributed.build")
def _build_distributed(spec: ProjectorSpec, mesh: Mesh):
    """Compile the sharded matched pair for ``spec`` on ``mesh``.

    Returns ``(fp, bp, spec_vol, spec_sino, comm_blocks)`` where fp/bp are
    a ``custom_vjp`` matched pair of ``shard_map`` programs and
    ``comm_blocks`` the number of BP blocks per shard.

    Program counters: ``distributed.local_ops`` (local op bundles asked
    for) and ``distributed.collectives`` (``psum`` and ``ppermute`` calls
    traced into the pair; registered at 0 here, so a layout that emits
    none reads 0).  The shard bodies' Python tracing is timed by the spans
    ``distributed.fp`` and ``distributed.bp``.
    """
    shard = spec.shard
    geom = spec.geom
    _validate_mesh(shard, mesh)
    aax, zax = shard.angle_axis, shard.z_axis
    na, nz = shard.angle_shards, shard.z_shards
    halo = shard.halo
    gt = geom.geom_type
    vol = geom.vol

    if nz > 1:
        if vol.nz % nz != 0:
            raise ValueError(
                f"vol.nz={vol.nz} must be divisible by z_shards={nz} "
                f"(pad the volume or change the mesh)")
        nzl = vol.nz // nz
        if gt in ("parallel", "fan"):
            if geom.n_rows % nz != 0:
                raise ValueError(
                    f"n_rows={geom.n_rows} must be divisible by "
                    f"z_shards={nz} for {gt} z-slabs")
            if halo != 0:
                raise ValueError(
                    f"{gt} z-slabs are exactly independent (rays stay in "
                    f"z-planes); halo must be 0, got {halo}")
        elif gt == "cone":
            if geom.n_rows % nz != 0:
                raise ValueError(
                    f"n_rows={geom.n_rows} must be divisible by "
                    f"z_shards={nz} (cone slabs pair with detector row "
                    f"blocks)")
        if gt in ("cone", "modular"):
            need = suggest_halo(geom, nz)
            if need >= nzl:
                raise ValueError(
                    f"{gt} z-slab sharding infeasible: the rays of a "
                    f"shard's {'view band' if gt == 'modular' else 'row block'} "
                    f"span {need} voxels beyond its slab, but the halo must "
                    f"stay below nz_local={nzl}; use fewer z shards "
                    f"(or angle sharding only)")
            if halo < need:
                raise ValueError(
                    f"halo={halo} too small for this geometry: the widest "
                    f"shard's rays reach {need} voxels into the neighbour "
                    f"slab — pass halo>={need} (suggest_halo(geom, "
                    f"z_shards) computes this)")
            if halo >= nzl:
                raise ValueError(
                    f"halo={halo} must be < nz_local={nzl} "
                    f"(single-neighbour exchange)")

    sliding_z = gt == "modular" and nz > 1

    # ---- view assignment + per-shard local geometries -------------------- #
    if sliding_z:
        if geom.n_angles % (na * nz) != 0:
            raise ValueError(
                f"n_angles={geom.n_angles} must be divisible by "
                f"angle_shards*z_shards={na * nz} for the sliding-z "
                f"pipeline (z bands × angle chunks)")
        per = geom.n_angles // (na * nz)
        band = geom.n_angles // nz
        # branch order: flat = iz * na + ia  <->  P((z, angle)) on views
        chunk_geoms = []
        for k in range(nz):
            evol = _ext_slab_vol(vol, nz, k, halo)
            for a in range(na):
                g = geom.subset(np.arange(k * band + a * per,
                                          k * band + (a + 1) * per))
                chunk_geoms.append(dataclasses.replace(g, vol=evol))
        spec_sino = P((zax, aax), None, None)
    else:
        chunks = _angle_chunks(geom, na)
        per = geom.n_angles // na
        if nz > 1 and gt == "cone":
            chunk_geoms = []
            for k in range(nz):
                evol = _ext_slab_vol(vol, nz, k, halo)
                for a in range(na):
                    g = _row_block_geom(chunks[a], nz, k)
                    chunk_geoms.append(dataclasses.replace(g, vol=evol))
        elif nz > 1:
            # parallel/fan: slabs are translation-invariant in z — one op
            # per angle chunk serves every slab shard.
            vol_local = dataclasses.replace(vol, nz=vol.nz // nz)
            chunk_geoms = [
                dataclasses.replace(c, vol=vol_local,
                                    n_rows=geom.n_rows // nz)
                for c in chunks]
        else:
            chunk_geoms = chunks
        spec_sino = P(aax, zax, None)
    spec_vol = P(None, None, zax)
    z_branched = sliding_z or (nz > 1 and gt == "cone")

    # ---- local op bundles ------------------------------------------------ #
    def _local_ops(g: CTGeometry):
        obs.count("distributed.local_ops")
        return ops.get_ops(spec.replace(geom=g, shard=None))

    bundles = [_local_ops(g) for g in chunk_geoms]
    local_fps = [fp for fp, _ in bundles]

    reduce = na > 1
    if shard.comm_blocks:
        nb = shard.comm_blocks
    else:
        nb = _auto_comm_blocks(per, chunk_geoms[0], spec.config) \
            if reduce else 1
    if per % nb != 0:
        raise ValueError(
            f"comm_blocks={nb} must divide the per-shard view count {per}")
    blk = per // nb
    if nb == 1:
        local_bps = [[bp for _, bp in bundles]]
    else:
        local_bps = [
            [_local_ops(g.subset(np.arange(b * blk, (b + 1) * blk)))[1]
             for g in chunk_geoms]
            for b in range(nb)]

    def _flat_idx():
        ia = jax.lax.axis_index(aax)
        if z_branched:
            return jax.lax.axis_index(zax) * na + ia
        return ia

    use_halo = halo > 0 and nz > 1
    obs.count("distributed.collectives", 0)     # 0 until one is traced

    @partial(compat.shard_map, mesh=mesh, in_specs=(spec_vol,),
             out_specs=spec_sino, check_vma=False)
    def _fp(f_local):
        with obs.span("distributed.fp"):
            x = f_local
            if use_halo:
                with jax.named_scope("dist_halo_exchange"):
                    x = halo_exchange_z(f_local, zax, halo)
            with jax.named_scope("dist_local_fp"):
                return jax.lax.switch(_flat_idx(), local_fps, x)

    @partial(compat.shard_map, mesh=mesh, in_specs=(spec_sino,),
             out_specs=spec_vol, check_vma=False)
    def _bp(p_local):
        # Overlap-communication schedule: one psum per comm block, issued
        # between the per-block Pallas backprojections — block b's
        # all-reduce is independent of block b+1's compute, so the XLA
        # async collectives hide the reduction behind the kernels.  With
        # comm_blocks=1 this is a single synchronous psum after the whole
        # local backprojection; with one angle shard there is nothing to
        # reduce and no psum.
        with obs.span("distributed.bp"):
            idx = _flat_idx()
            acc = None
            for b in range(nb):
                pb = p_local[b * blk:(b + 1) * blk] if nb > 1 else p_local
                with jax.named_scope("dist_local_bp"):
                    part = jax.lax.switch(idx, local_bps[b], pb)
                if reduce:
                    with jax.named_scope("dist_reduce"):
                        part = jax.lax.psum(part, aax)
                    obs.count("distributed.collectives")
                acc = part if acc is None else acc + part
            if use_halo:
                with jax.named_scope("dist_halo_reduce"):
                    acc = halo_reduce_z(acc, zax, halo)
            return acc

    # jit *inside* the custom_vjp pair: an eager shard_map re-traces the
    # whole mesh program on every call, which dominates any real workload.
    fp, bp = ops._make_pair(jax.jit(_fp), jax.jit(_bp))
    return fp, bp, spec_vol, spec_sino, nb


# --------------------------------------------------------------------------- #
# Public objects
# --------------------------------------------------------------------------- #
class DistributedProjector:
    """A matched differentiable projector pair laid out on a device mesh.

    Built from a :class:`ProjectorSpec` with a :class:`ShardSpec` attached::

        spec = ProjectorSpec(geom, shard=ShardSpec(("data", "model"),
                                                   angle_shards=4,
                                                   z_shards=2, halo=2))
        dp = DistributedProjector(spec, mesh)
        sino = dp(dp.shard_volume(f))       # A x, sharded
        vol  = dp.T(sino)                   # A^T y, sharded

    The object quacks like :class:`~repro.core.projector.Projector` — the
    iterative solvers (``sirt``/``cgls``/``fista_tv``) accept it directly,
    so distributed reconstruction needs no solver forks.
    """

    def __init__(self, spec: ProjectorSpec, mesh: Mesh):
        _require_spec(spec, "DistributedProjector")
        if spec.shard is None:
            raise ValueError(
                "spec has no ShardSpec attached; pass "
                "ProjectorSpec(geom, ..., shard=ShardSpec(...)) or use "
                "distribute(spec, mesh, ...)")
        self.spec = spec
        self.mesh = mesh
        (self.fp, self.bp, self._spec_vol, self._spec_sino,
         self.comm_blocks) = _build_distributed(spec, mesh)

    # -- Projector-compatible surface -------------------------------------- #
    @property
    def geom(self) -> CTGeometry:
        return self.spec.geom

    @property
    def shard(self) -> ShardSpec:
        return self.spec.shard

    def __call__(self, volume):
        return self.fp(volume)

    forward = __call__

    def backproject(self, sino):
        return self.bp(sino)

    @property
    def T(self):
        return self.backproject

    def vol_shape(self):
        return self.geom.vol.shape

    def sino_shape(self):
        return self.geom.sino_shape

    def data_consistency(self, volume, measured, mask=None):
        """0.5 * || M (A x - y) ||^2 / n with the sharded operator."""
        r = self(volume) - measured
        if mask is not None:
            r = r * mask
        return 0.5 * jnp.mean(jnp.square(r))

    # -- placement helpers -------------------------------------------------- #
    def shard_volume(self, f):
        """Place a global (nx, ny, nz) volume in the mesh layout."""
        return jax.device_put(f, NamedSharding(self.mesh, self._spec_vol))

    def shard_sino(self, p):
        """Place a global (n_angles, n_rows, n_cols) sinogram in the mesh
        layout (views z-band-major for the sliding-z pipeline)."""
        return jax.device_put(p, NamedSharding(self.mesh, self._spec_sino))

    def __repr__(self):
        s = self.shard
        return (f"DistributedProjector({self.geom.geom_type}, "
                f"angle_shards={s.angle_shards}, z_shards={s.z_shards}, "
                f"halo={s.halo}, vol={self.geom.vol.shape}, "
                f"sino={self.geom.sino_shape})")


def distribute(spec: ProjectorSpec, mesh: Mesh, *,
               angle_axis: str = "data", z_axis: Optional[str] = None,
               halo: Optional[int] = None,
               comm_blocks: int = 0) -> DistributedProjector:
    """Attach a mesh-derived :class:`ShardSpec` to ``spec`` and build the
    :class:`DistributedProjector`.

    ``halo=None`` sizes the z-halo automatically via :func:`suggest_halo`
    (0 for parallel/fan).  A spec that already carries a shard passes
    through unchanged (mixing it with layout kwargs raises).
    """
    _require_spec(spec, "distribute()")
    if spec.shard is not None:
        if (angle_axis, z_axis, halo, comm_blocks) != ("data", None, None, 0):
            raise TypeError(
                "distribute(): pass either a spec with a ShardSpec or "
                "layout kwargs, not both")
        return DistributedProjector(spec, mesh)
    z_shards = int(mesh.shape[z_axis]) if z_axis else 1
    if halo is None:
        halo = suggest_halo(spec.geom, z_shards)
    shard = ShardSpec(mesh_axes=(angle_axis, z_axis),
                      angle_shards=int(mesh.shape[angle_axis]),
                      z_shards=z_shards, halo=halo, comm_blocks=comm_blocks)
    return DistributedProjector(spec.replace(shard=shard), mesh)

