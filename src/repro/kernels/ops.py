"""Public differentiable projection ops.

``forward_project`` / ``back_project`` are linear maps wired together as a
*matched pair* through ``jax.custom_vjp``:

    d/df ||forward_project(f) - y||^2  ==  2 * back_project(forward_project(f) - y)

exactly (not approximately), which is the stability requirement the paper
places on iterative/DL use.  The VJP of the forward op *is* the back op and
vice versa, so autodiff never differentiates through the projector internals.

Backends:
    * ``ref``    — pure-jnp oracles (runs everywhere; the CPU path).
    * ``pallas`` — Pallas TPU kernels (``interpret=True`` on CPU for tests).
      Parallel, fan, cone, and (axial-frame) modular SF pairs are all
      Pallas matched pairs — each registered BP is the exact transpose of
      its FP kernel, so training steps stay on-kernel end to end for every
      geometry, including helical trajectories.
    * ``auto``   — pallas for geometry/model pairs with a kernel whose
      ``supports`` gate (if any) accepts the geometry, else ref.

Batching: kernels may register *batched* variants that fold a leading batch
dimension into the TPU lane axis (see ``fp_par.py``); when present these
replace the per-sample ``jax.vmap`` over the ``pallas_call`` — the vmap path
remains the fallback for the ref backend and batch-unaware kernels.

Modes: a kernel entry may additionally register an approximate *packed*
pair (cone: the lane-packed axial pre-resample, ``fp_cone.fp_cone_packed``)
guarded by a per-geometry predicate.  ``mode="exact"`` always uses the
exact pair, ``mode="packed"`` forces the packed one, and the default
``mode="auto"`` dispatches packed only when the registered gate
(``tune.packed_cone_ok`` — the derived error bound under tolerance)
accepts the geometry.  Both pairs are matched custom_vjp pairs, so
gradients stay exactly consistent in every mode.

Precision: every entry point takes ``compute_dtype`` ("bfloat16" |
"float32" | None = follow the input dtype) implementing the bf16-tile /
f32-accumulate policy of :mod:`repro.kernels.precision`; the ref backend
applies the matching quantize-data-only policy so oracles stay
dtype-matched.

Specs: every entry point canonically takes a
:class:`repro.core.spec.ProjectorSpec` — the frozen consolidation of
``(geom, model, backend, mode, compute_dtype, config)``.  Geometry-first
calls (``get_ops(geom, model=...)``) keep working through the deprecation
shim in :mod:`repro.core.spec` (one warning per entry point per process).

Tile/block sizes come from :class:`repro.kernels.tune.KernelConfig`; pass
``config=`` to pin one explicitly (it becomes part of the op-cache key, so a
fixed config never retraces).  The op cache is a bounded LRU keyed on
``spec.cache_key()`` — geometry *content* (``CTGeometry.canonical_hash()``)
plus model/backend/config/resolved-mode and the dtype pair (normalized
compute policy, input dtype) — so equal geometries share ops and evicted
entries release both the traced functions and the geometry they close over.
:func:`cache_stats` exposes the size and the hit and miss counters
(``ops.cache_hit`` and ``ops.cache_miss`` in :mod:`repro.runtime.obs`,
beside the spans ``ops.get_bundle`` and ``ops.build``); the serving layer's
warm-path guarantee ("a primed server never compiles on the request path")
is asserted against them.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.geometry import CTGeometry
from repro.core.spec import ProjectorSpec, as_spec
from repro.kernels import precision, ref, tune
from repro.runtime import obs


class _KernelEntry(NamedTuple):
    """A registered Pallas kernel pair (+ optional lane-packed batched pair
    and, for cone, an approximate *packed* pair gated by ``packed_ok``)."""
    fp: Callable
    bp: Callable
    fp_batched: Optional[Callable] = None
    bp_batched: Optional[Callable] = None
    fp_packed: Optional[Callable] = None
    bp_packed: Optional[Callable] = None
    packed_ok: Optional[Callable] = None     # geom -> bool (mode="auto" gate)
    supports: Optional[Callable] = None      # geom -> bool (kernel coverage)


# {(geom_type, model): _KernelEntry} — filled by the kernels package on import
_KERNEL_TABLE: Dict[Tuple[str, str], _KernelEntry] = {}

_MODES = ("auto", "exact", "packed")


def register_kernel(geom_type: str, model: str, fp: Callable, bp: Callable,
                    fp_batched: Optional[Callable] = None,
                    bp_batched: Optional[Callable] = None,
                    fp_packed: Optional[Callable] = None,
                    bp_packed: Optional[Callable] = None,
                    packed_ok: Optional[Callable] = None,
                    supports: Optional[Callable] = None):
    """Register a Pallas kernel pair.  All callables take
    ``(array, geom, config=KernelConfig|None, compute_dtype=None)`` — the
    precision policy of kernels/precision.py is part of the registration
    contract; the batched variants accept a leading batch dimension and fold
    it into the kernel (lane packing or view-axis folding) instead of
    requiring an outer vmap.

    ``fp_packed``/``bp_packed`` register an *approximate* matched pair (the
    lane-packed cone pre-resample) selected by ``mode="packed"`` or by
    ``mode="auto"`` when ``packed_ok(geom)`` holds (the per-geometry error
    bound stays under tolerance).

    ``supports`` restricts the entry to a geometry subclass (modular: axial
    frames): ``backend="auto"`` falls back to the ref oracle when it
    rejects a geometry; an explicit ``backend="pallas"`` still dispatches
    and lets the kernel raise its own informative error."""
    _KERNEL_TABLE[(geom_type, model)] = _KernelEntry(
        fp, bp, fp_batched, bp_batched, fp_packed, bp_packed, packed_ok,
        supports)


class Ops(NamedTuple):
    """Matched differentiable op bundle for one (geometry, model, backend)."""
    fp: Callable
    bp: Callable
    fp_batched: Optional[Callable]
    bp_batched: Optional[Callable]
    config: Optional[tune.KernelConfig]


def _make_pair(raw_fp: Callable, raw_bp: Callable) -> Tuple[Callable, Callable]:
    """Wire (A, A^T) together so each is the other's VJP."""

    @jax.custom_vjp
    def fp(f):
        return raw_fp(f)

    def fp_fwd(f):
        return raw_fp(f), None

    def fp_bwd(_, g):
        return (raw_bp(g),)

    fp.defvjp(fp_fwd, fp_bwd)

    @jax.custom_vjp
    def bp(p):
        return raw_bp(p)

    def bp_fwd(p):
        return raw_bp(p), None

    def bp_bwd(_, g):
        return (raw_fp(g),)

    bp.defvjp(bp_fwd, bp_bwd)
    return fp, bp


def _use_pallas(geom: CTGeometry, model: str, backend: str) -> bool:
    # "auto": use the Pallas kernels on TPU; the pure-jnp path elsewhere
    # (interpret-mode Pallas is for correctness tests, not production CPU use)
    # and for geometries the registered kernel does not support.  Callers
    # that must run on the kernels check :func:`resolve_backend`.
    if backend == "pallas":
        return True
    entry = _KERNEL_TABLE.get((geom.geom_type, model))
    return (backend == "auto" and entry is not None
            and (entry.supports is None or entry.supports(geom))
            and jax.default_backend() == "tpu")


def _resolve_mode(geom: CTGeometry, model: str, mode: str,
                  use_pallas: bool) -> str:
    """Collapse ``mode`` to the concrete pair that will dispatch
    ("exact" | "packed")."""
    if mode not in _MODES:
        raise ValueError(f"unknown mode {mode!r}; expected one of {_MODES}")
    if mode == "exact":
        return "exact"
    entry = _KERNEL_TABLE.get((geom.geom_type, model))
    has_packed = (use_pallas and entry is not None
                  and entry.fp_packed is not None
                  and entry.bp_packed is not None)
    if mode == "packed":
        if not has_packed:
            raise NotImplementedError(
                f"mode='packed' needs a registered packed kernel pair for "
                f"({geom.geom_type}, {model}) on the pallas backend")
        return "packed"
    # "auto": packed only when the registered gate accepts the geometry
    # (the per-geometry error bound is under tolerance).
    if has_packed and entry.packed_ok is not None and entry.packed_ok(geom):
        return "packed"
    return "exact"


def resolve_mode(geom, model: str = "sf", backend: str = "auto",
                 mode: str = "auto") -> str:
    """The concrete kernel mode ("exact" | "packed") that
    ``forward_project``/``back_project`` would dispatch for these arguments —
    exposed so callers (and tests) can observe the ``mode="auto"`` policy
    without probing numerics.  Accepts a ProjectorSpec or a geometry (this
    is a read-only probe, so the geometry form is not deprecated here)."""
    if isinstance(geom, ProjectorSpec):
        geom, model, backend, mode = (geom.geom, geom.model, geom.backend,
                                      geom.mode)
    return _resolve_mode(geom, model, mode, _use_pallas(geom, model, backend))


def resolve_backend(spec_or_geom, model: str = "sf",
                    backend: str = "auto") -> str:
    """The backend ("pallas" | "ref") that ``forward_project`` /
    ``back_project`` would dispatch: ``backend="auto"`` resolves to "ref"
    off the TPU and for geometries outside a kernel's ``supports`` gate, so
    a caller that must run on the chip's kernels asserts on this.  Accepts
    a ProjectorSpec or a geometry (a read-only probe, like
    :func:`resolve_mode`)."""
    if isinstance(spec_or_geom, ProjectorSpec):
        geom, model, backend = (spec_or_geom.geom, spec_or_geom.model,
                                spec_or_geom.backend)
    else:
        geom = spec_or_geom
    return "pallas" if _use_pallas(geom, model, backend) else "ref"


@obs.spanned("ops.build")
def _build(geom: CTGeometry, model: str, backend: str,
           config: Optional[tune.KernelConfig], use_pallas: bool,
           mode: str, compute_dtype) -> Ops:
    fp_b = bp_b = None
    cdt = compute_dtype
    if use_pallas:
        key = (geom.geom_type, model)
        if key not in _KERNEL_TABLE:
            raise NotImplementedError(f"no pallas kernel for {key}")
        entry = _KERNEL_TABLE[key]
        # An explicit user config is pinned; config=None flows through so
        # the kernel entry points resolve against the *actual* input batch
        # size and dtype (batch-/dtype-aware shape classes and autotune).
        if mode == "packed":
            # The packed pair lane-packs batches natively (3D and 4D inputs
            # through the same entry points).
            raw_fp = lambda f: entry.fp_packed(f, geom, config=config,
                                               compute_dtype=cdt)
            raw_bp = lambda p: entry.bp_packed(p, geom, config=config,
                                               compute_dtype=cdt)
            fp_b, bp_b = _make_pair(raw_fp, raw_bp)
        else:
            raw_fp = lambda f: entry.fp(f, geom, config=config,
                                        compute_dtype=cdt)
            raw_bp = lambda p: entry.bp(p, geom, config=config,
                                        compute_dtype=cdt)
            if entry.fp_batched is not None and entry.bp_batched is not None:
                fp_b, bp_b = _make_pair(
                    lambda f: entry.fp_batched(f, geom, config=config,
                                               compute_dtype=cdt),
                    lambda p: entry.bp_batched(p, geom, config=config,
                                               compute_dtype=cdt))
    else:
        raw_fp = lambda f: ref.forward(f, geom, model, dtype=cdt)
        raw_bp = lambda p: ref.adjoint(p, geom, model, dtype=cdt)
    fp, bp = _make_pair(raw_fp, raw_bp)
    return Ops(fp, bp, fp_b, bp_b, config)


# Bounded LRU over op bundles.  Keys are ``spec.cache_key()`` — geometry
# *content* (not object identity), so two equal geometries share one entry,
# and eviction drops the traced ops together with the geometry captured in
# their closures.
_OPS_CACHE: "OrderedDict[Tuple, Ops]" = OrderedDict()
_OPS_CACHE_SIZE = 256


@obs.spanned("ops.get_bundle")
def _get_bundle(spec: ProjectorSpec, in_dtype=None) -> Ops:
    if spec.shard is not None:
        raise ValueError(
            "spec carries a ShardSpec — the local op cache cannot realize "
            "a sharded layout; build DistributedProjector(spec, mesh) "
            "(repro.core.distributed), or drop the shard with "
            "spec.replace(shard=None) for single-device ops")
    geom = spec.geom
    use_pallas = _use_pallas(geom, spec.model, spec.backend)
    rmode = _resolve_mode(geom, spec.model, spec.mode, use_pallas)
    # The cache is keyed on the *user's* config value: None means "let the
    # kernel resolve per call" (note: re-registering configs after a bundle
    # is cached requires clear_cache() to take effect on the None key).
    # Mode is keyed on the *resolved* value so "auto" and an explicit
    # "packed"/"exact" share one bundle when they dispatch the same pair.
    # Dtype is part of the content key: the normalized compute policy (a
    # spec field) plus the input dtype the bundle was first applied to — a
    # cdt=None bundle follows its input's dtype, so f32 and bf16 callers
    # must not share traced closures (and even fixed-cdt bundles key the
    # input dtype so the output dtype stays caller-consistent).
    idt = None if in_dtype is None else jnp.dtype(in_dtype).name
    key = spec.cache_key(rmode, idt)
    hit = _OPS_CACHE.get(key)
    if hit is not None:
        obs.count("ops.cache_hit")
        _OPS_CACHE.move_to_end(key)
        return hit
    obs.count("ops.cache_miss")
    bundle = _build(geom, spec.model, spec.backend, spec.config, use_pallas,
                    rmode, spec.compute_dtype)
    _OPS_CACHE[key] = bundle
    while len(_OPS_CACHE) > _OPS_CACHE_SIZE:
        _OPS_CACHE.popitem(last=False)
    return bundle


def clear_cache() -> None:
    """Drop every cached op bundle (e.g. after re-registering configs)."""
    _OPS_CACHE.clear()


def cache_stats() -> Dict[str, int]:
    """Op-cache observability: ``{"size", "hits", "misses"}``.

    The serving layer's warm-path guarantee is checked against these — on a
    warm server, request traffic must add zero entries and zero misses."""
    return {"size": len(_OPS_CACHE), "hits": obs.counter("ops.cache_hit"),
            "misses": obs.counter("ops.cache_miss")}


def get_ops(spec_or_geom, model: str = "sf", backend: str = "auto",
            config: Optional[tune.KernelConfig] = None,
            mode: str = "auto", compute_dtype=None) -> Tuple[Callable, Callable]:
    """Return the (forward, back) matched differentiable pair for a spec.

    Canonical form: ``get_ops(ProjectorSpec(geom, ...))``.  The legacy
    geometry-first form (``get_ops(geom, model=..., ...)``) still works via
    the deprecation shim in :mod:`repro.core.spec`.

    ``mode`` selects between the exact kernels and an approximate *packed*
    pair where one is registered (cone): "exact" forces the exact pair,
    "packed" forces the packed pair (error ignored), "auto" uses packed only
    when the per-geometry error bound is under tolerance
    (``tune.packed_cone_ok``).  The packed pair is matched (exact transpose
    of itself), so gradients stay consistent in every mode.

    ``compute_dtype`` sets the kernels' tile precision ("bfloat16" |
    "float32"; None follows the input dtype) — accumulation is always f32
    and outputs keep the caller's dtype (see kernels/precision.py).

    Repeated calls with an equal spec return the *same* function objects, so
    jit caches built around them never retrace."""
    spec = as_spec(spec_or_geom, "get_ops", model=model, backend=backend,
                   mode=mode, compute_dtype=compute_dtype, config=config)
    bundle = _get_bundle(spec)
    return bundle.fp, bundle.bp


def _batched(op: Callable, x, vol_ndim_in: int):
    """Apply op over optional leading batch dims (generic vmap fallback)."""
    extra = x.ndim - vol_ndim_in
    if extra == 0:
        return op(x)
    if extra == 1:
        return jax.vmap(op)(x)
    lead = x.shape[:extra]
    flat = x.reshape((-1,) + x.shape[extra:])
    out = jax.vmap(op)(flat)
    return out.reshape(lead + out.shape[1:])


def _apply(op: Callable, op_batched: Optional[Callable], x, ndim_in: int):
    """Dispatch to the kernel's native batched path when one is registered;
    vmap per sample otherwise."""
    extra = x.ndim - ndim_in
    if extra == 0:
        return op(x)
    if op_batched is None:
        return _batched(op, x, ndim_in)
    lead = x.shape[:extra]
    flat = x if extra == 1 else x.reshape((-1,) + x.shape[extra:])
    out = op_batched(flat)
    return out if extra == 1 else out.reshape(lead + out.shape[1:])


def forward_project(f, spec_or_geom, model: str = "sf",
                    backend: str = "auto",
                    config: Optional[tune.KernelConfig] = None,
                    mode: str = "auto", compute_dtype=None):
    """A @ f.  ``f``: (..., nx, ny, nz) -> (..., n_angles, n_rows, n_cols).

    Canonical form: ``forward_project(f, ProjectorSpec(geom, ...))``; the
    geometry-first form survives via the deprecation shim."""
    spec = as_spec(spec_or_geom, "forward_project", model=model,
                   backend=backend, mode=mode, compute_dtype=compute_dtype,
                   config=config)
    b = _get_bundle(spec, in_dtype=f.dtype)
    return _apply(b.fp, b.fp_batched, f, 3)


def back_project(p, spec_or_geom, model: str = "sf",
                 backend: str = "auto",
                 config: Optional[tune.KernelConfig] = None,
                 mode: str = "auto", compute_dtype=None):
    """A^T @ p.  ``p``: (..., n_angles, n_rows, n_cols) -> (..., nx, ny, nz).

    Canonical form: ``back_project(p, ProjectorSpec(geom, ...))``; the
    geometry-first form survives via the deprecation shim."""
    spec = as_spec(spec_or_geom, "back_project", model=model,
                   backend=backend, mode=mode, compute_dtype=compute_dtype,
                   config=config)
    b = _get_bundle(spec, in_dtype=p.dtype)
    return _apply(b.bp, b.bp_batched, p, 3)
