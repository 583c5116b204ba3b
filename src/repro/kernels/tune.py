"""Kernel tile/block configuration: registry + heuristics + autotuner.

The Pallas projector kernels are parameterized by six tile sizes:

    bu   FP: detector-column tile (sublane axis of the output tile); the
         exact cone FP has none (it tiles the gathered axis by ``bg``)
    bv   lane tile — the 128-wide axis.  With lane packing this axis holds
         ``batch * n_rows`` detector-row lanes, so thin-z training batches
         fill the MXU instead of padding it.
    ba   FP: views per program.  The volume line (the dominant HBM stream)
         is fetched once per program and reused for ``ba`` views.
    bg   BP: gathered-axis (voxel) tile; also the exact cone FP's tile,
         as that FP is the cone BP's transpose.
    bab  BP: views per program — one wide sinogram-stripe DMA and a single
         output-tile accumulation per ``bab`` views.
    bs   BP: stripe reuse — gathered-axis sub-tiles served per sinogram
         stripe residency.  Each program covers ``bs * bg`` voxels, so one
         ``bab``-view stripe (double-buffered by the Pallas pipeline) is
         reused ``bs`` times before eviction instead of being re-fetched
         per gathered tile; the per-sub-tile detector window stays sized
         by ``bg``, so weight tiles do not widen.  On the TPU the parallel
         BP's default covers the whole gathered line
         (:func:`whole_line_bs`).

Historically these were module constants (``BU``/``BV``); now every call
site resolves a :class:`KernelConfig` through :func:`get_config`:

    1. an explicit per-shape-class entry (``register_config`` or a previous
       autotune run), else
    2. a measured autotune sweep when running on real TPU hardware and
       autotune is enabled (``REPRO_AUTOTUNE=1`` or ``autotune=True``), else
    3. the heuristic table (always used in interpret mode / CPU).

Configs are keyed by a coarse *shape class*, not the exact geometry, so one
sweep serves every geometry of the same regime (e.g. all 2D limited-angle
training shapes share an entry).  The packed cone pair tunes as its own
``"cone-packed"`` regime (its kernel structure is the fan kernel's, not the
exact cone kernel's), and the modular pair as a ``"modular"`` regime with
cone-style heuristics (grid-folded views, rows tiled physically on the v
axis); this module also owns the ``mode="auto"`` dispatch
gate for it (:func:`packed_cone_ok`).  ``KernelConfig`` is frozen/hashable and is
part of the op-cache key in ``repro.kernels.ops`` — passing the same config
therefore reuses the cached (traced) ops instead of retracing.

Measured autotune results additionally persist to disk
(``~/.cache/repro/tune.json``, override the path with
``REPRO_TUNE_CACHE_PATH``), keyed by shape class + jax backend, so servers
skip the warmup sweep on restart.  ``REPRO_TUNE_CACHE=0`` disables the disk
cache entirely (reads and writes).
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import pathlib
import time
import warnings
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.geometry import CTGeometry
from repro.runtime import obs

__all__ = [
    "KernelConfig",
    "shape_class",
    "get_config",
    "resolve_config",
    "register_config",
    "autotune",
    "clear",
    "cache_path",
    "save_tuned",
    "load_tuned",
    "packed_cone_tolerance",
    "packed_cone_ok",
]

LANE = 128          # TPU lane width: the bv axis should be a multiple of this
_SUBLANE = 8
# VMEM budget of the parallel BP's double-buffered f32 output block
# (2 * bs * bg * bv * 4 bytes): caps the whole-line stripe reuse.
BP_OUT_VMEM = 4 << 20


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Tile/block sizes for one (geometry-shape-class, kernel, dtype)."""

    bu: int = 16     # FP detector-column tile (not the exact cone FP)
    bv: int = LANE   # lane tile (packed batch * detector rows)
    ba: int = 1      # FP views per program
    bg: int = 16     # BP (and exact cone FP) gathered-axis tile
    bab: int = 1     # BP views per program
    bs: int = 1      # BP gathered sub-tiles per stripe residency (reuse)

    def __post_init__(self):
        for name in ("bu", "bv", "ba", "bg", "bab", "bs"):
            v = getattr(self, name)
            if not (isinstance(v, int) and v > 0):
                raise ValueError(f"KernelConfig.{name} must be a positive "
                                 f"int, got {v!r}")
        if self.bv % _SUBLANE:
            raise ValueError(
                f"bv must be a multiple of {_SUBLANE}, got {self.bv} "
                f"(use {LANE} for full lane utilization on TPU)")

    def replace(self, **kw) -> "KernelConfig":
        return dataclasses.replace(self, **kw)


# --------------------------------------------------------------------------- #
# Shape classes
# --------------------------------------------------------------------------- #
def _bucket(n: int) -> int:
    """Round up to the next power of two (coarse size bucketing)."""
    return 1 << max(0, int(n - 1).bit_length())


def shape_class(geom: CTGeometry, batch: int = 1,
                dtype=jnp.float32, packed: bool = False) -> Tuple:
    """Coarse key identifying a kernel-tuning regime.

    Buckets the axes that drive tile choice: transaxial volume size, the
    detector-column count, the view count, and the *lane occupancy*
    ``batch * n_rows`` (what actually lands on the 128-wide axis after
    packing).  Exact geometry values (angles, spacings, shifts) do not
    change the optimal tiles and are deliberately excluded.

    ``packed`` marks the lane-packed cone pair (``fp_cone_packed``), whose
    kernel structure — and therefore optimal tiles — is the fan kernel's,
    not the exact cone kernel's; it tunes as its own regime.
    """
    lanes = batch * geom.n_rows
    kind = geom.geom_type + ("-packed" if packed else "")
    return (kind,
            _bucket(max(geom.vol.nx, geom.vol.ny)),
            _bucket(geom.n_cols),
            _bucket(geom.n_angles),
            _bucket(lanes),
            jnp.dtype(dtype).name)


# --------------------------------------------------------------------------- #
# Registry
# --------------------------------------------------------------------------- #
_REGISTRY: Dict[Tuple, KernelConfig] = {}       # explicit + autotuned entries
_AUTOTUNED: Dict[Tuple, KernelConfig] = {}      # measured results only


def sweep_count() -> int:
    """Number of ``autotune`` invocations this process, the counter
    ``tune.sweep`` (warm-path probe: a primed serving instance must answer
    traffic without sweeping)."""
    return obs.counter("tune.sweep")


def register_config(cls_key: Tuple, cfg: KernelConfig) -> None:
    """Pin a config for a shape class (overrides heuristics and autotune)."""
    _REGISTRY[cls_key] = cfg


def clear() -> None:
    """Drop the in-process registries (the disk cache is left untouched)."""
    _REGISTRY.clear()
    _AUTOTUNED.clear()


# --------------------------------------------------------------------------- #
# Disk persistence (measured autotune results survive process restarts)
# --------------------------------------------------------------------------- #
def _disk_cache_enabled() -> bool:
    val = os.environ.get("REPRO_TUNE_CACHE", "1").strip().lower()
    return val not in ("", "0", "false", "no", "off")


def cache_path() -> pathlib.Path:
    """Location of the persisted tune cache (``REPRO_TUNE_CACHE_PATH`` or
    ``~/.cache/repro/tune.json``)."""
    p = os.environ.get("REPRO_TUNE_CACHE_PATH")
    if p:
        return pathlib.Path(p)
    return pathlib.Path.home() / ".cache" / "repro" / "tune.json"


def _disk_key(cls_key: Tuple) -> str:
    # Shape classes are flat tuples of strs/ints; the backend suffix keeps
    # TPU-measured configs from leaking onto other backends (and vice versa).
    return "|".join(str(x) for x in cls_key) + "@" + jax.default_backend()


def save_tuned(cls_key: Tuple, cfg: KernelConfig) -> None:
    """Best-effort persist of a measured config (no-op when disabled)."""
    if not _disk_cache_enabled():
        return
    path = cache_path()
    try:
        data = json.loads(path.read_text()) if path.exists() else {}
        if not isinstance(data, dict):
            data = {}
    except (OSError, ValueError):
        data = {}
    data[_disk_key(cls_key)] = dataclasses.asdict(cfg)
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(path.name + ".tmp")
        tmp.write_text(json.dumps(data, indent=2, sort_keys=True))
        os.replace(tmp, path)                     # atomic vs concurrent readers
    except OSError:
        pass                                      # cache is best-effort only


# Parsed-file memo keyed by (path, mtime_ns): get_config consults the disk
# cache on every registry miss, and without this every eager kernel call
# would re-read + re-parse the JSON file.  A save (here or by another
# process) bumps the mtime and invalidates the memo; a stat per call remains.
_DISK_MEMO: Dict[Tuple[str, int], dict] = {}


def _read_disk_cache() -> dict:
    path = cache_path()
    try:
        mtime = path.stat().st_mtime_ns
    except OSError:
        return {}
    memo_key = (str(path), mtime)
    if memo_key not in _DISK_MEMO:
        try:
            data = json.loads(path.read_text())
        except (OSError, ValueError):
            data = {}
        _DISK_MEMO.clear()                    # keep exactly one file cached
        _DISK_MEMO[memo_key] = data if isinstance(data, dict) else {}
    return _DISK_MEMO[memo_key]


def load_tuned(cls_key: Tuple) -> Optional[KernelConfig]:
    """Read a persisted config for this shape class + backend, or None."""
    if not _disk_cache_enabled():
        return None
    data = _read_disk_cache()
    raw = data.get(_disk_key(cls_key))
    if not isinstance(raw, dict):
        return None
    try:
        return KernelConfig(**{k: int(v) for k, v in raw.items()})
    except (TypeError, ValueError):
        return None                               # stale/foreign schema


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Pallas interpret mode: everywhere but the TPU.  The one switch every
    kernel module reads (as ``tune._interpret()``), so a test that compiles
    for a described chip patches this module and nothing else."""
    return not _on_tpu()


# --------------------------------------------------------------------------- #
# Layout helpers shared by the kernels (Mosaic tiling rules)
# --------------------------------------------------------------------------- #
def round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def sublane_tile(dtype) -> int:
    """Rows of one VMEM tile: 8 for 32-bit data, 16 for packed bf16.  A
    dynamic window along the second-minor axis starts on a multiple of it."""
    return _SUBLANE * 4 // jnp.dtype(dtype).itemsize


def aligned_window(w: int, n: int, align: int) -> int:
    """Static width of a window whose dynamic start is rounded down to a
    multiple of ``align`` (see :func:`window_start`): wide enough to hold
    any ``w``-wide window, capped at the axis length ``n`` (itself a
    multiple of ``align``)."""
    return min(round_up(w, align) + align, n)


def window_start(a, b, w_al: int, n: int, align: int):
    """In-kernel start of a ``w_al``-wide window (from
    :func:`aligned_window`) over an axis of length ``n``, covering the
    footprint whose ends sit at fractional indices ``a`` and ``b`` (f32
    scalars, either order).  The ``w_al - align`` window centred on the
    footprint is clipped into the axis and its start rounded down to
    ``align``, so the ``w_al``-wide slice still holds all of it and Mosaic
    can prove the slice tile-aligned (which it requires of packed bf16)."""
    if w_al >= n:
        return 0
    span = jnp.abs(jnp.ceil(b - a)).astype(jnp.int32)
    lo = jnp.floor(jnp.minimum(a, b)).astype(jnp.int32) - (
        w_al - align - span) // 2
    s = jnp.bitwise_and(jnp.clip(lo, 0, n - w_al), -align)
    return pl.multiple_of(s, align)


def iota_f32(shape, dim: int):
    """Float index ramp along ``dim``.  Mosaic has only an integer iota, so
    it is built in int32 and cast."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim).astype(jnp.float32)


def _autotune_enabled(flag: Optional[bool]) -> bool:
    if flag is not None:
        return flag
    val = os.environ.get("REPRO_AUTOTUNE", "0").strip().lower()
    return val not in ("", "0", "false", "no", "off")


def whole_line_bs(geom: CTGeometry, bg: int, bv: int = LANE) -> int:
    """Stripe reuse over the whole gathered line of either view group
    (``ceil(max(nx, ny) / bg)`` sub-tiles), so the parallel BP fetches each
    sinogram stripe once per (loop line, lane block, view block).  Capped
    so the double-buffered f32 output block stays within ``BP_OUT_VMEM``."""
    line = -(-max(geom.vol.nx, geom.vol.ny) // bg)
    return max(1, min(line, BP_OUT_VMEM // (2 * bg * bv * 4)))


def heuristic_config(geom: CTGeometry, batch: int = 1,
                     dtype=jnp.float32, packed: bool = False) -> KernelConfig:
    """Static table used off-TPU and as the autotune fallback/seed."""
    nu = geom.n_cols
    na = geom.n_angles
    # Column tile: big enough to keep the MXU sublane axis busy, small
    # enough that the gathered-axis window (which grows ~linearly in bu)
    # stays comfortably inside VMEM.
    bu = 8 if nu <= 16 else (16 if nu <= 512 else 32)
    bv = LANE
    if geom.geom_type == "cone" and packed:
        # The packed cone pair IS the fan kernel (the axial part is
        # pre-resampled outside): fan tiles, full 128-lane packing.
        bu = max(8, bu // 2)
    elif geom.geom_type in ("cone", "modular"):
        # The modular FP's gathered-axis window W grows with bu and is
        # walked by an inner loop — keep the column tile small.  The exact
        # cone pair takes bg = bu below for both of its kernels.
        bu = 8
        # Cone/modular kernels tile *physical* detector rows on the v axis
        # (no lane packing; the BP's lane axis is z) — pad rows to the
        # sublane multiple instead of a full 128-lane tile.
        bv = min(round_up(max(geom.n_rows, 1), _SUBLANE), LANE)
    elif geom.geom_type == "fan":
        # Fan is lane-packed like parallel, but its gathered-axis window is
        # magnified by sdd/(sod - R) — halve the column tile so the W-wide
        # VMEM window stays comparable to the parallel kernel's.
        bu = max(8, bu // 2)
    bg = bu
    # Stripe reuse only exists in the lane-packed BP kernels (parallel,
    # fan, packed cone); the view-folded cone/modular BPs ignore it.
    lane_packed_bp = geom.geom_type in ("parallel", "fan") or packed
    if _on_tpu():
        # View blocking amortizes the dominant HBM stream (volume line for
        # FP, sinogram stripe for BP); diminishing returns past ~8.
        ba = min(8 if na >= 8 else max(1, na), na)
        bab = min(4, na)
        # The parallel BP's stripe serves its whole gathered line; the fan
        # (and packed cone) BP widens its window with the magnification and
        # keeps two sub-tiles per stripe.  Autotune sweeps 1/2/4 too.
        if geom.geom_type == "parallel":
            bs = whole_line_bs(geom, bg, bv)
        else:
            bs = 2 if lane_packed_bp else 1
    else:
        # Interpret mode executes the per-view python loop serially — keep
        # programs minimal so correctness tests stay fast.
        ba = 1
        bab = 1
        bs = 1
    return KernelConfig(bu=bu, bv=bv, ba=ba, bg=bg, bab=bab, bs=bs)


def get_config(geom: CTGeometry, batch: int = 1, dtype=jnp.float32,
               autotune_flag: Optional[bool] = None,
               packed: bool = False) -> KernelConfig:
    """Resolve the config for ``geom`` (see module docstring for the order)."""
    key = shape_class(geom, batch, dtype, packed)
    if key in _REGISTRY:
        return _REGISTRY[key]
    if key in _AUTOTUNED:
        return _AUTOTUNED[key]
    disk = load_tuned(key)
    if disk is not None:                  # persisted measurement: skip sweep
        _AUTOTUNED[key] = disk
        return disk
    if _on_tpu() and _autotune_enabled(autotune_flag):
        return autotune(geom, batch=batch, dtype=dtype, packed=packed)
    return heuristic_config(geom, batch, dtype, packed)


@obs.spanned("tune.resolve_config")
def resolve_config(geom: CTGeometry, batch: int,
                   config: Optional[KernelConfig],
                   dtype=jnp.float32, packed: bool = False,
                   **overrides) -> KernelConfig:
    """Shared entry-point resolution: an explicit ``config`` wins, else the
    registry/heuristics via :func:`get_config` (keyed on the input dtype);
    non-None keyword overrides (e.g. a caller's ``bu=8``) are applied last."""
    cfg = config if config is not None \
        else get_config(geom, batch=batch, dtype=dtype, packed=packed)
    kw = {k: v for k, v in overrides.items() if v is not None}
    return cfg.replace(**kw) if kw else cfg


# --------------------------------------------------------------------------- #
# Packed-cone dispatch gate
# --------------------------------------------------------------------------- #
# Default ceiling on the packed approximation's worst-case axial footprint
# displacement (detector rows).  A quarter row keeps the documented relative
# error bound (2x the shift + the second-order obliquity term, see
# fp_cone.cone_packed_error_bound) comfortably below typical detector noise.
PACKED_CONE_DEFAULT_TOL = 0.25


def packed_cone_tolerance() -> float:
    """Row-shift ceiling for ``mode="auto"`` packed-cone dispatch
    (``REPRO_PACKED_CONE_TOL`` overrides the default)."""
    val = os.environ.get("REPRO_PACKED_CONE_TOL", "").strip()
    if val:
        try:
            return float(val)
        except ValueError:
            # A typo'd tolerance silently falling back to the default would
            # dispatch approximate kernels at a looser gate than the user
            # asked for — make the misconfiguration loud instead.
            raise ValueError(
                f"REPRO_PACKED_CONE_TOL={val!r} is not a float") from None
    return PACKED_CONE_DEFAULT_TOL


def packed_cone_ok(geom: CTGeometry) -> bool:
    """True when the packed (lane-packed, axial pre-resample) cone pair is
    within tolerance for this geometry — the ``mode="auto"`` gate."""
    if geom.geom_type != "cone" or geom.detector_type != "flat":
        return False
    from repro.kernels import fp_cone                 # late: avoid cycle
    return fp_cone.cone_packed_row_shift(geom) <= packed_cone_tolerance()


# --------------------------------------------------------------------------- #
# Autotuner
# --------------------------------------------------------------------------- #
def _time_call(fn, *args, reps: int = 3) -> float:
    fn = jax.jit(fn)        # measure the fused program production runs
    out = fn(*args)                                   # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _sweep(fn, geom: CTGeometry, x, reps: int, what: str, grid: Dict):
    """Time ``fn`` under every candidate config of ``grid`` (knob tuple ->
    KernelConfig) and return the fastest knob tuple.  A candidate the chip
    refuses (a tiling Mosaic rejects, a window past VMEM) is reported as a
    warning and skipped; a sweep in which every candidate fails raises."""
    best, t_best, errors = None, float("inf"), []
    for knobs, cfg in grid.items():
        try:
            t = _time_call(lambda a: fn(a, geom, config=cfg), x, reps=reps)
        except Exception as e:                        # noqa: BLE001
            errors.append(f"{cfg}: {type(e).__name__}: {e}")
            warnings.warn(f"autotune {geom.geom_type} {what}: candidate "
                          f"{errors[-1]}", RuntimeWarning, stacklevel=2)
            continue
        if t < t_best:
            best, t_best = knobs, t
    if best is None:
        raise RuntimeError(f"autotune {geom.geom_type} {what}: every "
                           f"candidate failed:\n" + "\n".join(errors))
    return best


def default_candidates(geom: CTGeometry) -> Iterable[KernelConfig]:
    """The measured sweep grid: small, but covers the axes that matter."""
    na = geom.n_angles
    bus = [b for b in (8, 16, 32) if b <= max(_SUBLANE, geom.n_cols * 2)]
    bas = sorted({min(b, na) for b in (1, 2, 4, 8)})
    bgs = [8, 16, 32]
    babs = sorted({min(b, na) for b in (1, 2, 4)})
    for bu, ba in itertools.product(bus, bas):
        for bg, bab in itertools.product(bgs, babs):
            bss = {1, 2, 4}               # BP stripe-reuse blocking factors
            if geom.geom_type == "parallel":
                bss.add(whole_line_bs(geom, bg))
            for bs in sorted(bss):
                yield KernelConfig(bu=bu, bv=LANE, ba=ba, bg=bg, bab=bab,
                                   bs=bs)


def autotune(geom: CTGeometry, batch: int = 1, dtype=jnp.float32,
             candidates: Optional[Iterable[KernelConfig]] = None,
             reps: int = 3, packed: bool = False) -> KernelConfig:
    """Measure candidate configs with the real kernels and cache the winner.

    Only meaningful on TPU (interpret-mode timings reflect the Python
    interpreter, not the hardware); elsewhere this returns the heuristic
    without measuring.  FP and BP are timed independently and the best
    (bu, ba) is combined with the best (bg, bab).
    """
    obs.count("tune.sweep")
    key = shape_class(geom, batch, dtype, packed)
    if not _on_tpu():
        cfg = heuristic_config(geom, batch, dtype, packed)
        _AUTOTUNED[key] = cfg
        return cfg

    from repro.kernels import fp_par                  # late: avoid cycle

    cand = list(candidates) if candidates is not None \
        else list(default_candidates(geom))
    if geom.geom_type == "cone" and packed:
        # The packed cone pair is structurally the fan kernel (lane-packed,
        # view-blocked) — run the same full fp/bp sweep on its entry points.
        from repro.kernels import fp_cone
        fp_fn, bp_fn = fp_cone.fp_cone_packed, fp_cone.bp_cone_packed
    elif geom.geom_type == "cone":
        # The exact cone FP is the BP's transpose and shares its gathered
        # tile bg (views fold into the grid, no FP view block): sweep
        # (bg, bab) on the pair.
        return _autotune_cone(geom, batch, dtype, cand, reps, key)
    elif geom.geom_type == "modular":
        # Modular folds views into the grid like the exact cone pair, but
        # its FP gathers a window per column tile: sweep FP-bu x
        # BP-(bg, bab) on the modular entry points.
        from repro.kernels import fp_modular
        return _autotune_viewfold(geom, batch, dtype, cand, reps, key,
                                  fp_modular.fp_modular_sf_pallas,
                                  fp_modular.bp_modular_sf_pallas)
    elif geom.geom_type == "fan":
        # Fan is Pallas end to end like parallel: same full fp/bp sweep.
        from repro.kernels import fp_fan
        fp_fn, bp_fn = fp_fan.fp_fan_sf_pallas, fp_fan.bp_fan_sf_pallas
    elif geom.geom_type == "parallel":
        fp_fn, bp_fn = fp_par.fp_parallel_sf_pallas, fp_par.bp_parallel_sf_pallas
    else:
        cfg = heuristic_config(geom, batch, dtype)
        _AUTOTUNED[key] = cfg
        return cfg
    fp_grid = sorted({(c.bu, c.ba) for c in cand})
    bp_grid = sorted({(c.bg, c.bab, c.bs) for c in cand})

    f, y = _probe_inputs(geom, batch, dtype)
    bu, ba = _sweep(fp_fn, geom, f, reps, "FP",
                    {(bu, ba): KernelConfig(bu=bu, ba=ba)
                     for bu, ba in fp_grid})
    bg, bab, bs = _sweep(bp_fn, geom, y, reps, "BP",
                         {(bg, bab, bs): KernelConfig(bg=bg, bab=bab, bs=bs)
                          for bg, bab, bs in bp_grid})
    cfg = KernelConfig(bu=bu, ba=ba, bg=bg, bab=bab, bs=bs)
    _AUTOTUNED[key] = cfg
    save_tuned(key, cfg)
    return cfg


def _probe_inputs(geom: CTGeometry, batch: int, dtype):
    """A volume and a sinogram of ones (batched where ``batch > 1``) to
    time candidate configs on."""
    lead = (batch,) if batch > 1 else ()
    return (jnp.ones(lead + geom.vol.shape, dtype),
            jnp.ones(lead + geom.sino_shape, dtype))


def _autotune_cone(geom: CTGeometry, batch: int, dtype, cand, reps: int,
                   key: Tuple) -> KernelConfig:
    """Sweep for the exact cone pair: its FP is the BP's transpose and
    tiles the gathered axis by the same ``bg``, so each (bg, bab) candidate
    is timed on FP and BP together.  ``bv`` stays on the heuristic, as in
    :func:`_autotune_viewfold`."""
    from repro.kernels import fp_cone                 # late: avoid cycle

    def pair(xy, geom, config):
        return (fp_cone.fp_cone_sf_pallas(xy[0], geom, config=config),
                fp_cone.bp_cone_sf_pallas(xy[1], geom, config=config))

    base = heuristic_config(geom, batch, dtype)
    bg, bab = _sweep(pair, geom, _probe_inputs(geom, batch, dtype), reps,
                     "FP+BP",
                     {(bg, bab): base.replace(bg=bg, bab=bab)
                      for bg, bab in sorted({(c.bg, c.bab) for c in cand})})
    cfg = base.replace(ba=1, bg=bg, bab=bab)
    _AUTOTUNED[key] = cfg
    save_tuned(key, cfg)
    return cfg


def _autotune_viewfold(geom: CTGeometry, batch: int, dtype, cand, reps: int,
                       key: Tuple, fp_fn, bp_fn) -> KernelConfig:
    """Sweep for the modular pair's grid-folded-view kernels: FP column
    tile (bu) + BP gathered tile / view block (bg, bab), mirroring the
    fan/parallel sweep.  The row tile bv stays on the heuristic (it
    tiles physical detector rows, whose count the shape class already
    encodes); there is no FP ``ba`` knob — views fold into the grid — and
    ``bs`` is not swept (the view-folded BPs ignore stripe blocking)."""
    base = heuristic_config(geom, batch, dtype)
    f, y = _probe_inputs(geom, batch, dtype)
    bu = _sweep(fp_fn, geom, f, reps, "FP",
                {bu: base.replace(bu=bu, ba=1)
                 for bu in sorted({c.bu for c in cand})})
    bg, bab = _sweep(bp_fn, geom, y, reps, "BP",
                     {(bg, bab): base.replace(bg=bg, bab=bab)
                      for bg, bab in sorted({(c.bg, c.bab) for c in cand})})
    cfg = base.replace(bu=bu, ba=1, bg=bg, bab=bab)
    _AUTOTUNED[key] = cfg
    save_tuned(key, cfg)
    return cfg
